"""repro.obs — APEX-style observability for the distributed runtime.

Two tiers (see DESIGN.md §10):

**Recording** —

- :mod:`repro.obs.trace`   — per-thread ring-buffer task/parcel tracer,
  off by default, near-zero disabled cost; its ``serve``/``train`` spans
  are also JAX profiler annotations, on the device trace's clock;
- :mod:`repro.obs.export`  — fleet trace collection over the parcelport,
  clock-corrected, merged into one Perfetto-loadable Chrome trace;
- :mod:`repro.obs.sampler` — counter time-series (histories, rates) and
  the ``--print-counters`` fleet report.

**Export** (ISSUE 10) —

- :mod:`repro.obs.metrics`    — OpenMetrics/Prometheus text exposition
  of the fleet counter tree (the listener lives in ``repro.net.httpd``);
- :mod:`repro.obs.timeseries` — append-only JSONL counter timelines,
  bounded by stride-doubling downsample;
- :mod:`repro.obs.top`        — the ``python -m repro.obs.top`` live
  fleet dashboard ("hpx-top").

**Analysis** (ISSUE 9) —

- :mod:`repro.obs.critical_path` — per-request dependency-path
  reconstruction with SLOW-taxonomy interval blame;
- :mod:`repro.obs.attribution`   — aggregate per-tier reports, folded
  into live histogram counters;
- :mod:`repro.obs.recorder`      — anomaly-triggered fleet flight
  recorder (controller-driven ``dump_trace`` actuator);
- :mod:`repro.obs.analyze`       — the ``python -m repro.obs.analyze``
  CLI.

Only :mod:`trace` is imported eagerly: it is a leaf the core runtime
instruments, so this package must never pull in the net tier at import
time (everything else loads on first attribute access).
"""

from repro.obs import trace  # noqa: F401 — the leaf recorder

__all__ = ["trace", "export", "sampler", "critical_path", "attribution",
           "recorder", "analyze", "metrics", "timeseries", "top"]

_LAZY = ("export", "sampler", "critical_path", "attribution", "recorder",
         "analyze", "metrics", "timeseries", "top")


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
