"""One run of one benchmark cell, on the chip it is started on.

    python3 benchmarks/chip/run_cell.py --workload qwen25_3b.decode_long \\
        --seed 1234 --seconds 10 --trace 0

Loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, draws the weights and requests from ``--seed``, warms up
every shape the traffic reaches, measures for ``--seconds``, checks what
was served against the plain reference, and prints one JSON line last on
standard output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace of a few
seconds in the middle of the window.  A run that finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``peaks.json`` exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE / "traffic"), str(HERE.parents[1] / "src")]

import manifest  # noqa: E402

OUT = manifest.ROOT / ".bench_out"
TRACE_S = 4.0   # traced seconds, in the middle of the window
GRACE_S = 60.0  # how long past the close answers due in the window may take
PAGE_SAMPLE_S = 0.02


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def configure_jax() -> None:
    """Persistent compilation cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program written to it, keyed
    with its debug info, so that a program loaded from the cache carries
    the op names (named scopes) of the source that runs; the TPU runtime's
    logs inside the checkout too (not /tmp/tpu_logs).  Call before JAX
    first touches a device."""
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(manifest.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def check_device(chips: int):
    """The chips this run uses and their peaks; exits without a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"{chips} chips asked for, {len(devs)} found")
    peaks = manifest.load_json(manifest.HERE / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return devs[:chips], peaks[kind]


class CompileCounter:
    """Programs compiled or loaded from the cache, from JAX's monitoring."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.names = []

        def on_event(event: str, secs: float, **kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs
                self.names.append(kw.get("fun_name", "?"))

        jax.monitoring.register_event_duration_secs_listener(on_event)


class _Tracer(threading.Thread):
    """Starts the profiler ``offset`` seconds after the window opens and
    stops it ``TRACE_S`` later (a thread of its own, so the client never
    waits for the profiler)."""

    def __init__(self, path: Path, offset: float, length: float):
        super().__init__(daemon=True)
        self.path, self.offset, self.length = path, offset, length
        self.t0 = threading.Event()
        self.open_t = 0.0
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        import jax

        try:
            self.t0.wait()
            time.sleep(max(0.0, self.open_t + self.offset - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host annotations only
            jax.profiler.start_trace(str(self.path), profiler_options=opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — reported by the run
            self.error = e


class _PageSampler(threading.Thread):
    """Samples the page gauges, and leaves a clock mark in the trace at
    each sample, which ties the profiler's clock to the host's."""

    def __init__(self, system):
        super().__init__(daemon=True)
        self.system = system
        self.samples = []
        self.marks = []
        self.stop = threading.Event()

    def run(self) -> None:
        import jax

        from trace_reduce import MARK

        while not self.stop.wait(PAGE_SAMPLE_S):
            with jax.profiler.TraceAnnotation(f"{MARK}#{len(self.marks)}"):
                t = time.perf_counter()
            self.marks.append(t)
            self.samples.append((t, *self.system.pages()))


def _trace_offset(trace, marks) -> Optional[float]:
    """Profiler clock minus host clock, from the clock marks."""
    from trace_reduce import MARK

    pairs = [(s, marks[int(n.rsplit("#", 1)[1])])
             for s, _, n in trace.annotations(rf"^{MARK}#\d+$")]
    if not pairs:
        return None
    return statistics.median(s - t * 1e9 for s, t in pairs)


def run(cell: dict, conf: dict, mix: dict, seed: int, seconds: float,
        trace: bool, peaks: dict, devices, metrics: list,
        limits: dict, compiles: CompileCounter, t_start: float = T_START,
        fault: Optional[str] = None, control: Optional[str] = None) -> dict:
    """One run; returns the result line (a dict).  ``fault`` names a fault
    of ``faults.py`` to plant in the timed path (tests, ``control.py``);
    ``control`` names a control of ``reference.CONTROLS`` to judge on the
    same sample (``control.py``, the tests)."""
    import correctness
    import faults
    import records
    import system as system_mod
    import trace_reduce
    from model_spec import from_config

    from traffic_common import reachable_lengths

    m = from_config(conf["name"], conf)
    gen = manifest.generator(mix["kind"])
    requests = gen.generate(mix, seed, seconds, m.vocab)
    t_build = time.perf_counter()
    sut = system_mod.System(conf, m, seed,
                            fault=faults.FAULTS[fault] if fault else None)
    t_built = time.perf_counter()
    state = {}
    warm = sut.warm_up(reachable_lengths(mix["prompt"]), seed)

    def warmed() -> bool:
        bad = [r.failed for r in warm if r.failed]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
        if all(r.done_t for r in warm):
            state.setdefault("warm_s", time.perf_counter() - t_built)
            return True
        return False

    tracer = sampler = None
    if trace:
        tdir = OUT / "trace" / cell["name"]
        shutil.rmtree(tdir, ignore_errors=True)
        length = min(TRACE_S, seconds)
        tracer = _Tracer(tdir, max(0.0, (seconds - length) / 2), length)
        tracer.start()
        sampler = _PageSampler(sut)

    def on_open(t0: float) -> None:
        state["counters0"] = sut.counters()
        state["compiles0"] = compiles.count
        if tracer is not None:
            tracer.open_t = t0
            tracer.t0.set()
            sampler.start()

    t0 = gen.run(mix, sut.submit, requests, seconds, warmed, on_open)
    t1 = t0 + seconds
    counters1 = sut.counters()
    in_window = compiles.count - state["compiles0"]
    if sampler is not None:
        sampler.stop.set()
        sampler.join()
    due = [r for r in requests if t0 <= r.sent_t < t1]
    deadline = t1 + GRACE_S
    while time.perf_counter() < deadline:
        finished = sum(len(r.tokens) for r in requests if r.finished)
        if (all(r.done_t for r in due)
                and (finished >= correctness.SAMPLE_TOKENS
                     or all(r.done_t for r in requests if r.sent_t))):
            break
        time.sleep(0.05)
    unanswered = sum(1 for r in due if not r.done_t)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    if tracer is not None:
        tracer.join()
        if tracer.error is not None:
            raise tracer.error
    sut.close()
    del sut
    gc.collect()
    in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                 for d in devices)

    tr = offset = None
    if trace:
        tr = trace_reduce.reduce(trace_reduce.find_xplane(str(tdir)))
        offset = _trace_offset(tr, sampler.marks)
    rec = records.Run(
        workload=cell["name"], model=m, serve=conf["system"]["serve"],
        peaks=peaks, seconds=seconds, requests=requests, t0=t0, t1=t1,
        setup_s=t0 - t_start,
        counters0=state["counters0"], counters1=counters1,
        pages=sampler.samples if sampler else [], trace=tr,
        trace_offset_ns=offset)
    values = {}
    for spec in metrics:
        v = manifest.reader(spec["name"]).read(rec)
        if v is not None:
            values[spec["name"]] = {"value": float(v), "unit": spec["unit"]}

    t_ref = time.perf_counter()
    picked = correctness.sample(requests, seed)
    res = correctness.gaps(m, seed, picked, control=control)
    checks = correctness.checks(limits, picked, res, requests, unanswered)
    t_ref_end = time.perf_counter()

    attempted = sum(1 for r in requests
                    if (t0 <= r.sent_t < t1) or any(t0 <= t < t1 for t in r.token_t))
    failed = sum(1 for r in requests if r.failed)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": attempted, "failed": failed, "metrics": values,
           "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    stats = correctness.summary([g["gap"] for g in res])
    if control:
        ctl = correctness.checks(
            limits, picked, [{"gap": g["control_gap"]} for g in res],
            requests, unanswered)
        out["control"] = {
            "name": control, "correct": all(c["ok"] for c in ctl.values()),
            "checks": {k: {"value": c["value"], "limit": c["limit"]}
                       for k, c in ctl.items()},
            "stats": correctness.summary([g["control_gap"] for g in res])}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    log(f"set-up: start {t_build - t_start:.3f} s, model and weights "
        f"{t_built - t_build:.3f} s, {len(warm)} warm-up requests answered "
        f"after {state['warm_s']:.3f} s, the steady state after "
        f"{t0 - t_built:.3f} s; programs compiled or loaded before the "
        f"window {state['compiles0']} in {compiles.seconds:.3f} s")
    log(f"compiles_in_window {in_window} "
        f"{compiles.names[state['compiles0']:][:in_window][:8]}")
    log(f"device bytes in use after the system closed {in_use}; "
        f"reference {t_ref_end - t_ref:.3f} s")
    log(f"requests: {len(requests)} generated, {attempted} attempted, "
        f"{failed} failed, {len(picked)} compared")
    log(f"gap statistics {json.dumps(stats)}")
    if control:
        log(f"control {control}: correct {out['control']['correct']}, "
            f"gap statistics {json.dumps(out['control']['stats'])}")
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']} "
            f"{'ok' if c['ok'] else 'FAIL'}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = manifest.benchmark()
    cell = manifest.workload(bench, args.workload)
    conf = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    limits = manifest.limits(cell["name"])
    metrics = manifest.metrics_for(bench, cell["name"], bool(args.trace))
    for spec in metrics:
        manifest.reader(spec["name"])  # a missing reader fails before the run
    manifest.family(conf["model_type"])  # so does a missing family
    configure_jax()
    devices, peaks = check_device(int(cell["chips"]))
    import repro.core as core

    compiles = CompileCounter()
    core.init(pools={"default": 2, "prefill": 2, "io": 1})
    try:
        out = run(cell, conf, mix, args.seed, args.seconds, bool(args.trace),
                  peaks, devices, metrics, limits, compiles)
    finally:
        core.finalize()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
