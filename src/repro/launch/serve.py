"""Serving launcher: batched requests through the paged continuous-batching
serving stack (engine replicas behind the least-loaded router), optionally
spread over multiple OS-process localities.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen25_3b --smoke \
      --requests 8 --max-new 16 --engines 2 --temperature 0.8 --top-k 40
  PYTHONPATH=src python -m repro.launch.serve --arch qwen25_3b --smoke \
      --requests 12 --max-new 16 --localities 2
  PYTHONPATH=src python -m repro.launch.serve --arch qwen25_3b --smoke \
      --requests 24 --max-new 16 --localities 3 --fleet --slo --stream
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--plan", default="serve")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--workers", type=int, default=4)
    # routing layer
    ap.add_argument("--engines", type=int, default=1,
                    help="engine replicas behind the least-loaded router "
                         "(single-locality mode)")
    ap.add_argument("--localities", type=int, default=1,
                    help="OS-process localities; >1 bootstraps repro.net "
                         "and runs one engine per locality")
    # cache layer
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--no-paged", action="store_true",
                    help="dense per-slot cache instead of the block pool")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="seed-style inline prefill (the barrier baseline)")
    # sampling / streaming
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--stream", action="store_true",
                    help="consume tokens via per-request channels (crosses "
                         "localities through the token relay)")
    # fleet control plane
    ap.add_argument("--fleet", action="store_true",
                    help="run the adaptive control plane on locality 0: "
                         "counter sweeps -> policies -> actuators, plus "
                         "gated-batch release each tick (needs "
                         "--localities > 1)")
    ap.add_argument("--slo", action="store_true",
                    help="SLO tiers: first remote engine pinned interactive,"
                         " the rest batch; batch admission gated on gossiped"
                         " KV-page occupancy (hysteresis 0.85/0.60)")
    ap.add_argument("--slo-mix", type=float, default=0.25, metavar="FRAC",
                    help="fraction of requests submitted interactive when "
                         "--slo is on (default 0.25)")
    # observability
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record a fleet-wide task/parcel trace and write "
                         "one merged Chrome trace JSON (Perfetto-loadable)")
    ap.add_argument("--print-counters", metavar="PATTERN", default=None,
                    help="end-of-run fleet counter report (HPX "
                         "--hpx:print-counter parity), e.g. '/serve*'")
    ap.add_argument("--slow-report", action="store_true",
                    help="after --trace export, run the critical-path "
                         "analyzer and print the per-tier SLOW blame "
                         "report (python -m repro.obs.analyze parity)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve an OpenMetrics /metrics endpoint from "
                         "locality 0 (0 = ephemeral port); every scrape "
                         "sweeps the fleet's counters live")
    ap.add_argument("--timeline", metavar="PATH", default=None,
                    help="persist a JSONL counter timeline (bounded by "
                         "stride-doubling downsample); summarize later "
                         "with python -m repro.obs.analyze --timeline")
    ap.add_argument("--flight-recorder", metavar="PREFIX", default=None,
                    help="arm the anomaly flight recorder on the fleet "
                         "controller: always-on rings + dump_trace trigger "
                         "rules, anomaly traces written to "
                         "results/PREFIX-N.json (needs --fleet)")
    args = ap.parse_args()
    if args.slow_report and not args.trace:
        ap.error("--slow-report needs --trace PATH (it analyzes the "
                 "exported merged trace)")
    if args.flight_recorder and not args.fleet:
        ap.error("--flight-recorder needs --fleet (the controller's tick "
                 "evaluates the trigger rules)")
    if (args.fleet or args.slo) and args.localities < 2:
        ap.error("--fleet/--slo need --localities > 1 (the control plane "
                 "manages remote engines)")
    if args.slo:
        args.fleet = True  # the gate needs the controller's release tick
    if args.localities > 1 and args.engines != 1:
        ap.error("--engines is single-locality replication; with "
                 "--localities N the topology is one engine per locality")

    import repro.core as core
    from repro.configs import get_config
    from repro.dist.plan import get_plan
    from repro.models.model import build_model
    from repro.serve.engine import SamplingParams, ServeConfig
    from repro.serve.router import Router, default_extra_inputs
    from repro.launch import compile_cache

    compile_cache.enable()

    # Resource partition: decode continuations on "default", prefill on its
    # own pool, host I/O (logging/ckpt/parcel pumps) on "io" — capacity goes
    # where the work is, and I/O can never stall a decode step.
    pools = {"default": args.workers, "prefill": 2, "io": 1}
    core.init(pools=pools)
    cfg = get_config(args.arch, smoke=args.smoke)

    scfg = ServeConfig(max_batch=args.max_batch, cache_len=args.cache_len,
                       max_new_tokens=args.max_new, page_size=args.page_size,
                       paged=not args.no_paged,
                       pipeline_admission=not args.no_pipeline)
    net = None
    if args.localities > 1:
        from repro import net as rnet

        net = rnet.bootstrap(args.localities, pools=pools, worker_pools=pools)
        if args.trace:
            from repro.obs import export as obs_export

            obs_export.enable_fleet(net)
        router = Router.over_localities(net, args.arch, scfg,
                                        smoke=args.smoke, plan=args.plan)
    else:
        if args.trace:
            from repro.obs import trace as obs_trace

            obs_trace.enable()
        model = build_model(cfg, get_plan(args.plan))
        params = model.init(jax.random.PRNGKey(0))
        router = Router.replicate(model, params, scfg, args.engines,
                                  extra_inputs=default_extra_inputs(cfg))
    controller = None
    if args.slo:
        from repro.fleet import BATCH, INTERACTIVE, AdmissionController

        from repro.serve.router import RemoteEngine

        # first remote engine serves the latency tier, the rest take batch;
        # batch admission rides the occupancy gossip on completion parcels
        remote = [e for e in router.engines if isinstance(e, RemoteEngine)]
        for i, e in enumerate(remote):
            router.set_tier(e.name, INTERACTIVE if i == 0 else BATCH)
        AdmissionController.for_router(router, high=0.85, low=0.60)
    recorder = None
    if args.fleet:
        from repro.fleet import FleetController

        controller = FleetController(net, router, interval=0.25)
        if args.flight_recorder:
            from repro.obs.recorder import FlightRecorder

            recorder = FlightRecorder(net, prefix=args.flight_recorder)
            recorder.start()  # always-on rings, fleet-wide
            recorder.install(controller, p99_high=5.0)
        controller.start()
    exporter = None
    if args.metrics_port is not None:
        from repro.obs.metrics import MetricsExporter

        exporter = MetricsExporter(net=net, port=args.metrics_port).start()
        print(f"metrics: {exporter.url}", flush=True)
    timeline = None
    tl_sampler = None
    if args.timeline:
        from repro.obs.sampler import FleetSampler
        from repro.obs.timeseries import TimelineWriter

        timeline = TimelineWriter(args.timeline, pattern="*", interval=0.25,
                                  meta={"launcher": "serve",
                                        "arch": args.arch})
        if controller is not None:
            # ride the control plane's sweep — one sampler, two consumers
            controller.sampler.timeline = timeline
        else:
            tl_sampler = FleetSampler(pattern="*", interval=0.25, net=net,
                                      timeline=timeline)
            tl_sampler.sample_once()  # t=0 baseline record
            tl_sampler.start()
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    def _slo_for(i: int):
        if not args.slo:
            return None
        from repro.fleet import BATCH, INTERACTIVE

        return INTERACTIVE if (i % max(round(1 / max(args.slo_mix, 1e-9)), 1)
                               == 0) else BATCH

    if args.stream:
        streams = []
        for i in range(args.requests):
            prompt = rng.integers(1, cfg.vocab_size, size=rng.integers(4, 32)).tolist()
            streams.append(router.submit_stream(prompt, sampling=sampling,
                                                slo=_slo_for(i)))
        outs = []
        for ch, fut in streams:
            toks = list(ch)  # arrives token-by-token as slots advance
            outs.append(fut.get(timeout=600))
            assert toks == outs[-1]
    else:
        futures = []
        for i in range(args.requests):
            prompt = rng.integers(1, cfg.vocab_size, size=rng.integers(4, 32)).tolist()
            futures.append(router.submit(prompt, sampling=sampling,
                                         slo=_slo_for(i)))
        outs = [f.get(timeout=600) for f in futures]
    if controller is not None:
        controller.tick()  # final release sweep before measuring
        controller.stop()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(o) for o in outs)
    report = {
        "requests": len(outs),
        "engines": len(router.engines),
        "localities": args.localities,
        "generated_tokens": total_tokens,
        "wall_s": round(dt, 3),
        "tokens_per_s": round(total_tokens / dt, 2),
        "counters": dict(core.counters.query("/serve*")),
    }
    if net is not None:
        from repro import net as rnet

        # per-locality serving counters, read across the parcelport
        report["per_locality_tokens"] = {
            f"locality#{loc}": dict(rnet.query_counters(
                loc, "/serve{engine*}/tokens/generated"))
            for loc in range(args.localities)
        }
    if args.trace:
        from repro.obs import export as obs_export

        tr = obs_export.export_chrome_trace(args.trace, net=net)
        report["trace"] = {"path": args.trace,
                           "events": len(tr["traceEvents"])}
        if args.slow_report:
            from repro.obs import attribution as obs_attr

            rep = obs_attr.slow_report(tr)
            print(obs_attr.format_report(rep))
            report["slow_report"] = {"requests": rep["requests"],
                                     "tiers": sorted(rep["tiers"])}
    if recorder is not None:
        report["flight_recorder"] = {
            "dumps": int(recorder.c_dumps.get_value()),
            "last": recorder.last_path,
        }
        recorder.stop()
    if args.print_counters:
        from repro.obs import sampler as obs_sampler

        obs_sampler.print_counter_report(args.print_counters, net=net)
    if timeline is not None:
        if tl_sampler is not None:
            tl_sampler.stop()
            tl_sampler.sample_once()  # end-of-run record (≥2 guaranteed)
        timeline.close()
        report["timeline"] = {"path": args.timeline,
                              "records": timeline.records_written,
                              "stride": timeline.stride}
    if exporter is not None:
        report["metrics_url"] = exporter.url
        exporter.close()
    if net is not None:
        net.shutdown()
    print(json.dumps(report, indent=1))
    core.finalize()


if __name__ == "__main__":
    main()
