"""Training launcher.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen25_3b --smoke \
      --steps 100 --batch 8 --seq 128 --plan futurized
  PYTHONPATH=src python -m repro.launch.train --arch mamba2_780m --smoke \
      --steps 50 --ckpt-every 20 --ckpt-dir /tmp/ck

Full (non ``--smoke``) configs are for real accelerator fleets; on this CPU
container use ``--smoke`` (reduced same-family config) or the dry-run
(``repro.launch.dryrun``) for the production shapes.
"""

from __future__ import annotations

import argparse
import json

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--plan", default="futurized")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--scheduler", default="local",
                    choices=("static", "local", "hierarchical"))
    ap.add_argument("--localities", type=int, default=1,
                    help="multi-locality runtime: N OS processes")
    ap.add_argument("--sharded-rows", type=int, default=0,
                    help="locality-sharded dataset of this many token rows "
                         "(synthesized in place at each owning locality); "
                         "the trainer feeds from locality 0's segments")
    # observability
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record a fleet-wide task/parcel trace and write "
                         "one merged Chrome trace JSON (Perfetto-loadable)")
    ap.add_argument("--print-counters", metavar="PATTERN", default=None,
                    help="end-of-run fleet counter report (HPX "
                         "--hpx:print-counter parity), e.g. '/train*'")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve an OpenMetrics /metrics endpoint from "
                         "locality 0 (0 = ephemeral port)")
    ap.add_argument("--timeline", metavar="PATH", default=None,
                    help="persist a JSONL counter timeline; summarize with "
                         "python -m repro.obs.analyze --timeline")
    args = ap.parse_args()

    import contextlib

    import repro.core as core
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, ShardedTokenDataset
    from repro.dist.plan import get_plan
    from repro.models.model import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig, Trainer
    from repro.launch import compile_cache

    compile_cache.enable()

    # Resource partition: compute-plane tasks on "default", prefetch
    # assembly + checkpoint writes on the single-worker "io" pool.  A
    # sharded dataset needs the net runtime even at one locality.
    pools = {"default": args.workers, "io": 1}
    if args.localities > 1 or args.sharded_rows > 0:
        if args.scheduler != "local":
            ap.error("--scheduler is not supported together with "
                     "--localities/--sharded-rows (the multi-locality "
                     "bootstrap brings up the default scheduler)")
        from repro import net as rnet

        ctx = rnet.running(max(args.localities, 1), pools=pools)
    else:
        core.init(policy=args.scheduler, pools=pools)
        ctx = contextlib.nullcontext()
    with ctx as net:
        if args.trace:
            from repro.obs import export as obs_export

            obs_export.enable_fleet(net)
        exporter = None
        if args.metrics_port is not None:
            from repro.obs.metrics import MetricsExporter

            exporter = MetricsExporter(net=net,
                                       port=args.metrics_port).start()
            print(f"metrics: {exporter.url}", flush=True)
        timeline = tl_sampler = None
        if args.timeline:
            from repro.obs.sampler import FleetSampler
            from repro.obs.timeseries import TimelineWriter

            timeline = TimelineWriter(args.timeline, pattern="*",
                                      interval=0.25,
                                      meta={"launcher": "train",
                                            "arch": args.arch})
            tl_sampler = FleetSampler(pattern="*", interval=0.25, net=net,
                                      timeline=timeline)
            tl_sampler.sample_once()  # t=0 baseline record
            tl_sampler.start()
        cfg = get_config(args.arch, smoke=args.smoke)
        plan = get_plan(args.plan, **({"microbatches": args.microbatches}
                                      if args.plan != "bsp" and args.microbatches > 1 else {}))
        model = build_model(cfg, plan)
        dcfg = DataConfig(batch_size=args.batch, seq_len=args.seq)
        prefetcher = None
        if args.sharded_rows > 0:
            ds = ShardedTokenDataset.create("/data/train-shard", cfg, dcfg,
                                            rows=args.sharded_rows)
            prefetcher = ds.feeder()
            print(json.dumps({"sharded_rows": len(ds),
                              "local_rows": int(prefetcher.global_rows.shape[0]),
                              "segments": ds.pv.nsegments}))
        trainer = Trainer(
            model,
            AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                        total_steps=args.steps),
            dcfg,
            TrainConfig(steps=args.steps, log_every=args.log_every,
                        ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir),
            prefetcher=prefetcher,
        )
        if args.resume:
            print(f"resumed at step {trainer.resume()}")
        history = trainer.fit()
        for h in history:
            print(json.dumps(h))
        print(json.dumps({"counters": dict(core.counters.query("/train*"))}))
        if args.trace:
            tr = obs_export.export_chrome_trace(args.trace, net=net)
            print(json.dumps({"trace": args.trace,
                              "events": len(tr["traceEvents"])}))
        if args.print_counters:
            from repro.obs import sampler as obs_sampler

            obs_sampler.print_counter_report(args.print_counters, net=net)
        if timeline is not None:
            tl_sampler.stop()
            tl_sampler.sample_once()  # end-of-run record (≥2 guaranteed)
            timeline.close()
            print(json.dumps({"timeline": args.timeline,
                              "records": timeline.records_written,
                              "stride": timeline.stride}))
        if exporter is not None:
            exporter.close()
    core.finalize()


if __name__ == "__main__":
    main()
