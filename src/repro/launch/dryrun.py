import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count at first init).  This module is the ONLY place that forces 512
# placeholder devices — smoke tests and benches see the real CPU device.
# It is an emulation tool, pinned to the CPU (its --all children inherit
# the pin): it never opens an accelerator another process may hold.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the real step function (train_step for train
shapes, prefill/serve steps for inference shapes) against ShapeDtypeStruct
stand-ins with the production shardings, compiles it, and records:

- ``memory_analysis()``   bytes per device (proves the cell fits HBM),
- ``cost_analysis()``     HLO FLOPs / bytes (roofline numerator),
- post-SPMD collective inventory (``dist.hlo_analysis``) with while-loop
  trip counts — collective_bytes is NOT in cost_analysis,
- compile wall time.

Results go to ``results/dryrun/<arch>__<shape>__<mesh>__<plan>.json`` —
EXPERIMENTS.md §Dry-run / §Roofline read from there.

Usage:
  python -m repro.launch.dryrun --arch qwen25_3b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--mesh both] [--plan futurized]
"""

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _serve_params_sds(specs):
    """Serving uses bf16 weights (no fp32 master copy at inference)."""
    return {p: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16) for p, s in specs.items()}


def run_cell(arch: str, shape: str, mesh_name: str, plan_name: str,
             out_dir: Path = RESULTS, force: bool = False,
             microbatches: int = 1, variant: str = "") -> dict:
    from repro.configs import SHAPES, get_config
    from repro.dist.plan import get_plan
    from repro.launch import mesh as mesh_mod
    from repro.models.model import build_model
    from repro.models.params import param_bytes
    from repro.optim import adamw
    from repro.train import step as step_mod

    out_dir.mkdir(parents=True, exist_ok=True)
    tag = plan_name if microbatches == 1 else f"{plan_name}-mb{microbatches}"
    if variant:
        tag = f"{tag}-{variant}"
    out_path = out_dir / f"{arch}__{shape}__{mesh_name}__{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    cell = SHAPES[shape]
    plan = get_plan(plan_name, **({"microbatches": microbatches}
                                  if microbatches > 1 else {}))
    if variant:  # perf-iteration ablations on the optimized plan
        from dataclasses import replace as _replace

        rules = dict(plan.rules)
        if variant in ("bf16only", "nomods"):
            rules["seq_sp"] = None
        kw = {"rules": rules}
        if variant in ("sponly", "nomods", "spupfront"):
            kw["bf16_boundaries"] = False
        if variant == "spupfront":  # gather weights once per step, reuse
            kw["gather_upfront"] = True  # across all microbatches
        if variant in ("tponly", "tponly-kvseq"):  # == the `serve` plan ablations
            rules["embed"] = None
            kw["fsdp"] = False
            kw["gather_upfront"] = True  # params already whole per TP shard
            if variant == "tponly":
                rules["kv_seq"] = None
        plan = _replace(plan, **kw)
    mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_name == "multipod"))
    n_dev = int(np.prod(list(mesh.shape.values())))
    model = build_model(cfg, plan)
    specs = model.param_specs()

    t0 = time.time()
    with jax.set_mesh(mesh):
        p_sh, o_sh = step_mod.train_state_shardings(model, mesh)

        if cell.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            fn = step_mod.make_train_step(model, opt_cfg, mesh)
            b_specs = model.batch_specs(cell)
            b_sh = step_mod.batch_shardings(model, mesh, b_specs)
            jitted = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(model.abstract_params(),
                                   adamw.abstract_state(specs), b_specs)
        elif cell.kind == "prefill":
            fn = step_mod.make_prefill_step(model)
            in_specs = model.prefill_specs(cell)
            in_sh = step_mod.batch_shardings(model, mesh, in_specs)
            c_specs = model.cache_specs(cell.global_batch, cell.seq_len,
                                        enc_len=cell.seq_len)
            c_sh = step_mod.cache_shardings(model, mesh, c_specs)
            jitted = jax.jit(fn, in_shardings=(p_sh, in_sh),
                             out_shardings=(None, c_sh))
            lowered = jitted.lower(_serve_params_sds(specs), in_specs)
        else:  # decode
            fn = step_mod.make_decode_step(model)
            c_specs, tok_spec = model.decode_specs(cell)
            c_sh = step_mod.cache_shardings(model, mesh, c_specs)
            t_sh = plan.sharding(("batch", None), tok_spec.shape, mesh)
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh),
                             out_shardings=(t_sh, c_sh), donate_argnums=(1,))
            lowered = jitted.lower(_serve_params_sds(specs), c_specs, tok_spec)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # ---------------- analyses -------------------------------------------
    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                if hasattr(ma, k):
                    mem[k] = int(getattr(ma, k))
    except Exception as e:  # noqa: BLE001
        mem["error"] = str(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(ca[k]) for k in ("flops", "bytes accessed") if k in ca}
    except Exception as e:  # noqa: BLE001
        cost["error"] = str(e)

    # static HLO profile: exact matmul FLOPs & collective bytes with
    # while-loop trip counts (cost_analysis counts loop bodies once)
    from repro.dist.hlo_analysis import parse_module

    hlo = compiled.as_text()
    mod = parse_module(hlo, n_dev)
    coll = mod.collectives()
    flops_dev = mod.dot_flops()
    traffic_dev = mod.memory_traffic()

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "plan": tag,
        "n_devices": n_dev, "kind": cell.kind,
        "seq_len": cell.seq_len, "global_batch": cell.global_batch,
        "param_bytes_fp32": param_bytes(specs),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "hlo_flops_per_device": float(flops_dev),
        "hlo_flops_total": float(flops_dev) * n_dev,
        "hbm_traffic_per_device": float(traffic_dev),
        "cost_analysis_raw": cost,  # loop bodies counted once; see hlo_*
        "collectives": {
            "count": coll.count(),
            "wire_bytes_total": int(coll.total_wire()),
            "wire_bytes_ici": int(coll.total_wire(crosses_pod=False)),
            "wire_bytes_dci": int(coll.total_wire(crosses_pod=True)),
            "operand_bytes_total": int(coll.total_operand()),
            "by_kind": {k: int(v) for k, v in coll.by_kind().items()},
        },
        "hlo_bytes": len(hlo),
    }
    out_path.write_text(json.dumps(rec, indent=1))
    # keep the optimized HLO (gzipped) so analyses can be refined without
    # recompiling — the perf loop reads these
    import gzip

    with gzip.open(out_path.with_suffix(".hlo.gz"), "wt") as f:
        f.write(hlo)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod", "both"))
    ap.add_argument("--plan", default="futurized")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--variant", default="",
                    choices=("", "bf16only", "sponly", "nomods", "spupfront",
                             "tponly", "tponly-kvseq"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.all:
        # subprocess per cell: isolation + bounded memory per compile
        from repro.configs import all_cells

        meshes = ("pod", "multipod") if args.mesh == "both" else (args.mesh,)
        cells = all_cells()
        done = failed = 0
        for mesh_name in meshes:
            for arch, shape in cells:
                tag = f"{arch}__{shape}__{mesh_name}__{args.plan}"
                if (out_dir / f"{tag}.json").exists() and not args.force:
                    done += 1
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh_name,
                       "--plan", args.plan, "--out", str(out_dir)]
                if args.force:
                    cmd.append("--force")
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True)
                ok = r.returncode == 0
                done += ok
                failed += not ok
                print(f"[{'OK' if ok else 'FAIL'}] {tag} ({time.time()-t0:.0f}s)",
                      flush=True)
                if not ok:
                    (out_dir / f"{tag}.err").write_text(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
        print(f"dryrun --all: {done} ok, {failed} failed")
        sys.exit(1 if failed else 0)

    rec = run_cell(args.arch, args.shape, args.mesh, args.plan,
                   out_dir=out_dir, force=args.force,
                   microbatches=args.microbatches, variant=args.variant)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
