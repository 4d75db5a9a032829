"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
serving, comparison and training helpers work at smoke widths on the CPU
(Pallas in interpret mode), so the script cannot rot between chip runs."""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.dist.plan import get_plan
from repro.models.model import build_model

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_a_tpu(tmp_path):
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, timeout=300, cwd=tmp_path,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "phase device: FAIL" in r.stderr
    assert '"ok"' not in r.stdout


def test_serve_and_pallas_agree_at_smoke_size(rt, cs):
    cfg = dataclasses.replace(get_config("qwen25_3b", smoke=True),
                              param_dtype="bfloat16")
    model = build_model(cfg, get_plan("serve"))
    params = model.init(jax.random.PRNGKey(cs.SEED))
    assert {p.dtype for p in params.values()} == {jax.numpy.dtype("bfloat16")}
    prompts = cs.make_prompts(cfg.vocab_size, (16, 40, 100))
    kw = dict(max_batch=2, cache_len=128, page_size=16, max_new_tokens=4)
    clock = cs.CompileClock()
    toks, logits, _, stats = cs.serve_requests(model, params, prompts, clock, kw)
    assert stats["tokens"] == 3 * 5 and stats["requests"] == 3
    pmodel = build_model(dataclasses.replace(cfg, attn_impl="pallas"),
                         get_plan("serve"))
    ptoks, plogits, _, _ = cs.serve_requests(pmodel, params, prompts, clock, kw)
    assert [t[0] for t in toks] == [t[0] for t in ptoks]
    worst = cs.compare_logits(logits, plogits)
    assert all(worst[k] <= cs.LOGIT_TOL[k] for k in cs.LOGIT_TOL), worst
    assert cs.compare_logits(logits, logits) == {"rms": 0.0, "max": 0.0}
    assert cs.paged_kernel_vs_oracle(cfg, B=2, maxp=4) <= cs.KERNEL_ATOL


def test_first_tokens_agree_allows_only_near_ties(cs):
    import numpy as np

    base = np.random.default_rng(0).normal(size=1000).astype(np.float32)
    base[:3] = [5.0, 4.0, 3.0]  # clear winner: token 0
    tie = base.copy()
    tie[1] = 5.0 - 0.5 * cs.TIE_MARGIN * tie.std()  # token 1 near-tied
    got = cs.first_tokens_agree([base, base, tie, tie], [0, 0, 0, 0],
                                [0, 1, 1, 2])
    assert got == [True, False, True, False]
    assert all(type(g) is bool for g in got)  # printed as JSON booleans


def test_train_helper_and_guard_count(rt, cs):
    cfg = get_config("qwen25_3b", smoke=True)
    model, losses, _, on = cs.train(cfg, None, steps=2)
    assert len(losses) == 2 and on == [jax.devices()[0].id]
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    assert cs.guard_replicated(model.plan, model.param_specs(), mesh) == (0, 0)
