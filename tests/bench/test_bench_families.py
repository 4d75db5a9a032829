"""The harness reads each model through its family module
(``benchmarks/chip/families/<model_type>.py``).

The pinned values were computed on the CPU by the harness as it was before
the family modules, when the dense decoder was written into
``model_spec``, ``weights``, ``reference`` and ``flops``: the same seed must
still give the same weights, the same reference gaps and the same counts.
"""

import hashlib
import sys
from math import prod

import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import flops
import manifest
import reference
import run_cell
import weights
from model_spec import from_config

# sha256 (first 16 hex digits) of each served leaf's bf16 bytes, seed 2**35 + 1
DIGESTS = {
    "blk/bk": "ef36df0be1ae737c", "blk/bq": "4acfcec9ea3a72b9",
    "blk/bv": "35cf76e299d444fc", "blk/ln1": "7755fa8843c7c89f",
    "blk/ln2": "4f291030b7a136c9", "blk/w_gate": "6341835ffe3dba41",
    "blk/w_in": "dd73349485fd9463", "blk/w_out": "d80e3fd6382e1c92",
    "blk/wk": "4c367f810b02640a", "blk/wo": "c66212cc1202fdb0",
    "blk/wq": "ad8c645f1fbb9ba0", "blk/wv": "63b6d9f45ad440c9",
    "final_ln": "89d1713dd8b087b9", "tok_embed": "afdedc51d62fa5f5"}

# reference.compare(..., control="w8a8") on SAMPLES, seed key 2**33 + 5
GAPS = {
    "tiny_qwen": [
        {"gap": [4.068769931793213, 5.003899574279785, 3.666128635406494,
                 3.531564950942993, 3.6517603397369385, 3.607978105545044],
         "control_gap": [0.0, 0.0, 0.0, 0.00549430213868618, 0.0, 0.0]},
        {"gap": [3.376394033432007, 2.344374895095825, 3.7845304012298584],
         "control_gap": [0.0, 0.0, 0.021887028589844704]}],
    "tiny_starcoder": [
        {"gap": [4.96282958984375, 4.525666236877441, 3.7291600704193115,
                 3.4977922439575195, 2.9497463703155518, 2.620418071746826],
         "control_gap": [0.0] * 6},
        {"gap": [4.098756313323975, 2.588981866836548, 3.2744176387786865],
         "control_gap": [0.0] * 3}]}


def _samples():
    rng = np.random.default_rng(11)
    return [{"prompt": rng.integers(1, 512, 37).tolist(),
             "served": rng.integers(1, 512, 6).tolist()},
            {"prompt": rng.integers(1, 512, 530).tolist(),
             "served": rng.integers(1, 512, 3).tolist()}]


def _system_specs(conf):
    import dataclasses

    from repro.configs import get_config
    from repro.dist.plan import get_plan
    from repro.models.model import build_model

    sysc = conf["system"]
    cfg = dataclasses.replace(get_config(sysc["arch"], smoke=True),
                              **sysc["overrides"])
    model = build_model(cfg, get_plan("serve"))
    return model, {n: (tuple(s.shape), jnp.dtype(s.dtype).name)
                   for n, s in model.param_specs().items()}


@pytest.mark.parametrize("conf", [bench_tiny.QWEN, bench_tiny.STARCODER])
def test_served_weights_are_the_parents(conf):
    m = from_config(conf["name"], conf)
    _, expected = _system_specs(conf)
    params = weights.served_params(manifest.family(m.model_type).layout(m),
                                   weights.root_key(2**35 + 1), expected)
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in params.items()}
    assert got == {k: v for k, v in DIGESTS.items() if k in expected}


@pytest.mark.parametrize("conf", [bench_tiny.QWEN, bench_tiny.STARCODER])
def test_reference_gaps_are_the_parents(conf):
    """The CPU backend may sum a float32 product in another order on
    another CPU, a change of about 1e-7 of a gap; a changed operation moves
    these gaps by far more than the tolerance."""
    m = from_config(conf["name"], conf)
    res = reference.compare(m, weights.root_key(2**33 + 5), _samples(),
                            control="w8a8")
    for got, want in zip(res, GAPS[conf["name"]], strict=True):
        for k in ("gap", "control_gap"):
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=0)


def test_operation_counts_are_the_parents():
    q = from_config("qwen25_3b", manifest.config("qwen25_3b"))
    assert flops.param_count(q) == 3085938688
    assert flops.kv_bytes_per_token(q) == 36864
    assert flops._attn_flops(q, 1, 1021) == 301105152.0
    assert flops.decode_step(q, [1000, 3000]) == {
        "flops": 13523025920.0, "bytes": 6319407104.0}
    assert flops.decode_step(q, list(range(256, 3072, 117))) == {
        "flops": 166531072000.0, "bytes": 7702654976.0}
    assert flops.prefill(q, 1020) == 5814231433216.0
    assert flops.prefill(q, 3072) == 18439369916416.0


def _two_stacks():
    """One leading layer under ``d0/`` and three under ``blk/`` that share
    their plain names, and an untied head."""
    leaves = {"ln1": ((8,), "norm"), "wq": ((8, 16), "matrix"),
              "bq": ((16,), "bias")}
    return weights.Layout(
        {"embed": ("tok_embed", (128, 8), "embed"),
         "final_norm": ("final_ln", (8,), "norm"),
         "lm_head": ("lm_head", (8, 128), "matrix")},
        (weights.Stack("d0/", 0, 1, leaves),
         weights.Stack("blk/", 1, 3, {**leaves, "w_in": ((8, 4, 24), "matrix")})))


def test_generic_weights_over_two_stacks():
    layout = _two_stacks()
    want = layout.served()
    assert want["d0/wq"] == ((1, 8, 16), "bfloat16")
    assert want["blk/w_in"] == ((3, 8, 4, 24), "bfloat16")
    assert weights.param_count(layout) == sum(prod(s) for s, _ in want.values())
    key = weights.root_key(2**40 + 3)
    served = weights.served_params(layout, key, want)
    glob, layer = weights.reference_weights(layout, key)
    for n, (name, _, _) in layout.glob.items():
        np.testing.assert_array_equal(np.asarray(served[name], np.float32),
                                      np.asarray(glob[n]))
    for st in layout.stacks:
        for i in range(st.layers):
            w = layer(st.first + i)
            assert set(w) == set(st.leaves)
            for n in st.leaves:
                np.testing.assert_array_equal(
                    np.asarray(served[st.prefix + n][i], np.float32),
                    np.asarray(w[n]))
    # the same plain name draws other values in each stack and each layer
    rows = [np.asarray(served["d0/wq"][0])] + [
        np.asarray(served["blk/wq"][i]) for i in range(3)]
    assert len({r.tobytes() for r in rows}) == 4
    # a matrix of any rank is N(0, 1/fan_in), fan_in its second-to-last size
    std = float(np.asarray(served["blk/w_in"], np.float32).std())
    assert abs(std * 4**0.5 - 1) < 0.1


def test_generic_weights_refuse_another_layout():
    layout = _two_stacks()
    want = layout.served()
    renamed = {("blk/wk" if k == "blk/wq" else k): v for k, v in want.items()}
    with pytest.raises(ValueError, match=r"not made here \['blk/wk'\]"):
        weights.served_params(layout, weights.root_key(1), renamed)
    tied = {k: v for k, v in want.items() if k != "lm_head"}
    with pytest.raises(ValueError, match="lm_head"):
        weights.served_params(layout, weights.root_key(1), tied)
    d0, blk = layout.stacks
    overlap = layout._replace(stacks=(d0, blk._replace(first=0)))
    with pytest.raises(ValueError, match="overlap"):
        overlap.served()


def test_unknown_model_type_names_the_family_file(monkeypatch):
    path = manifest.HERE / "families" / "no_such_family.py"
    with pytest.raises(FileNotFoundError, match=str(path)):
        manifest.family("no_such_family")
    with pytest.raises(FileNotFoundError, match="no_such_family.py"):
        from_config("x", {**bench_tiny.QWEN, "model_type": "no_such_family"})
    # run_cell fails before it looks for a chip
    monkeypatch.setattr(manifest, "config",
                        lambda name: {**manifest.load_json(
                            manifest.HERE / "configs" / f"{name}.json"),
                            "model_type": "no_such_family"})
    monkeypatch.setattr(run_cell, "configure_jax", lambda: pytest.fail("ran"))
    with pytest.raises(FileNotFoundError, match="no_such_family.py"):
        run_cell.main(["--workload", bench_tiny.CELL, "--seed", "1",
                       "--seconds", "1"])


LLAMA_LIKE = '''"""A dense decoder with no biases, spelled as Llama spells it."""
from dense_decoder import *  # noqa: F401,F403
from dense_decoder import read_spec


def spec(name, conf):
    return read_spec(name, conf, norm="rms", eps_key="rms_norm_eps",
                     gated=True, act="silu", qkv_bias=bool(conf["attention_bias"]))
'''


def test_a_new_model_type_needs_only_its_family_file(tmp_path, monkeypatch):
    """A family file of its own, in a copy of the harness's family
    directory, is all a configuration of a new ``model_type`` needs: the
    spec, the weights the system holds, the reference and the counts come
    through it, and the reference agrees with the system's prefill."""
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setattr(manifest, "HERE", tmp_path)
    monkeypatch.delitem(sys.modules, "family_toy_llama", raising=False)
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "toy_llama.py").write_text(LLAMA_LIKE)
    conf = {**bench_tiny.QWEN, "name": "tiny_llama", "model_type": "toy_llama",
            "attention_bias": False, "tie_word_embeddings": False,
            "system": {**bench_tiny.QWEN["system"], "overrides": {
                "param_dtype": "bfloat16", "tie_embeddings": False,
                "qkv_bias": False}}}
    m = from_config(conf["name"], conf)
    model, expected = _system_specs(conf)
    assert flops.param_count(m) == sum(prod(s) for s, _ in expected.values())
    key = weights.root_key(9)
    params = weights.served_params(manifest.family("toy_llama").layout(m),
                                   key, expected)
    prompt = np.random.default_rng(1).integers(1, m.vocab, 29).tolist()
    logits, _ = model.prefill(params, {"tokens": jnp.asarray([prompt])})
    tok = int(jnp.argmax(logits[0, :m.vocab]))
    res = reference.compare(m, key, [{"prompt": prompt, "served": [tok]}])
    assert res[0]["gap"][0] < 0.05
    assert flops.decode_step(m, [10, 20])["flops"] > 0


def test_untied_qwen2_run_is_correct():
    """A whole tiny run of a Qwen2 configuration with its own head."""
    out = bench_tiny.run(conf=bench_tiny.QWEN_UNTIED)
    assert out["correct"], out["checks"]
    assert out["checks"]["gap_mean"]["value"] < out["checks"]["gap_mean"]["limit"]
