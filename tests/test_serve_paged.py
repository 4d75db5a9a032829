"""Paged continuous-batching serving stack: per-slot divergence, page-pool
reuse, streaming, sampling, routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.future import Channel, ChannelClosed
from repro.dist.plan import get_plan
from repro.models.model import build_model
from repro.serve.engine import Engine, SamplingParams, ServeConfig
from repro.serve.router import Router


@pytest.fixture(scope="module")
def served():
    cfg = get_config("starcoder2_3b", smoke=True)
    model = build_model(cfg, get_plan("futurized"))
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def _manual_greedy(model, params, prompt, n):
    pin = {"tokens": jnp.asarray(prompt, jnp.int32)[None, :]}
    logits, cache = jax.jit(model.prefill, static_argnames=("cache_len",))(
        params, pin, cache_len=96)
    out = [int(jnp.argmax(logits, -1)[0])]
    dec = jax.jit(model.decode)
    for _ in range(n):
        logits, cache = dec(params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits, -1)[0]))
    return out


def _truncate_at_eos(toks, eos):
    out = []
    for t in toks:
        out.append(t)
        if t == eos:
            break
    return out


def test_per_slot_length_divergence(rt, served):
    """Requests with different max_new share the batch; every slot must
    match its own reference decode (per-row lengths in the kernel)."""
    cfg, model, params = served
    prompts = [[5, 6, 7, 8], [100, 3, 50, 2, 9, 11], [42, 7]]
    new = [2, 7, 4]
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=96,
                                            max_new_tokens=8))
    futs = [eng.submit(p, max_new=n) for p, n in zip(prompts, new)]
    outs = [f.get(timeout=300) for f in futs]
    for p, n, o in zip(prompts, new, outs):
        assert o == _manual_greedy(model, params, p, n), (p, n)


def test_per_slot_eos_divergence(rt, served):
    """EOS ends one slot early while its batch-mates continue exactly."""
    cfg, model, params = served
    pa, pb = [5, 6, 7, 8], [100, 3, 50, 2, 9, 11]
    n = 6
    ra = _manual_greedy(model, params, pa, n)
    rb = _manual_greedy(model, params, pb, n)
    # pick an eos whose *first* occurrence in ra is mid-sequence
    k = next(i for i in range(1, n) if ra[i] not in ra[:i])
    eos = ra[k]
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=96,
                                            max_new_tokens=n, eos_id=eos))
    fa = eng.submit(pa)
    fb = eng.submit(pb)
    assert fa.get(timeout=300) == _truncate_at_eos(ra, eos)
    assert fb.get(timeout=300) == _truncate_at_eos(rb, eos)
    assert len(fa.get()) == k + 1 < n + 1  # ended early, batch-mate exact


def test_paged_free_list_reuse_under_churn(rt, served):
    """Admission churn cycles pages through the free list: cumulative
    allocations exceed pool capacity (reuse) and everything returns."""
    cfg, model, params = served
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=64,
                                            max_new_tokens=3, page_size=16,
                                            name="churn#0"))
    kv = eng.backend.kv
    futs = [eng.submit(list(range(1, 2 + i % 17))) for i in range(9)]
    outs = [f.get(timeout=300) for f in futs]
    assert all(len(o) == 4 for o in outs)
    assert kv.pages_in_use() == 0
    assert kv.free_pages() == kv.num_pages - 1
    assert eng.c_sub.get_value() - eng.c_done.get_value() == 0
    from repro.core import counters
    assert counters.get_value("/serve{churn#0}/pages/allocated") > kv.num_pages - 1
    assert (counters.get_value("/serve{churn#0}/pages/allocated")
            == counters.get_value("/serve{churn#0}/pages/freed"))


def test_stream_channel_order_and_close(rt, served):
    """Streamed tokens arrive in generation order, the first before the
    request completes, and the channel closes on finish."""
    cfg, model, params = served
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=96,
                                            max_new_tokens=16))
    ch, fut = eng.submit_stream([5, 6, 7, 8])
    # 16 decode steps (seconds) remain when the first token arrives — wide
    # margin against scheduler jitter on a loaded CI machine
    first = ch.get(timeout=300)
    assert not fut.is_ready(), "first token must stream before completion"
    rest = list(ch)
    out = fut.get(timeout=300)
    assert [first] + rest == out
    with pytest.raises(ChannelClosed):
        ch.get(timeout=1)


def test_greedy_sampling_equivalence_at_t0(rt, served):
    """temperature=0 reduces to exact argmax regardless of top-k/top-p."""
    cfg, model, params = served
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=96,
                                            max_new_tokens=5))
    p = [9, 8, 7, 6]
    o_plain = eng.submit(p).get(timeout=300)
    o_t0 = eng.submit(p, sampling=SamplingParams(temperature=0.0, top_k=7,
                                                 top_p=0.5)).get(timeout=300)
    assert o_plain == o_t0 == _manual_greedy(model, params, p, 5)


def test_sampling_respects_top_k(rt, served):
    """Sampled tokens with top_k=1 are exactly the greedy sequence (the
    nucleus of one); higher temperature still yields valid token ids."""
    cfg, model, params = served
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=96,
                                            max_new_tokens=4))
    p = [5, 6, 7, 8]
    o_k1 = eng.submit(p, sampling=SamplingParams(temperature=0.7, top_k=1)
                      ).get(timeout=300)
    assert o_k1 == _manual_greedy(model, params, p, 4)
    o_hot = eng.submit(p, sampling=SamplingParams(temperature=1.2, top_k=20)
                       ).get(timeout=300)
    assert all(0 <= t < cfg.vocab_size for t in o_hot)


def test_router_least_loaded_dispatch(rt, served):
    """The router reads per-engine in-flight counters and avoids the busy
    replica."""
    cfg, model, params = served
    scfg = ServeConfig(max_batch=2, cache_len=64, max_new_tokens=2)
    router = Router.replicate(model, params, scfg, 2)
    e0, e1 = router.engines
    assert router.pick() == 0  # ties → first
    e0.c_sub.increment(3)  # fake 3 in-flight requests on replica 0
    assert e0.load() == 3 and e1.load() == 0
    assert router.pick() == 1
    out = router.submit([4, 5, 6]).get(timeout=300)
    assert len(out) == 3
    from repro.core import counters
    assert counters.get_value("/serve{router}/dispatch/engine#1") >= 1
    assert counters.get_value("/serve{engine#1}/requests/completed") >= 1
    e0.c_sub.increment(-3)  # restore


def test_seed_parity_mode_matches_greedy(rt, served):
    """The A/B baseline (dense cache + inline-prefill barrier) still
    produces exact greedy tokens — the bench compares against it."""
    cfg, model, params = served
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=96,
                                            max_new_tokens=4, paged=False,
                                            pipeline_admission=False))
    assert not eng.paged
    p = [11, 12, 13]
    assert eng.submit(p).get(timeout=300) == _manual_greedy(model, params, p, 4)


def test_pages_walked_counts_the_pages_up_to_each_new_token(rt, served):
    """Each decode step adds, per decoding row, the pages holding positions
    up to its new token: prompts of 14 and 3 tokens, 4 decode steps each
    (after the prefill's token), 16-token pages, walk 1+1+2+2 and 1+1+1+1
    pages."""
    from repro.core import counters

    cfg, model, params = served
    name = "engine#walk"
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=64,
                                            max_new_tokens=4, name=name))
    walked = f"/serve{{{name}}}/decode/pages_walked"
    before = counters.get_value(walked)
    futs = [eng.submit(list(range(1, 15)), max_new=4),
            eng.submit([7, 8, 9], max_new=4)]
    assert [len(f.get(timeout=300)) for f in futs] == [5, 5]
    assert counters.get_value(walked) - before == 6 + 4


def test_decode_step_compiles_once(rt, served):
    """Admission churn (different prompt lengths, sampling params, EOS
    timings) never changes decode-step shapes: one compile, total."""
    cfg, model, params = served
    eng = Engine(model, params, ServeConfig(max_batch=2, cache_len=64,
                                            max_new_tokens=3))
    futs = [eng.submit(list(range(1, 2 + i)),
                       sampling=SamplingParams(temperature=0.5 * (i % 2),
                                               top_k=i))
            for i in range(5)]
    for f in futs:
        f.get(timeout=300)
    assert eng.decode_compile_count() == 1


# the kernel path against the gather oracle in float32, in stds of what is
# compared: an online and a dense softmax differ by float32 rounding (in
# bf16 one rounding of the attention output differs, and random weights
# with top-k routing carry it to a few hundredths of a std in the logits)
KERNEL_ATOL = 1e-4


def _oracle_attention(cfg, plan, h, lp, pools, pt, pos):
    """Write the token into one layer's pools (K and V, or the latent),
    then attend over the pages: each row's page list gathered in full and a
    masked softmax (under MLA in absorbed form)."""
    from repro.kernels import ops
    from repro.models import layers as Lx

    dt = jnp.dtype(cfg.dtype)
    B = h.shape[0]
    if cfg.mla:
        q_nope, q_pe, lat = Lx._mla_project(cfg, h[:, 0], lp, "", pos)
        rows = (lat[:, None],)
    else:
        H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q, k, v = (h @ lp[w].astype(dt) for w in ("wq", "wk", "wv"))
        if cfg.qkv_bias:
            q, k, v = (a + lp[b].astype(dt) for a, b in
                       zip((q, k, v), ("bq", "bk", "bv")))
        q, k, v = q.reshape(B, H, Dh), k.reshape(B, KV, Dh), v.reshape(B, KV, Dh)
        if cfg.rope:
            q, k = Lx._rope_single(cfg, q, pos), Lx._rope_single(cfg, k, pos)
        rows = (k, v)
    page = pools[0].shape[2]
    pidx = pt[jnp.arange(B), pos // page]
    pools = tuple(p.at[pidx, :, pos % page].set(r.astype(p.dtype))
                  for p, r in zip(pools, rows))
    pages = [ops.gather_layer_pages(p[None], jnp.int32(0), pt) for p in pools]
    T = pages[0].shape[2]
    valid = jnp.arange(T)[None, :] < (pos + 1)[:, None]
    if cfg.mla:
        o = Lx._mla_absorbed(cfg, lp, "", q_nope, q_pe, pages[0][:, 0], valid)
    else:
        kc, vc = pages
        s = jnp.einsum("bkgd,bktd->bkgt", q.reshape(B, KV, H // KV, Dh),
                       kc.astype(dt), preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(Dh))
        pr = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, -1e30), -1)
        o = jnp.einsum("bkgt,bktd->bkgd", pr.astype(dt), vc.astype(dt))
        o = o.reshape(B, 1, H * Dh)
    return o @ lp["wo"].astype(dt), pools


def _oracle_decode(cfg, plan, params, cache, token):
    """One paged decode step: a scan over each layer group with its pools
    as ``xs``/``ys``, every layer writing its token before it attends."""
    from repro.models import layers as Lx
    from repro.models.moe import moe_ffn
    from repro.models.transformer import _groups, cache_keys

    pt, pos = cache["page_table"], cache["pos"]
    out = dict(cache, pos=pos + 1)
    x = Lx.embed(cfg, plan, params["tok_embed"], token)
    for prefix, _ in _groups(cfg):
        keys = cache_keys(cfg, prefix)
        moe_layer = cfg.is_moe and prefix == "blk/"
        stacked = {n[len(prefix):]: a for n, a in params.items()
                   if n.startswith(prefix)}

        def layer(x, xs):
            lp, pools = xs
            h = Lx.norm(cfg, x, lp["ln1"])
            h, pools = _oracle_attention(cfg, plan, h, lp, pools, pt, pos)
            x = x + h
            h = Lx.norm(cfg, x, lp["ln2"])
            ffn = (moe_ffn(cfg, plan, h, lp, "moe/", serve=True)[0] if moe_layer
                   else Lx.mlp(cfg, plan, h, lp, ""))
            return x + ffn, pools

        x, pools = jax.lax.scan(layer, x,
                                (stacked, tuple(cache[k] for k in keys)))
        out.update(zip(keys, pools))
    x = Lx.norm(cfg, x, params["final_ln"])
    table = params["tok_embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = Lx.unembed(cfg, plan, x, table, transpose=cfg.tie_embeddings)
    return logits[:, 0, :], out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("arch", ["qwen25_3b", "deepseek_moe_16b",
                                  "deepseek_v2_lite"])
def test_decode_paged_matches_write_then_read_oracle(arch, impl, monkeypatch):
    """The decode step reads the pools in place, attends to the step's new
    rows as inputs and writes them once after the layer scan; over 3 steps
    its logits for live rows and its pools equal an oracle that writes each
    layer's token before attending over the gathered pages.  ``xla`` is the
    serving path off TPU, the same gather: equal to the bit.  ``pallas`` is
    the TPU path, the paged kernel (here in interpret mode), compared in
    float32 to ``KERNEL_ATOL`` of their spread (an online softmax rounds
    otherwise than a dense one).  Live rows start at the first and the last
    offset of a page; idle slots sit at 0 with every page on scratch page
    0, as the engine leaves them.  Their logits and page 0 are left out:
    under the oracle's order an idle row reads the other idle rows' tokens
    of the same layer from page 0."""
    import dataclasses

    from repro.kernels import ops

    cfg = get_config(arch, smoke=True)
    if impl == "pallas":
        monkeypatch.setattr(ops, "paged_attention", ops.paged_attention_kernel)
        cfg = dataclasses.replace(cfg, dtype="float32")
    plan = get_plan("serve")
    model = build_model(cfg, plan)
    params = model.init(jax.random.PRNGKey(3))
    page, maxp, B = 8, 4, 4
    P = 2 * maxp + 2
    specs = model.paged_cache_specs(P, page, B, maxp)
    pool_keys = [n for n in specs if n not in ("page_table", "pos")]
    cache = {n: jax.random.normal(jax.random.PRNGKey(i), specs[n].shape,
                                  specs[n].dtype)
             for i, n in enumerate(pool_keys)}
    assert (("k0" in cache or "ckv0" in cache) == (cfg.first_dense > 0))
    rng = np.random.default_rng(0)
    pages = 1 + rng.permutation(P - 1)[:2 * maxp].reshape(2, maxp)
    pt = np.zeros((B, maxp), np.int32)
    pt[:2] = pages  # rows 2, 3 idle: all of their table is page 0
    pos0 = np.asarray([2 * page, 2 * page - 1, 0, 0], np.int32)
    live = np.asarray([True, True, False, False])
    cache["page_table"] = jnp.asarray(pt)
    cache["pos"] = jnp.asarray(pos0)
    tok = jnp.asarray([[5], [77], [3], [9]], jnp.int32)

    def same(g, w, what):
        if impl == "xla":
            np.testing.assert_array_equal(g, w, err_msg=what)
        else:
            np.testing.assert_allclose(g, w, rtol=0, atol=KERNEL_ATOL * w.std(),
                                       err_msg=what)

    step = jax.jit(model.decode_paged)
    oracle = jax.jit(lambda p, c, t: _oracle_decode(cfg, plan, p, c, t))
    got, want = cache, dict(cache)
    for _ in range(3):
        lg, got = step(params, got, tok)
        lw, want = oracle(params, want, tok)
        same(np.asarray(lg)[live], np.asarray(lw)[live], "logits")
        for n in cache:
            g, w = (np.asarray(c[n], np.float32) for c in (got, want))
            if g.ndim == 5:  # a pool: every page but the scratch page
                g, w = g[:, 1:], w[:, 1:]
            same(g, w, n)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)[:, None]
        # the engine re-uploads the idle slots' fill, 0, every step
        got = dict(got, pos=jnp.where(live, got["pos"], 0))
        want = dict(want, pos=jnp.where(live, want["pos"], 0))
