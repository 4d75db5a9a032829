"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,S,H,KV,Dh", [
    (1, 128, 4, 4, 64),   # MHA
    (2, 256, 4, 2, 64),   # GQA
    (1, 384, 8, 1, 32),   # MQA, odd seq multiples
    (2, 200, 4, 2, 64),   # needs padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, KV, Dh, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, Dh), dtype)
    o = ops.flash_attention(q, k, v, causal=causal)
    e = ref.mha(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(e, np.float32), atol=_tol(dtype) * 4)


def test_flash_attention_window():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    o = ops.flash_attention(q, k, v, causal=True, window=64)
    e = ref.mha(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(e), atol=1e-4)


@pytest.mark.parametrize("B,T,H,KV,Dh,length", [
    (2, 512, 4, 2, 64, 300),
    (1, 1024, 8, 8, 32, 1024),
    (3, 300, 4, 1, 64, 17),   # padding + MQA + short fill
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, T, H, KV, Dh, length, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), dtype)
    o = ops.decode_attention(q, k, v, jnp.asarray(length))
    e = ref.decode_mha(q, k, v, length=length)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(e, np.float32), atol=_tol(dtype) * 4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_per_row_lengths(dtype):
    """The seed bug: one scalar length masked every row, so slots at
    different fill depths attended over stale/zero KV.  A (B,) vector must
    match the oracle row-by-row."""
    B, T, H, KV, Dh = 4, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), dtype)
    lens = jnp.asarray([1, 17, 100, 256], jnp.int32)
    o = ops.decode_attention(q, k, v, lens)
    e = ref.decode_mha(q, k, v, length=lens)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(e, np.float32), atol=_tol(dtype) * 4)
    # divergence is real: the scalar path at max(lens) differs on short rows
    o_scalar = ops.decode_attention(q, k, v, jnp.asarray(256))
    assert not np.allclose(np.asarray(o, np.float32)[0],
                           np.asarray(o_scalar, np.float32)[0])


# (KV, G, W, pools, value width): separate K and V pools at qwen2.5-3b's
# heads, and one latent pool at deepseek-v2-lite's row (c_kv 512 ‖ k_pe 64,
# padded to 640), each row both key and value
PAGED_GEOMETRIES = {"gqa": (2, 8, 128, 2, 128), "latent": (1, 16, 640, 1, 512)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("geometry", sorted(PAGED_GEOMETRIES))
def test_paged_decode_attention_matches_oracle(geometry, layer, dtype):
    """The paged kernel (interpret mode) against the XLA gather path, on
    layer 0 or L-1 of a stacked pool: rows whose pool holds 0 tokens (an
    idle row, its table all scratch page 0), page-2, page-1, page, a block
    and page·maxp-1 tokens, so that with the step's new row, given only as
    an input, they attend 1, page-1, page, page+1, a block+1 and cache_len
    positions.  Every pool slot the rows do not attend is NaN, and the
    output is finite: the kernel reads no page past a row's fill, and masks
    the unused tail of the pages it reads."""
    from repro.kernels.decode_attention import paged_attention_fwd

    KV, G, W, npool, vw = PAGED_GEOMETRIES[geometry]
    L, page, maxp, ppb = 3, 16, 12, 2
    li = 0 if layer == "first" else L - 1
    lens = np.asarray([0, page - 2, page - 1, page, ppb * page,
                       page * maxp - 1], np.int32)
    B = len(lens)
    P = B * maxp + 1
    rng = np.random.default_rng(11)
    perm = 1 + rng.permutation(P - 1)
    pt = np.zeros((B, maxp), np.int32)
    live = np.zeros((P, page), bool)
    for b, n in enumerate(lens):
        npg = -(-n // page)
        pt[b, :npg] = perm[b * maxp:b * maxp + npg]
        for t in range(n):
            live[pt[b, t // page], t % page] = True
    ks = jax.random.split(jax.random.PRNGKey(12), 2 + 2 * npool)
    pools = tuple(
        jnp.where(jnp.asarray(live)[None, :, None, :, None] | (
            jnp.arange(L) != li)[:, None, None, None, None],
            jax.random.normal(ks[i], (L, P, KV, page, W)), jnp.nan
        ).astype(dtype) for i in range(npool))
    new = tuple(jax.random.normal(ks[npool + i], (B, KV, W), dtype)
                for i in range(npool))
    q = jax.random.normal(ks[-1], (B, KV, G, W), dtype)
    args = (q, pools, new, jnp.int32(li), jnp.asarray(pt), jnp.asarray(lens))
    kw = dict(scale=1.0 / np.sqrt(W), value_width=vw)
    o = paged_attention_fwd(*args, pages_per_block=ppb, buffers=2,
                            interpret=True, **kw)
    assert o.shape == (B, KV, G, vw)
    o = np.asarray(o, np.float32)
    assert np.isfinite(o).all()
    clean = tuple(jnp.nan_to_num(p) for p in pools)
    e = ops.paged_attention_gather(q, clean, new, args[3], args[4], args[5],
                                   **kw)
    np.testing.assert_allclose(o, np.asarray(e, np.float32),
                               atol=_tol(dtype) * 4)


def test_paged_kernel_ring_spans_rows_and_idle_rows():
    """A ring deeper than a row's blocks keeps copies in flight across rows,
    past idle rows between them: every ring depth gives the same output."""
    from repro.kernels.decode_attention import paged_attention_fwd

    KV, G, W, page, maxp, L = 2, 4, 128, 8, 6, 2
    lens = np.asarray([0, 9, 0, 0, 40, 1, 0], np.int32)
    B = len(lens)
    P = B * maxp + 1
    pt = (1 + np.arange(B * maxp, dtype=np.int32)).reshape(B, maxp)
    pt[lens == 0] = 0
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    pools = tuple(jax.random.normal(k, (L, P, KV, page, W)) for k in ks[:2])
    new = tuple(jax.random.normal(k, (B, KV, W)) for k in ks[2:4])
    q = jax.random.normal(ks[4], (B, KV, G, W))
    args = (q, pools, new, jnp.int32(1), jnp.asarray(pt), jnp.asarray(lens))
    kw = dict(scale=W ** -0.5, value_width=W)
    e = np.asarray(ops.paged_attention_gather(*args, **kw))
    for buffers in (2, 3, 5):
        o = paged_attention_fwd(*args, pages_per_block=2, buffers=buffers,
                                interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(o), e, atol=1e-5,
                                   err_msg=str(buffers))


def test_pages_per_block_follows_the_row_width():
    """A block copies at least ``BLOCK_BYTES`` whatever the geometry, in the
    fewest pages that are a power of two, and spans no more than a row."""
    def pool(KV, W, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((2, 9, KV, 16, W), dtype)

    for pools in [(pool(2, 128), pool(2, 128)), (pool(1, 640),),
                  (pool(8, 64, jnp.float32),) * 2]:
        n = ops.pages_per_block(pools, 16, 4096)
        row = sum(p.shape[2] * p.shape[4] * p.dtype.itemsize for p in pools)
        assert n & (n - 1) == 0
        assert n * 16 * row >= ops.BLOCK_BYTES > (n // 2) * 16 * row
        assert ops.pages_per_block(pools, 16, 3) == min(n, 3)


def test_gather_paged_kv_roundtrip():
    """The XLA path's gather reads one layer of a stacked pool in place,
    head-major: row b's j-th page lands at tokens [j·page, (j+1)·page)."""
    L, P, page, KV, Dh, B, maxp = 3, 10, 16, 2, 32, 2, 4
    pool = jax.random.normal(jax.random.PRNGKey(10), (L, P, KV, page, Dh))
    pt = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)
    g = ops.gather_layer_pages(pool, jnp.int32(2), pt)
    assert g.shape == (B, KV, maxp * page, Dh)
    np.testing.assert_array_equal(np.asarray(g[0, :, :page]),
                                  np.asarray(pool[2, 1]))
    np.testing.assert_array_equal(np.asarray(g[1, :, page:2 * page]),
                                  np.asarray(pool[2, 4]))


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 128, 2, 16, 1, 16, 32),
    (2, 96, 4, 16, 2, 32, 32),   # GQA-style groups + padding (96 % 32 == 0)
    (1, 100, 2, 8, 2, 16, 64),   # non-divisible → pad
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, S, H, P, G, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, G, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, G, N)) * 0.3).astype(dtype)
    y = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    e, _ = ref.ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(e, np.float32),
                               atol=_tol(dtype) * 8, rtol=1e-2)


@pytest.mark.parametrize("B,S,W", [(1, 256, 128), (2, 130, 100), (1, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(B, S, W, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, W)) * 0.1).astype(dtype)
    h = ops.rglru_scan(a, b)
    e = ref.rglru(a, b)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(e, np.float32), atol=_tol(dtype) * 4)


@pytest.mark.parametrize("N", [1000, 65536, 70000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_triad_sweep(N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    a = jax.random.normal(ks[0], (N,), dtype)
    b = jax.random.normal(ks[1], (N,), dtype)
    o = ops.stream_triad(a, b, 3.0)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref.triad(a, b, 3.0), np.float32),
                               atol=_tol(dtype))


def test_flash_attention_trainable_grads_match_oracle():
    """custom_vjp kernel path: grads == jax.grad of the pure-jnp oracle."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))

    def loss_kernel(q, k, v):
        return jnp.sum(ops.flash_attention_trainable(q, k, v, True, 0) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.mha(q, k, v, causal=True) ** 2)

    l1, g1 = jax.value_and_grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    l2, g2 = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(l1) - float(l2)) < 1e-2
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
