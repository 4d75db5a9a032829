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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,Dh,page,maxp", [
    (3, 4, 2, 64, 32, 8),   # GQA
    (2, 8, 8, 32, 16, 4),   # MHA, small pages
    (1, 8, 1, 64, 64, 4),   # MQA
    (2, 24, 2, 128, 16, 6),  # starcoder2-3b heads: G=12, not a multiple of 8
])
def test_paged_decode_attention_matches_oracle(B, H, KV, Dh, page, maxp, dtype):
    """Paged kernel walking shuffled per-request page lists == dense oracle."""
    T = page * maxp
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), dtype)
    rng = np.random.default_rng(0)
    P = B * maxp + 3  # pool with spare pages; page 0 reserved
    perm = 1 + rng.permutation(P - 1)[: B * maxp].reshape(B, maxp)
    k_pages = np.zeros((P, KV, page, Dh), np.float32)
    v_pages = np.zeros((P, KV, page, Dh), np.float32)
    for b in range(B):
        for j in range(maxp):
            blk = slice(j * page, (j + 1) * page)
            k_pages[perm[b, j]] = np.asarray(k[b, blk], np.float32).transpose(1, 0, 2)
            v_pages[perm[b, j]] = np.asarray(v[b, blk], np.float32).transpose(1, 0, 2)
    lens = jnp.asarray(rng.integers(1, T + 1, size=B), jnp.int32)
    o = ops.paged_decode_attention(q, jnp.asarray(k_pages, dtype),
                                   jnp.asarray(v_pages, dtype),
                                   jnp.asarray(perm, jnp.int32), lens)
    e = ref.decode_mha(q, k, v, length=lens)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(e, np.float32), atol=_tol(dtype) * 4)


def test_gather_paged_kv_roundtrip():
    P, page, KV, Dh, B, maxp = 10, 16, 2, 32, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    k_pages = jax.random.normal(ks[0], (P, KV, page, Dh))
    v_pages = jax.random.normal(ks[1], (P, KV, page, Dh))
    pt = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)
    kg, vg = ops.gather_paged_kv(k_pages, v_pages, pt)
    assert kg.shape == (B, KV, maxp * page, Dh)
    np.testing.assert_array_equal(np.asarray(kg[0, :, :page]), np.asarray(k_pages[1]))
    np.testing.assert_array_equal(np.asarray(vg[1, :, page:2 * page]),
                                  np.asarray(v_pages[4]))


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
