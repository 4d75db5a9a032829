"""The main-path Pallas kernels compile for a described TPU v5e at the
widths of the configs they serve — no chip needed: the TPU compiler is
installed and compiles for a topology it is only told about.  This is what
interpret mode cannot show (block tiling, VMEM limits), and a compile that
emits ``tpu_custom_call`` is a Mosaic kernel, not interpret-mode XLA.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.  Keep all such compiles in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention_fwd,
                                            paged_decode_attention_fwd)
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd

# (num_heads, num_kv_heads, head_dim) of the serving configs
WIDTHS = {"qwen25_3b": (16, 2, 128), "starcoder2_3b": (24, 2, 128)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_kernel_compiles(one_chip, arch):
    H, KV, Dh = WIDTHS[arch]
    B, page, maxp, P = 8, 16, 128, 1025  # max_batch 8, cache_len 2048
    bf = jnp.bfloat16
    hlo = _compile(paged_decode_attention_fwd, one_chip,
                   ((B, KV, H // KV, Dh), bf), ((P, KV, page, Dh), bf),
                   ((P, KV, page, Dh), bf), ((B, maxp), jnp.int32),
                   ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_dense_decode_kernel_compiles(one_chip, arch):
    H, KV, Dh = WIDTHS[arch]
    B, T = 8, 2048
    bf = jnp.bfloat16
    hlo = _compile(decode_attention_fwd, one_chip,
                   ((B, KV, H // KV, Dh), bf), ((B, KV, T, Dh), bf),
                   ((B, KV, T, Dh), bf), ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_flash_attention_kernel_compiles(one_chip, arch):
    H, KV, Dh = WIDTHS[arch]
    R, S = H, 1024  # one request's heads, a 1024-token prefill bucket
    bf = jnp.bfloat16
    hlo = _compile(flash_attention_fwd, one_chip,
                   ((R, S, Dh), bf), ((R, S, Dh), bf), ((R, S, Dh), bf))
    assert "tpu_custom_call" in hlo


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "compile-refused: the (1, chunk) dt block on a (B·H, S) array breaks the "
    "TPU tiling rule (last two block dims divisible by (8, 128) or equal to "
    "the array's); the kernel is on no model path"))
def test_ssd_scan_kernel_compiles(one_chip):
    BH, S, P, N, chunk = 48, 1024, 64, 128, 256  # mamba2_780m, batch 1
    f32 = jnp.float32
    hlo = _compile(lambda *a: ssd_scan_fwd(*a, chunk=chunk), one_chip,
                   ((BH, S, P), f32), ((BH, S), f32), ((BH,), f32),
                   ((BH, S, N), f32), ((BH, S, N), f32))
    assert "tpu_custom_call" in hlo


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=(
    "compile-refused: Mosaic has no lowering for the kernel's in-kernel "
    "dynamic_slice (Unimplemented primitive); the kernel is on no model "
    "path"))
def test_rglru_scan_kernel_compiles(one_chip):
    B, S, W = 1, 1024, 2560  # recurrentgemma_2b lru_width
    f32 = jnp.float32
    hlo = _compile(rglru_scan_fwd, one_chip, ((B, S, W), f32), ((B, S, W), f32))
    assert "tpu_custom_call" in hlo
