"""The main-path Pallas kernels compile for a described TPU v5e at the
widths of the configs they serve — no chip needed: the TPU compiler is
installed and compiles for a topology it is only told about.  This is what
interpret mode cannot show (block tiling, VMEM limits), and a compile that
emits ``tpu_custom_call`` is a Mosaic kernel, not interpret-mode XLA.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.  Keep all such compiles in this one file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.dist.plan import get_plan
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.models.layers import mla_row_width
from repro.models.model import build_model

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


def _paged_geometry(cfg):
    """(KV, G, W, pools, value width) of a config's paged decode: K and V
    pools of its heads, or under MLA one latent pool of rows."""
    if cfg.mla:
        return 1, cfg.num_heads, mla_row_width(cfg), 1, cfg.kv_lora_rank
    KV = cfg.num_kv_heads
    return KV, cfg.num_heads // KV, cfg.head_dim, 2, cfg.head_dim


@pytest.mark.parametrize("arch", sorted(WIDTHS) + ["deepseek_v2_lite"])
def test_paged_decode_kernel_compiles(one_chip, arch):
    """The paged kernel as the TPU path runs it, on stacked pools read in
    place: max_batch 24, cache_len 4096, every row's pages in the pool."""
    KV, G, W, npool, vw = _paged_geometry(get_config(arch))
    B, page, maxp, L = 24, 16, 256, 3
    P = B * maxp + 1
    bf = jnp.bfloat16

    def attend(q, pools, new, layer, pt, pos):
        return ops.paged_attention_kernel(q, pools, new, layer, pt, pos,
                                          scale=0.1, value_width=vw,
                                          interpret=False)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((B, KV, G, W), bf), ((L, P, KV, page, W), bf), ((B, KV, W), bf),
        ((), jnp.int32), ((B, maxp), jnp.int32), ((B,), jnp.int32))]
    q, pool, new, *rest = args
    compiled = jax.jit(attend).lower(q, (pool,) * npool, (new,) * npool,
                                     *rest).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < B * KV * G * W * 4


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


_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1}
# `%name = dtype[dims]{layout} opcode(` — array-valued instructions only
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
_FREE = {"parameter", "get-tuple-element", "tuple", "bitcast"}


def _large_ops(hlo: str, min_bytes: int) -> dict:
    """Instructions of the compiled module that yield an array of at least
    ``min_bytes``, whatever its shape, leaving out fused computations' bodies
    (their fusion is the op) and ops that move no data."""
    fused = set(re.findall(r"kind=\w+, calls=%([\w.-]+)", hlo))
    out, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp in fused or m.group(4) in _FREE:
            continue
        n = _DTYPE_BYTES.get(m.group(2), 8)
        for d in filter(None, m.group(3).split(",")):
            n *= int(d)
        if n >= min_bytes:
            out[m.group(1)] = line.strip()[:160]
    return out


def _donated_outputs(hlo: str) -> set:
    """Names of the ops whose results the entry returns in a donated input's
    buffer, looking through bitcasts (at depth XLA writes a pool as rows of
    a flattened bitcast and returns the bitcast back)."""
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                        hlo).group(1)
    idx = [int(i) for i in re.findall(r"\{(\d+)\}: \(", aliased)]
    entry = hlo[hlo.index("\nENTRY "):]
    root = re.search(r"ROOT %\S+ = .* tuple\((.*?)\)", entry).group(1)
    outs = [o.strip().lstrip("%") for o in root.split(",")]
    bitcast_of = dict(re.findall(r"%(\S+) = \S+ bitcast\(%([^)]+)\)", entry))
    names = set()
    for i in idx:
        name = outs[i]
        while name in bitcast_of:
            name = bitcast_of[name]
        names.add(name)
    return names


def test_paged_decode_step_reads_pools_in_place(one_chip):
    """The serving decode program (XLA attention) at qwen25_3b's widths, 2
    layers, batch 24, 256 pages a row, 6,145 pages, cache donated: the K/V
    pools stay out of the layer scan.  The only ops that yield as many bytes
    as one layer's slice of a pool are the step's writes, one per pool, each
    an output aliased to its donated pool; and the temporaries are no more
    than the one layer's K and V page lists the attention gathers (each one
    page short of a slice) plus less than one more pool slice.  Passing the
    pools through the scan as ``xs``/``ys`` costs a slice copied out and
    back per layer and a copy of each whole pool: 0.38 GiB of temporaries
    here."""
    cfg = dataclasses.replace(get_config("qwen25_3b"), num_layers=2,
                              param_dtype="bfloat16")
    model = build_model(cfg, get_plan("serve"))
    B, maxp, page = 24, 256, 16
    P, KV, Dh = B * maxp + 1, cfg.num_kv_heads, cfg.head_dim

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = {n: on_chip(s) for n, s in model.abstract_params().items()}
    cache = {n: on_chip(s) for n, s in
             model.paged_cache_specs(P, page, B, maxp).items()}
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_paged, donate_argnums=(1,)).lower(
        params, cache, token).compile()
    hlo = compiled.as_text()

    slice_bytes = P * KV * page * Dh * 2
    gathered_bytes = 2 * B * maxp * page * KV * Dh * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < gathered_bytes + slice_bytes, temp
    large = _large_ops(hlo, slice_bytes)
    writes = _donated_outputs(hlo) & set(large)
    assert len(writes) == 2, large  # one in-place write per pool
    assert set(large) == writes, large


def test_paged_latent_decode_step_runs_the_kernel_in_place(one_chip,
                                                         monkeypatch):
    """The serving decode program as the TPU compiles it (the paged kernel)
    at deepseek_v2_lite's widths, 2 layers (the dense one and one holding 8
    experts), batch 24, 256 pages a row, 6,145 pages, cache donated: the
    latent pools are read in place.  The temporaries stay under one
    layer's page list of one pool, so nothing gathers the pages, slices
    ``pool[layer]`` or selects over a gathered copy; and the only ops that
    yield as many bytes as one layer's slice of a pool are the step's
    writes, one per pool, each an output aliased to its donated pool."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("deepseek_v2_lite"), num_layers=2,
                              param_dtype="bfloat16", expert_shard=(0, 8))
    model = build_model(cfg, get_plan("serve"))
    B, maxp, page = 24, 256, 16
    P, C = B * maxp + 1, mla_row_width(cfg)

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = {n: on_chip(s) for n, s in model.abstract_params().items()}
    cache = {n: on_chip(s) for n, s in
             model.paged_cache_specs(P, page, B, maxp).items()}
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_paged, donate_argnums=(1,)).lower(
        params, cache, token).compile()
    hlo = compiled.as_text()

    assert "tpu_custom_call" in hlo
    page_list_bytes = B * maxp * page * C * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < page_list_bytes, temp
    large = _large_ops(hlo, P * page * C * 2)
    writes = _donated_outputs(hlo) & set(large)
    assert len(writes) == 2, large  # one in-place write per pool
    assert set(large) == writes, large


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
