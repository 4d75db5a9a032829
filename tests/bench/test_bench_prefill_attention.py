"""The reader of ``kernels.prefill_attention_roofline`` on a synthetic run
and a stubbed table: its pairing of prefill executions with the engine's
``prefill`` spans, its count per query-key pair in both families, and its
``None`` cases."""

import pytest

import manifest
import spans
import trace_reduce as tr
from model_spec import from_config
from test_bench_spans import DEV, MODEL, PEAKS, host, reader, synthetic_run

NAME = "kernels.prefill_attention_roofline"
PREFILL_ATTN = "jit(prefill)/while/body/closed_call/prefill_attention/pallas_call:"
PREFILL_MLP = "jit(prefill)/while/body/closed_call/mlp/dot_general:"


@pytest.fixture
def stub(monkeypatch):
    """Installs a table as the one every run reads."""
    def install(table):
        monkeypatch.setattr(spans, "table", lambda run: table)
    return install


def prefill_run():
    """Three prefill executions: the first dispatched before the trace
    opened (its span is missing), then two whose spans overlap, the
    second span opened before the first execution ran."""
    d = tr.Device(DEV, modules=[(3.00e9, 3.05e9, "jit_prefill(5)"),
                                (3.06e9, 3.08e9, "jit__decode_fn(7)"),
                                (3.20e9, 3.25e9, "jit_prefill(5)"),
                                (3.30e9, 3.38e9, "jit_prefill(6)")])
    run = synthetic_run(tr.Trace([d], [], (3.0e9, 3.4e9)))
    ops = [spans.Op(3.01e9, 3.04e9, DEV, PREFILL_ATTN),  # its span missing
           spans.Op(3.06e9, 3.07e9, DEV, PREFILL_ATTN),  # not a prefill
           spans.Op(3.20e9, 3.21e9, DEV, PREFILL_ATTN),  # 10 ms, 1,000 tokens
           spans.Op(3.21e9, 3.24e9, DEV, PREFILL_MLP),
           spans.Op(3.30e9, 3.32e9, DEV, PREFILL_ATTN)]  # 20 ms, 3,000 tokens
    waits = [host("prefill", 3.10e9, 3.26e9, prompt_len=1000, bucket=1024),
             host("prefill.wait", 3.25e9, 3.26e9),
             host("prefill", 3.15e9, 3.39e9, prompt_len=3000, bucket=4096)]
    return run, spans.Table(waits, {DEV: ops})


def test_prefill_attention_roofline_by_hand(stub):
    run, table = prefill_run()
    stub(table)
    mod = manifest.reader(NAME)
    per_pair = 4 * MODEL.layers * MODEL.heads * MODEL.head_dim
    assert mod.pair_flops(MODEL) == pytest.approx(per_pair)
    least = 0.0
    for n in (1000, 3000):
        fl = per_pair * n * (n + 1) / 2
        by = n * MODEL.layers * 2 * (MODEL.heads + MODEL.kv_heads) \
            * MODEL.head_dim * 2
        least += max(fl / PEAKS["bf16_flops_per_s"], by / PEAKS["hbm_bytes_per_s"])
    got = mod.read(run)
    assert got == pytest.approx(100.0 * least / 0.030)
    assert 0 < got < 100


def test_prefill_attention_pairs_in_order_of_end():
    mod = manifest.reader(NAME)
    run, table = prefill_run()
    execs = [(3.00e9, 3.05e9), (3.20e9, 3.25e9), (3.30e9, 3.38e9)]
    waits = sorted(table.spans("prefill"), key=lambda h: h.end)
    got = [(e, w.stats["prompt_len"]) for e, w in mod.pair(execs, waits)]
    assert got == [((3.20e9, 3.25e9), 1000), ((3.30e9, 3.38e9), 3000)]
    # a span whose execution ended after the trace pairs with nothing
    assert mod.pair(execs[:2], waits) == [(execs[1], waits[0])]


def test_prefill_attention_pair_count_of_the_latent_family():
    """DeepSeek-V2's plain form: queries and keys Dn + Dr wide, values Dv,
    every head, all layers; not the absorbed decode form."""
    ds = from_config("deepseek_v2_lite", manifest.config("deepseek_v2_lite"))
    mod = manifest.reader(NAME)
    want = 2 * ds.layers * ds.heads * (ds.qk_nope + ds.qk_rope + ds.v_head)
    assert mod.pair_flops(ds) == pytest.approx(want)
    assert want == 2 * 27 * 16 * (128 + 64 + 128)
    assert mod.qkvo_bytes(ds, 10) == 10 * 27 * 2 * 16 * (192 + 128) * 2


def test_prefill_attention_roofline_finds_nothing(stub):
    run, table = prefill_run()
    stub(None)
    assert reader(NAME)(run) is None
    # a program without the scope, as before the kernel had one
    stub(spans.Table(table.host, {DEV: [o._replace(scope=PREFILL_MLP)
                                        for o in table.ops[DEV]]}))
    assert reader(NAME)(run) is None
    # no prefill spans to pair with
    stub(spans.Table([], table.ops))
    assert reader(NAME)(run) is None
    stub(table)
    assert reader(NAME)(synthetic_run(None)) is None
