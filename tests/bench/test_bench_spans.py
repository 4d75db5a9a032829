"""spans.py on a small recorded TPU trace, the three readers of the engine's
spans and the decode program's scopes on a synthetic run and a stubbed
table (their ``None`` cases too), and a whole traced run on the CPU."""

from pathlib import Path

import jax
import pytest

import bench_tiny
import flops
import manifest
import records
import run_cell
import spans
import trace_reduce as tr
from model_spec import from_config

FIXTURE = str(Path(__file__).with_name("trace_fixture.xplane.pb"))
DEV = "/device:TPU:0"
ATTN = "jit(_decode_fn)/while/body/closed_call/paged_attention/dot_general:"
MLP = "jit(_decode_fn)/while/body/closed_call/mlp/dot_general:"


def reader(name):
    return manifest.reader(name).read


# ------------------------------------------------------------- spans.load
def test_fixture_ops_and_host_events_match_trace_reduce():
    t = spans.load(FIXTURE)
    ref = tr.reduce(FIXTURE)
    ops = sorted((o.start, o.end) for o in t.ops[DEV])
    ref_ops = sorted((s, e) for s, e, _ in ref.devices[0].ops)
    assert len(ops) == len(ref_ops) == 12
    assert all(abs(a - c) < 5 and abs(b - d) < 5  # ns, rounding
               for (a, b), (c, d) in zip(ops, ref_ops))
    assert [(h.start, h.end, h.name) for h in t.host] == ref.host
    # the op_name metadata of a fused op is its metadata's ``tf_op`` stat
    assert {o.scope for o in t.ops[DEV]} == {
        None, "jit(alpha)/dot_general:", "jit(beta)/reduce_sum:"}
    assert len(t.spans("bench_decode#0")) == 1
    assert {h.thread for h in t.host if h.name.startswith("bench_decode")}


def test_scope_match_and_leaf_ops():
    op = spans.Op(0, 1, DEV, ATTN)
    assert spans.in_scope(op, "paged_attention")
    assert not spans.in_scope(op, "attention")
    assert not spans.in_scope(spans.Op(0, 1, DEV, None), "paged_attention")
    t = spans.Table([], {DEV: [spans.Op(0, 10, DEV, "while"),
                               spans.Op(1, 4, DEV, ATTN),
                               spans.Op(5, 9, DEV, MLP),
                               spans.Op(12, 13, DEV, ATTN)]})
    assert [o.start for o in t.leaf_ops(DEV)] == [1, 5, 12]
    assert [o.start for o in t.ops_between(DEV, 0, 12)] == [1, 5]
    assert t.ops_between("/device:TPU:1", 0, 12) == []


def test_table_is_none_without_a_trace():
    run = synthetic_run(trace=None)
    assert spans.table(run) is None
    run = synthetic_run()
    run.workload = "no.such.cell"
    assert spans.table(run) is None


# -------------------------------------------------------------- readers
MODEL = from_config("qwen25_3b", manifest.config("qwen25_3b"))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def request(idx, prompt, token_t):
    from traffic_common import Request

    r = Request(idx, [1] * prompt, len(token_t) - 1, 0.0)
    r.token_t = list(token_t)
    r.tokens = [2] * len(token_t)
    return r


def synthetic_run(trace="default"):
    """Two requests decoding from host time 1 s on, two decode executions
    on the device at host times 2 s and 3 s (the profiler's clock runs
    1e9 ns ahead of the host's)."""
    reqs = [request(0, 1000, [1.0, 2.5, 3.5, 9.0]),
            request(1, 3000, [1.0, 2.5, 3.5, 9.0])]
    if trace == "default":
        d = tr.Device(DEV, modules=[(3.0e9, 3.1e9, "jit__decode_fn(7)"),
                                    (3.2e9, 3.25e9, "jit_prefill(3)"),
                                    (4.0e9, 4.1e9, "jit__decode_fn(7)")])
        trace = tr.Trace([d], [], (3.0e9, 4.1e9))
    return records.Run(
        workload="qwen25_3b.decode_long", model=MODEL, serve={"max_batch": 2},
        peaks=PEAKS, seconds=10.0, requests=reqs, t0=0.0, t1=10.0,
        setup_s=1.0, counters0={"steps": 0.0}, counters1={"steps": 10.0},
        trace=trace, trace_offset_ns=1e9)


def host(name, start, end, **stats):
    return spans.HostSpan(start, end, name, 1, stats)


@pytest.fixture
def stub(monkeypatch):
    """Installs a table as the one every run reads."""
    def install(table):
        monkeypatch.setattr(spans, "table", lambda run: table)
    return install


def test_step_ms_by_hand(stub):
    stub(spans.Table([host("decode_step", 0, 90e6, step_num=4),
                      host("decode_step.wait", 10e6, 80e6),
                      host("decode_step", 100e6, 190e6, step_num=5),
                      host("emit", 190e6, 192e6),
                      host("decode_step", 220e6, 300e6, step_num=6)], {}))
    assert reader("engine.step_ms")(synthetic_run()) == pytest.approx(110.0)


def test_step_host_ms_by_hand(stub):
    stub(spans.Table([host("decode_step.wait", 10e6, 80e6),
                      host("decode_step.wait", 83e6, 150e6),
                      host("decode_step.wait", 155e6, 200e6)], {}))
    # gaps 3 ms and 5 ms
    assert reader("engine.step_host_ms")(synthetic_run()) == pytest.approx(4.0)


@pytest.mark.parametrize("metric", ["engine.step_ms", "engine.step_host_ms"])
def test_span_readers_find_nothing(stub, metric):
    stub(None)  # a run without a trace, or a system without these spans
    assert reader(metric)(synthetic_run()) is None
    stub(spans.Table([host("decode_step", 0, 1), host("decode_step.wait", 0, 1),
                      host("bench_clock#0", 2, 3)], {}))
    assert reader(metric)(synthetic_run()) is None


def test_paged_attention_roofline_by_hand(stub):
    ops = [spans.Op(3.00e9, 3.02e9, DEV, ATTN),   # execution 1: 20 ms
           spans.Op(3.02e9, 3.05e9, DEV, MLP),
           spans.Op(3.21e9, 3.22e9, DEV, ATTN),   # inside the prefill: no
           spans.Op(4.00e9, 4.01e9, DEV, ATTN),   # execution 2: 10 + 10 ms
           spans.Op(4.02e9, 4.03e9, DEV, ATTN),
           spans.Op(4.03e9, 4.09e9, DEV, None)]
    stub(spans.Table([], {DEV: ops}))
    run = synthetic_run()
    # at host time 2 s each row holds its prompt and one token; at 3 s, two
    contexts = [[1000, 3000], [1001, 3001]]
    least = 0.0
    for cs in contexts:
        fl = sum(flops._attn_flops(MODEL, 1, c + 1) for c in cs)
        by = sum(c + 1 for c in cs) * flops.kv_bytes_per_token(MODEL)
        assert by / PEAKS["hbm_bytes_per_s"] > fl / PEAKS["bf16_flops_per_s"]
        least += by / PEAKS["hbm_bytes_per_s"]
    got = reader("kernels.paged_attention_roofline")(run)
    assert got == pytest.approx(100.0 * least / 0.040)
    assert 0 < got < 100


def test_paged_attention_roofline_finds_nothing(stub):
    run = synthetic_run()
    stub(None)
    assert reader("kernels.paged_attention_roofline")(run) is None
    # no op carries a scope stat (a trace without op metadata)
    stub(spans.Table([], {DEV: [spans.Op(3.0e9, 3.05e9, DEV, None)]}))
    assert reader("kernels.paged_attention_roofline")(run) is None
    # ops, but none under the scope (a program without named scopes)
    stub(spans.Table([], {DEV: [spans.Op(3.0e9, 3.05e9, DEV, MLP)]}))
    assert reader("kernels.paged_attention_roofline")(run) is None
    # a run without its trace or its clock marks
    stub(spans.Table([], {DEV: [spans.Op(3.0e9, 3.05e9, DEV, ATTN)]}))
    assert reader("kernels.paged_attention_roofline")(synthetic_run(None)) is None
    run.trace_offset_ns = None
    assert reader("kernels.paged_attention_roofline")(run) is None


# ------------------------------------------------------------ whole run
NEW = ("engine.step_ms", "engine.step_host_ms",
       "kernels.paged_attention_roofline")


def test_traced_run_reads_the_engine_spans():
    """On the CPU the trace holds the engine's spans but no device plane:
    the two span readers read, the kernel's share is left out.  The cell
    is named apart from the tiny runs of the other tests, which share the
    trace directory of their cell."""
    import repro.core as core

    core.init(pools={"default": 2, "prefill": 2, "io": 1})
    metrics = [{"name": n, "unit": "-"} for n in
               ("engine.step_wall_ms",) + NEW]
    out = run_cell.run(
        {"name": "tiny.spans", "chips": 1}, bench_tiny.QWEN, bench_tiny.BACKLOG,
        2**31 + 29, 2.0, True, bench_tiny.PEAKS, jax.devices()[:1], metrics,
        {**manifest.limits(bench_tiny.CELL), "tokens_compared": 20},
        run_cell.CompileCounter())
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert {"engine.step_ms", "engine.step_host_ms"} <= set(got)
    assert "kernels.paged_attention_roofline" not in got
    assert 0 < got["engine.step_host_ms"]["value"] < got["engine.step_ms"]["value"]
