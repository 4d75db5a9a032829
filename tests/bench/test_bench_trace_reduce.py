"""trace_reduce: span arithmetic by hand, and a small trace recorded on a
TPU v5e (``trace_fixture.xplane.pb``, made by ``make_trace_fixture.py``)."""

from pathlib import Path

import pytest

import trace_reduce as tr

FIXTURE = Path(__file__).with_name("trace_fixture.xplane.pb")
# the device plane's clock ran 1.1 ms ahead of the host plane's when the
# fixture was recorded: a program may start that much before its annotation
CLOCK_SLACK_NS = 5_000_000


def test_merge_overlap_complement():
    busy = tr.merge([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tr.overlap(busy, [(2, 6)]) == 2
    assert tr.complement(busy, (0, 12)) == [(3, 5), (9, 12)]
    assert tr.complement(busy, (4, 6)) == [(4, 5)]


def synthetic():
    d = tr.Device("/device:TPU:0",
                  ops=[(0, 10, "fusion.1"), (10, 30, "fusion.2"),
                       (50, 60, "fusion.1"), (90, 100, "copy.3")],
                  modules=[(0, 30, "jit__decode_fn(7)"), (50, 60, "jit_prefill(3)"),
                           (90, 100, "jit__decode_fn(7)")])
    d.busy = tr.merge((s, e) for s, e, _ in d.ops)
    host = sorted([(28, 52, "bench_decode#0"), (61, 89, "host_gap"),
                   (65, 70, "PjitFunction(fold_in)"), (35, 49, "bench_clock#0")])
    return tr.Trace([d], host, (0, 100))


def test_busy_programs_and_gaps_by_hand():
    t = synthetic()
    assert t.busy_s() == pytest.approx(50e-9)
    assert t.busy_s([(20, 55)]) == pytest.approx(15e-9)
    assert t.module_s(r"_decode_fn") == pytest.approx(40e-9)
    assert t.module_s(r"prefill") == pytest.approx(10e-9)
    assert t.top_ops(2) == [["jit__decode_fn/fusion.2", 20e-9], ["jit__decode_fn/fusion.1", 10e-9]]
    gaps = t.idle_gaps(5)
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9])
    assert gaps[0][0].startswith("after jit_prefill; host: host_gap")
    assert "bench_decode" in gaps[1][0] and "#" not in gaps[1][0]
    assert "bench_clock" not in gaps[1][0]  # clock marks are no host work
    assert [a[2] for a in t.annotations(r"^bench_decode#\d+$")] == ["bench_decode#0"]


def test_recorded_tpu_trace():
    t = tr.reduce(str(FIXTURE))
    assert len(t.devices) == 1
    alpha = t.module_events(r"alpha")
    beta = t.module_events(r"beta")
    assert len(alpha) == 3 and len(beta) == 3
    busy = t.busy_s()
    programs = t.module_s(r"alpha|beta")
    assert 0 < busy <= t.window_s
    # the ops of the two programs are the busy time, to within the gaps
    # between ops inside a program
    assert 0.8 * programs <= busy <= programs * 1.01
    # the three 5 ms host sleeps are idle gaps named by the sleep's
    # annotation (the longest gap is the trace's tail, after the last beta)
    gaps = [g for g in t.idle_gaps(6) if "host_gap" in g[0]]
    assert len(gaps) >= 3 and all(s >= 0.004 for _, s in gaps[:3])
    anns = t.annotations(r"^bench_decode#\d+$")
    assert len(anns) == 3
    # each alpha ran inside its host annotation, to within the slack
    # between the device's and the host's clocks
    for (a0, a1, _), (s, e, _) in zip(sorted(anns), alpha):
        assert a0 - CLOCK_SLACK_NS <= s and e <= a1 + CLOCK_SLACK_NS
