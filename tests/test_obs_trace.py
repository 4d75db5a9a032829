"""Trace recorder: ring-buffer correctness, context propagation, Chrome
conversion, and the disabled-cost contract (repro.obs.trace / export)."""

import threading

import pytest

from repro.obs import export, trace


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts disabled with empty buffers and leaves no state."""
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


# ----------------------------------------------------------------- disabled
def test_disabled_records_nothing():
    with trace.span("x", "t"):
        trace.instant("i", "t")
        trace.async_begin("r", 1)
        trace.async_end("r", 1)
    trace.complete("c", "t", 0.0)
    assert trace.events() == []


def test_disabled_span_is_shared_noop():
    assert trace.span("a", "t") is trace.span("b", "t")


def test_disabled_profiled_span_records_nothing():
    with trace.span("decode_step", "serve", step_num=3, reqs=["a"]) as s:
        s.set(finished=1)
    assert s is not trace.span("x", "t")  # a profiler annotation, not the no-op
    assert trace.events() == []


def test_span_open_across_disable_drops_cleanly():
    trace.enable()
    s = trace.span("x", "t")
    with s:
        trace.disable()
    assert trace.events() == []  # no half-recorded span


# ------------------------------------------------------------------- spans
def test_span_records_complete_event_with_args():
    trace.enable()
    with trace.span("work", "sched", pool="default"):
        pass
    evs = trace.events()
    assert len(evs) == 1
    ph, name, cat, ts, dur, eid, args = evs[0]
    assert (ph, name, cat) == ("X", "work", "sched")
    assert dur >= 0.0 and args == {"pool": "default"}


def test_profiled_span_adds_set_args_in_ring():
    trace.enable()
    with trace.span("emit", "serve", batch=2, reqs=["r0:1"]) as s:
        s.set(finished=1)
    (ev,) = trace.events()
    assert ev[1] == "emit"
    assert ev[6] == {"batch": 2, "reqs": ["r0:1"], "finished": 1}


def test_nested_span_records_parent_context():
    trace.enable()
    with trace.span("outer", "t") as outer:
        assert trace.current_context() == outer.sid
        with trace.span("inner", "t"):
            pass
    inner = [e for e in trace.events() if e[1] == "inner"][0]
    assert inner[6]["parent"] == f"{outer.sid[0]}:{outer.sid[1]}"
    assert trace.current_context() is None


def test_with_context_installs_foreign_parent():
    trace.enable()
    with trace.with_context((7, 42)):
        with trace.span("child", "net"):
            pass
    child = [e for e in trace.events() if e[1] == "child"][0]
    assert child[6]["parent"] == "7:42"


def test_flow_markers_surround_span():
    trace.enable()
    fid = trace.new_id()
    with trace.span("send", "net", flow_out=fid):
        pass
    with trace.span("recv", "net", flow_in=fid):
        pass
    phases = {e[0] for e in trace.events()}
    assert phases == {"X", "s", "f"}
    s = [e for e in trace.events() if e[0] == "s"][0]
    f = [e for e in trace.events() if e[0] == "f"][0]
    assert s[5] == f[5] == tuple(fid)


# ------------------------------------------------------------- ring buffer
def test_ring_wraparound_keeps_newest_and_counts_drops():
    buf = trace.TraceBuffer(capacity=8, tid=1, thread_name="t", epoch=0)
    for i in range(20):
        buf.append(("i", f"e{i}", "t", float(i), 0.0, None, None))
    evs, dropped = buf.snapshot()
    assert dropped == 12
    assert [e[1] for e in evs] == [f"e{i}" for i in range(12, 20)]


def test_ring_concurrent_writers_wraparound():
    """Each thread owns its own ring (the no-lock invariant); under heavy
    concurrent appends with wraparound every snapshot stays internally
    consistent: newest-suffix per thread, exact drop accounting."""
    trace.enable(capacity=64)
    n_threads, n_events = 8, 500
    barrier = threading.Barrier(n_threads)

    def writer(k: int) -> None:
        barrier.wait()
        for i in range(n_events):
            trace.instant(f"w{k}", "t", i=i)

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    bufs = [b for b in trace.export_buffers()
            if b["events"] and b["events"][0][1].startswith("w")]
    assert len(bufs) == n_threads
    for b in bufs:
        names = {e[1] for e in b["events"]}
        assert len(names) == 1  # single-writer: no cross-thread bleed
        assert len(b["events"]) == 64
        assert b["dropped"] == n_events - 64
        seq = [e[6]["i"] for e in b["events"]]
        assert seq == list(range(n_events - 64, n_events))  # newest suffix


def test_clear_drops_events_and_reregisters():
    trace.enable()
    trace.instant("before", "t")
    trace.clear()
    assert trace.events() == []
    trace.instant("after", "t")
    assert [e[1] for e in trace.events()] == ["after"]


# ----------------------------------------------------------- chrome export
def test_chrome_conversion_shapes():
    trace.enable()
    fid = trace.new_id()
    with trace.span("send", "net", flow_out=fid, dst=1):
        pass
    trace.instant("mark", "t")
    trace.async_begin("request", 5, "serve")
    trace.async_end("request", 5, "serve")
    tr = export.merged_trace()
    by_ph = {}
    for e in tr["traceEvents"]:
        by_ph.setdefault(e["ph"], []).append(e)
    assert {"M", "X", "s", "i", "b", "e"} <= set(by_ph)
    x = by_ph["X"][0]
    assert x["ts"] >= 0 and x["dur"] >= 0  # µs, clock-corrected
    assert by_ph["s"][0]["id"] == f"{fid[0]}:{fid[1]}"
    assert by_ph["b"][0]["id"] == by_ph["e"][0]["id"]
    procs = [e for e in by_ph["M"] if e["name"] == "process_name"]
    assert procs and procs[0]["args"]["name"].startswith("locality#")


def test_flow_links_audit():
    trace.enable()
    fid = trace.new_id()
    with trace.span("send", "net", flow_out=fid):
        pass
    with trace.span("recv", "net", flow_in=fid):
        pass
    links = export.flow_links(export.merged_trace())
    key = f"{fid[0]}:{fid[1]}"
    assert links[key]["src"] is not None and links[key]["dst"] is not None
