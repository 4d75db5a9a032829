"""The serving engine's spans on the JAX profiler's clock: a tiny paged
model served under ``jax.profiler.start_trace``, its ``.xplane.pb`` read
back with ``ProfileData``; the ring recorder beside it, off and on."""

import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.dist.plan import get_plan
from repro.models.model import build_model
from repro.obs import critical_path as cpm
from repro.obs import export, trace
from repro.serve.engine import Engine, ServeConfig

PROMPTS = [[5, 6, 7, 8], [100, 3, 50, 2, 9, 11, 40, 41, 42, 43, 44, 45,
                          46, 47, 48, 49, 50, 51, 52], [42, 7]]
MAX_NEW = 4

# span → the arguments it must carry in the profiler's trace
SPAN_ARGS = {
    "admit": {"admitted"},
    "decode_step": {"step_num", "batch"},
    "decode_step.dispatch": set(),
    "decode_step.wait": set(),
    "emit": {"finished"},
    "prefill": {"rid", "prompt_len", "bucket", "queued_ms"},
    "prefill.wait": set(),
}


@pytest.fixture(scope="module")
def served(rt):
    cfg = get_config("qwen25_3b", smoke=True)
    model = build_model(cfg, get_plan("serve"))
    params = model.init(jax.random.PRNGKey(1))
    eng = Engine(model, params, ServeConfig(
        max_batch=2, cache_len=64, max_new_tokens=MAX_NEW,
        name="engine#spans"))
    _serve(eng)  # compile every program outside the traced runs
    return eng


@pytest.fixture(autouse=True)
def _ring_off():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _serve(eng):
    """Serve ``PROMPTS``, then let the loop reach a step boundary, so every
    span it opened has closed."""
    futs = [eng.submit(p) for p in PROMPTS]
    outs = [f.get(timeout=300) for f in futs]
    assert [len(o) for o in outs] == [MAX_NEW + 1] * len(PROMPTS)
    eng.pause(timeout=60)
    eng.resume()


def _host_events(trace_dir):
    """(name, line, start_ns, end_ns, stats) of every host event."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            out.extend((e.name, i, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events)
    return out


@pytest.fixture(scope="module")
def profiled(served, tmp_path_factory):
    """Host events of one traced serving round, with the ring off; and
    what the ring held afterwards."""
    d = tmp_path_factory.mktemp("spans")
    trace.disable()
    trace.clear()
    jax.profiler.start_trace(str(d))
    try:
        _serve(served)
    finally:
        jax.profiler.stop_trace()
    return _host_events(d), trace.events()


def test_every_span_carries_its_arguments(profiled):
    events, _ = profiled
    for name, args in SPAN_ARGS.items():
        found = [st for n, _, _, _, st in events if n == name]
        assert found, name
        assert all(args <= set(st) for st in found), (name, found[:2])
    admitted = [st for n, _, _, _, st in events
                if n == "admit" and st["admitted"] > 0]
    assert sum(st["admitted"] for st in admitted) == len(PROMPTS)
    assert all(st["ready_ms"] >= 0.0 for st in admitted)
    prefills = [st for n, _, _, _, st in events if n == "prefill"]
    assert sorted(st["prompt_len"] for st in prefills) == sorted(
        len(p) for p in PROMPTS)
    for st in prefills:
        assert st["bucket"] >= st["prompt_len"] and st["queued_ms"] >= 0.0
    steps = [st for n, _, _, _, st in events if n == "decode_step"]
    assert len({st["step_num"] for st in steps}) == len(steps)
    assert all(1 <= st["batch"] <= 2 for st in steps)
    finished = sum(st["finished"] for n, _, _, _, st in events if n == "emit")
    assert finished == len(PROMPTS)


def test_children_nest_inside_their_parent(profiled):
    events, _ = profiled

    def spans(name):
        return [(line, s, e) for n, line, s, e, _ in events if n == name]

    for parent, children in (("decode_step", ("decode_step.dispatch",
                                              "decode_step.wait")),
                             ("prefill", ("prefill.wait",))):
        outer = spans(parent)
        for child in children:
            inner = spans(child)
            assert len(inner) == len(outer), child
            for line, s, e in inner:
                assert any(line == pl and ps <= s and e <= pe
                           for pl, ps, pe in outer), (child, s, e)
    # prefills run on the prefill pool, not on the decode loop's thread
    loop = {line for line, _, _ in spans("decode_step")}
    assert loop == {line for line, _, _ in spans("emit")}
    assert not loop & {line for line, _, _ in spans("prefill")}


def test_ring_stays_empty_while_disabled(profiled):
    _, ring = profiled
    assert ring == []


def test_ring_enabled_keeps_critical_path(served):
    trace.enable()
    _serve(served)
    tr = export.merged_trace()
    tags = [t for t in cpm.request_ids(tr) if t.startswith("engine#spans/")]
    assert len(tags) >= len(PROMPTS)
    for tag in tags[-len(PROMPTS):]:
        cp = cpm.critical_path(tr, tag)
        assert cp is not None
        whats = {iv.what for iv in cp.intervals}
        assert {"prefill", "decode_step"} <= whats, (tag, whats)
    names = {e[1] for e in trace.events()}
    assert set(SPAN_ARGS) <= names
