"""Futures/promises semantics (HPX P1)."""
import threading
import time

import pytest

import repro.core as core
from repro.core.future import (Future, FutureError, Promise,
                               make_exceptional_future, make_ready_future,
                               unwrap, when_all, when_any)


def test_promise_future_basic(rt):
    p = Promise()
    f = p.future()
    assert not f.is_ready()
    p.set_value(42)
    assert f.is_ready() and f.has_value()
    assert f.get() == 42


def test_promise_single_shot(rt):
    p = Promise()
    p.set_value(1)
    with pytest.raises(FutureError):
        p.set_value(2)


def test_exception_propagates(rt):
    f = core.spawn(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        f.get()
    assert f.has_exception()


def test_then_chain(rt):
    f = core.spawn(lambda: 3)
    g = f.then_value(lambda x: x * 2).then_value(lambda x: x + 1)
    assert g.get() == 7


def test_long_then_chain_runs_every_link(rt):
    """A 2,000-link continuation chain runs every link once: none is lost,
    and resolving the chain does not exhaust the stack."""
    f = make_ready_future(0)
    for _ in range(2000):
        f = f.then_value(lambda x: x + 1)
    assert f.get(timeout=60) == 2000


def test_then_sees_exception(rt):
    f = make_exceptional_future(ValueError("boom"))
    g = f.then(lambda fut: "caught" if fut.has_exception() else "missed")
    assert g.get() == "caught"


def test_when_all_and_any(rt):
    fs = [core.spawn(lambda i=i: i) for i in range(20)]
    ready = when_all(fs).get()
    assert sorted(f.get() for f in ready) == list(range(20))
    slow = core.spawn(lambda: (time.sleep(0.5), "slow")[1])
    fast = make_ready_future("fast")
    assert when_any([slow, fast]).get() == 1


def test_when_all_empty(rt):
    assert when_all([]).get() == []


def test_unwrap_nested(rt):
    v = unwrap({"a": make_ready_future(1),
                "b": [make_ready_future(2), 3],
                "c": make_ready_future(make_ready_future(4))})
    assert v == {"a": 1, "b": [2, 3], "c": 4}


def test_get_timeout(rt):
    p = Promise()
    with pytest.raises(TimeoutError):
        p.future().get(timeout=0.05)


def test_nested_blocking_does_not_deadlock(rt):
    """Blocked tasks help along (HPX thread suspension analogue)."""

    def fib(n):
        if n < 2:
            return n
        a = core.spawn(fib, n - 1)
        return a.get() + fib(n - 2)

    assert core.spawn(fib, 13).get(timeout=60) == 233


def test_set_exception_reaches_late_registered_callbacks(rt):
    """Callbacks registered AFTER a future failed must still observe the
    exception — the remote-completion path registers its bookkeeping hook
    whenever the result frame happens to land, including 'already'."""
    f = make_exceptional_future(ValueError("late"))
    seen = []
    f.on_ready(lambda fut: seen.append(fut.exception()))
    assert len(seen) == 1 and isinstance(seen[0], ValueError)
    # and a .then() continuation attached late sees it too
    g = f.then(lambda fut: type(fut.exception()).__name__)
    assert g.get(timeout=10) == "ValueError"
    # value-projecting continuation propagates the error instead
    with pytest.raises(ValueError, match="late"):
        f.then_value(lambda v: v).get(timeout=10)


def test_callbacks_fire_outside_the_lock(rt):
    """A callback may re-enter the same future (get / another on_ready /
    then) without deadlocking — i.e. completion and the already-ready path
    must never hold the future's lock while running callbacks."""
    order = []

    # case 1: callback registered after completion re-enters immediately
    f = make_ready_future(10)
    f.on_ready(lambda fut: (order.append(fut.get(timeout=1)),
                            fut.on_ready(lambda g: order.append(g.get(timeout=1) + 1))))
    assert order == [10, 11]

    # case 2: callback registered before completion re-enters from _set
    # (wait() inside the callback would deadlock if _set held the lock)
    p = Promise()
    fut = p.future()
    fut.on_ready(lambda g: order.append((g.wait(timeout=1),
                                         type(g.exception()).__name__)))
    p.set_exception(RuntimeError("x"))
    assert order == [10, 11, (True, "RuntimeError")]


def test_promise_set_from_relays_value_and_exception(rt):
    src_ok = make_ready_future(5)
    dst: Promise = Promise()
    dst.set_from(src_ok)
    assert dst.future().get(timeout=1) == 5

    src_bad = make_exceptional_future(KeyError("k"))
    dst2: Promise = Promise()
    dst2.set_from(src_bad)
    with pytest.raises(KeyError):
        dst2.future().get(timeout=1)
