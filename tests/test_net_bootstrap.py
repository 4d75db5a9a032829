"""Leak-proof bootstrap: worker processes are reaped even when the code
between bootstrap and shutdown raises (``net.running`` context manager and
the ``net_factory`` fixture).  A stranded worker would idle for the test
runner's lifetime and poison every later bootstrap (the one-runtime-per-
process invariant), so teardown-on-failure is a correctness property."""

import pytest

from repro import net as rnet


def test_running_reaps_workers_when_body_raises(rt):
    procs = []
    with pytest.raises(RuntimeError, match="boom"):
        with rnet.running(3) as net:
            procs = list(net._procs.values())
            assert len(procs) == 2 and all(p.is_alive() for p in procs)
            raise RuntimeError("boom")
    assert rnet.current() is None, "runtime must be uninstalled"
    for p in procs:
        p.join(timeout=30)
    assert all(not p.is_alive() for p in procs), "workers must be reaped"


def test_net_factory_tears_down_between_tests(rt, net_factory):
    net = net_factory(2)
    assert rnet.current() is net and net.n_localities == 2
    assert rnet.run_on(1, _probe).get(timeout=60) == 1
    # no explicit shutdown: the fixture's ExitStack owns it — verified by
    # the next test being able to bootstrap at all


def test_bootstrap_after_factory_teardown(rt):
    assert rnet.current() is None, "previous fixture leaked its runtime"
    with rnet.running(1) as net:  # degenerate single-locality bootstrap
        assert net.is_root() and net.n_localities == 1 and not net._procs
    assert rnet.current() is None


def _probe(rt_remote):
    return rt_remote.locality


@pytest.mark.parametrize("platforms", ["", "tpu", "tpu,cpu"])
def test_bootstrap_refuses_workers_that_would_share_a_chip(
        rt, tmp_path, monkeypatch, platforms):
    """One process per chip: on a host with a TPU, workers that inherit a
    platform list reaching it are refused at once, before any spawn."""
    from repro.net import locality

    (tmp_path / "accel0").touch()
    monkeypatch.setattr(locality, "_TPU_DEVICE_GLOBS",
                        (str(tmp_path / "accel*"),))
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match="one process per chip"):
        rnet.bootstrap(2, timeout=5.0)
    assert rnet.current() is None
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with rnet.running(2) as net:  # CPU-pinned workers may still spawn
        assert rnet.run_on(1, _probe).get(timeout=60) == 1
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(RuntimeError, match="one process per chip"):
            net.spawn_locality()
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
