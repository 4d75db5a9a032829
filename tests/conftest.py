import pytest


@pytest.fixture(scope="session")
def rt():
    """Session-wide AMT runtime (hpx::init equivalent)."""
    import repro.core as core

    runtime = core.init(num_workers=4, policy="local")
    yield runtime
    core.finalize()


@pytest.fixture()
def rng():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture()
def net_factory(rt):
    """Leak-proof multi-locality bootstrap for tests: every runtime made
    through the factory is shut down (workers reaped) even when the test
    body raises — a failing test cannot strand processes and poison the
    rest of the suite."""
    import contextlib

    from repro import net as rnet

    with contextlib.ExitStack() as stack:
        yield lambda n, **kw: stack.enter_context(rnet.running(n, **kw))
