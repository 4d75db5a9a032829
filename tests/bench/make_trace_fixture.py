"""Records ``trace_fixture.xplane.pb``, the small TPU trace that
``test_bench_trace_reduce.py`` reduces.  Run it on a TPU host:

    python3 tests/bench/make_trace_fixture.py OUT_DIR

Three rounds of: program ``alpha`` inside the annotation ``bench_decode#<i>``,
a 5 ms host sleep inside ``host_gap``, then program ``beta``.  The device is
idle during each sleep, so the longest idle gaps overlap ``host_gap``.
"""

import glob
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def alpha(x):
    return jnp.tanh(x @ x)


def beta(x):
    return (x * 2.0).sum(axis=0)


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    a, b = jax.jit(alpha), jax.jit(beta)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((a(x), b(x)))
    jax.profiler.start_trace(out + "/raw")
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench_decode#{i}"):
            y = a(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host_gap"):
            time.sleep(0.005)
        b(y).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(out + "/raw/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(src, out + "/trace_fixture.xplane.pb")


if __name__ == "__main__":
    main(sys.argv[1])
