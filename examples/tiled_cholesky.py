"""Tiled Cholesky by futurization — the paper's linear-algebra showcase.

    PYTHONPATH=src python examples/tiled_cholesky.py

The factorization is expressed as a dataflow DAG: each tile op (potrf /
trsm / syrk / gemm) is a task whose inputs are futures of other tiles.
No global barrier anywhere — tasks fire the moment their tiles are ready,
which is exactly the paper's 'constraint-based synchronization'.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as core
from repro.core.dataflow import dataflow


def tiled_cholesky(A: np.ndarray, tile: int):
    """Right-looking blocked Cholesky as a dataflow DAG of jitted tile ops."""
    n = A.shape[0] // tile
    potrf = jax.jit(jnp.linalg.cholesky)
    trsm = jax.jit(lambda L, B: jax.scipy.linalg.solve_triangular(
        L, B.T, lower=True).T)
    syrk = jax.jit(lambda C, L: C - L @ L.T)
    gemm = jax.jit(lambda C, A_, B_: C - A_ @ B_.T)

    tiles = {(i, j): core.make_ready_future(
        jnp.asarray(A[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]))
        for i in range(n) for j in range(n) if j <= i}

    for k in range(n):
        tiles[(k, k)] = dataflow(potrf, tiles[(k, k)])
        for i in range(k + 1, n):
            tiles[(i, k)] = dataflow(trsm, tiles[(k, k)], tiles[(i, k)])
        for i in range(k + 1, n):
            tiles[(i, i)] = dataflow(syrk, tiles[(i, i)], tiles[(i, k)])
            for j in range(k + 1, i):
                tiles[(i, j)] = dataflow(gemm, tiles[(i, j)], tiles[(i, k)],
                                         tiles[(j, k)])
    out = np.zeros_like(A)
    for (i, j), fut in tiles.items():
        out[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile] = np.asarray(fut.get())
    return np.tril(out)


def main() -> None:
    core.init(num_workers=4)
    rng = np.random.default_rng(7)
    N, tile = 1024, 128
    X = rng.standard_normal((N, N)).astype(np.float32)
    A = X @ X.T + N * np.eye(N, dtype=np.float32)

    t0 = time.perf_counter()
    L = tiled_cholesky(A, tile)
    dt = time.perf_counter() - t0
    err = float(np.max(np.abs(L @ L.T - A)) / np.max(np.abs(A)))
    n_tiles = (N // tile) * (N // tile + 1) // 2
    print(f"N={N} tile={tile} ({n_tiles} tiles) in {dt * 1e3:.1f} ms, "
          f"reconstruction rel err {err:.2e}")
    print("tasks executed:",
          int(core.counters.get_value("/scheduler{default}/tasks/executed")))
    core.finalize()


if __name__ == "__main__":
    main()
