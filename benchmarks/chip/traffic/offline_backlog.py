"""Offline backlog: every request is queued before the window opens.

The pool is large enough that at least ``min_queued`` requests still wait
when the window closes, so every batch slot stays busy throughout.  The
window opens once ``open_after_completed`` requests have finished: by then
the first batch, admitted all at once, has given way to requests admitted
one by one as others finish, as in the steady state of a long backlog.
All requests are due at submission.

The lengths come in one stratified order that is the same for every seed
(``SCHEDULE``), so every run's window holds the same work; the seed draws
the token ids (and the weights).
"""

from __future__ import annotations

import time

from traffic_common import (
    Request, host_rng, lognormal_lengths, stratified_order, token_ids)

OPEN_TIMEOUT_S = 600
SCHEDULE = 0  # the host stream of the length order, whatever the seed


def generate(mix: dict, seed: int, seconds: float, vocab: int):
    n, block = int(mix["requests"]), int(mix["block"])
    prompts = lognormal_lengths(mix["prompt"], n)
    outputs = lognormal_lengths(mix["output"], n)
    order_p = stratified_order(n, block, host_rng(SCHEDULE, 1))
    order_o = stratified_order(n, block, host_rng(SCHEDULE, 2))
    ids = host_rng(seed, 3)
    return [Request(i, token_ids(ids, prompts[order_p[i]], vocab),
                    int(outputs[order_o[i]]) - 1, 0.0) for i in range(n)]


def run(mix: dict, submit, requests, seconds: float, ready, on_open) -> float:
    """Submit everything, wait for the steady state and for ``ready()`` (the
    warm-up's requests answered), hold the window open; returns the time
    the window opened."""
    now = time.perf_counter()
    for r in requests:
        r.sent_t = now
        submit(r)
    need = int(mix["open_after_completed"])
    deadline = time.perf_counter() + OPEN_TIMEOUT_S
    while not ready() or sum(1 for r in requests if r.done_t) < need:
        failed = [r.failed for r in requests if r.failed]
        if failed:
            raise RuntimeError(f"requests failed before the window: {failed[:3]}")
        if time.perf_counter() > deadline:
            raise RuntimeError(f"fewer than {need} requests finished in "
                               f"{OPEN_TIMEOUT_S} s")
        time.sleep(0.005)
    t0 = time.perf_counter()
    on_open(t0)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    return t0
