"""95th percentile (nearest rank) of every gap between two consecutive
tokens of a request, both delivered in the window, in milliseconds."""

from records import nearest_rank


def read(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r.token_t, r.token_t[1:])
            if run.t0 <= a and b < run.t1]
    p = nearest_rank(gaps, 0.95)
    return None if p is None else p * 1e3
