"""KV cache: mean of the ``pages/in_use`` gauge over ``pages/capacity``,
sampled through the window, in percent."""

from records import mean


def read(run):
    share = [used / cap for t, used, cap in run.pages
             if run.t0 <= t < run.t1 and cap > 0]
    m = mean(share)
    return None if m is None else 100.0 * m
