"""Device: share of the traced window in which no op ran on the chip, in
percent (1 − busy union / window, from the trace)."""


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
