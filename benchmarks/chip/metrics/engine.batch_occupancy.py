"""Engine: decode tokens delivered in the window (every token after a
request's first) over decode steps × max_batch, in percent."""

from records import tokens_in_window


def read(run):
    steps = run.counters1["steps"] - run.counters0["steps"]
    decoded = sum(1 for _, i, _ in tokens_in_window(run) if i > 0)
    if steps <= 0:
        return None
    return 100.0 * decoded / (steps * run.serve["max_batch"])
