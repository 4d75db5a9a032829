"""Engine: mean interval between the starts of consecutive ``decode_step``
spans of the engine (profiler annotations, ``spans.py``) in the traced
seconds, in milliseconds: the decode loop's period seen from inside, where
``engine.step_wall_ms`` divides the client's decoding time by a count."""

import spans


def read(run):
    t = spans.table(run)
    starts = [s.start for s in t.spans("decode_step")] if t else []
    if len(starts) < 2:
        return None
    return (starts[-1] - starts[0]) / (len(starts) - 1) / 1e6
