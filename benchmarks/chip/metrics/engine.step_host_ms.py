"""Engine: mean time from the end of one ``decode_step.wait`` span (the
step's blocking token readback) to the start of the next, in the traced
seconds, in milliseconds: all the host does between two device waits,
emitting tokens, admitting requests and dispatching the next step."""

from records import mean

import spans


def read(run):
    t = spans.table(run)
    waits = t.spans("decode_step.wait") if t else []
    m = mean([b.start - a.end for a, b in zip(waits, waits[1:])])
    return None if m is None else m / 1e6
