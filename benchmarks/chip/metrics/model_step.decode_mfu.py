"""Model step: the decode program's least time over its device time, in
percent of the chip's peak.

The least time of a step is the larger of its operations over peak
FLOP/s and its needed bytes (weights, the live K/V of each active row, the
new K/V) over HBM bandwidth, from ``flops.decode_step``.  The rows and
their K/V lengths at each decode program execution in the trace come from
the client's records of the delivered tokens, at the host time that the
execution maps to (``records.decoding_contexts``)."""

import flops
from records import decoding_contexts

DECODE = r"_decode_fn"


def read(run):
    tr = run.trace
    if tr is None or run.trace_offset_ns is None:
        return None
    steps, device_ns = [], 0
    for s, e, _ in tr.module_events(DECODE):
        contexts = decoding_contexts(run, run.to_host_s(s))
        if contexts:
            steps.append(contexts)
            device_ns += e - s
    if not steps or device_ns <= 0:
        return None
    least = flops.decode_least_time(run.model, steps, run.peaks)
    return 100.0 * least["seconds"] / (device_ns / 1e9)
