"""Kernels: the least time of decode attention over the device time of the
ops under the name scope ``paged_attention`` (``spans.py``), over every
traced execution of the decode program, in percent.

The least time of one execution is the larger of the attention FLOPs
(``flops._attn_flops``: each row's query over its ``context + 1`` keys) over
peak FLOP/s and the live K/V bytes those keys hold
(``flops.kv_bytes_per_token``) over HBM bandwidth.  Needed bytes come from
the model, not from what the implementation reads, so the share cannot pass
100%.  The rows and their K/V lengths at each execution come from the
client's records (``records.decoding_contexts``), as for
``model_step.decode_mfu``."""

import re

import flops
import spans
from records import decoding_contexts

DECODE = re.compile(r"_decode_fn")
SCOPE = "paged_attention"


def read(run):
    tr, t = run.trace, spans.table(run)
    if tr is None or t is None or run.trace_offset_ns is None:
        return None
    m = run.model
    least, device_ns = 0.0, 0.0
    for d in tr.devices:
        for s, e, name in d.modules:
            if not DECODE.search(name):
                continue
            contexts = decoding_contexts(run, run.to_host_s(s))
            ops = [o for o in t.ops_between(d.name, s, e) if spans.in_scope(o, SCOPE)]
            if not contexts or not ops:
                continue
            keys = sum(c + 1 for c in contexts)
            need = flops.least_time(
                sum(flops._attn_flops(m, 1, c + 1) for c in contexts),
                keys * flops.kv_bytes_per_token(m), run.peaks)
            least += need["seconds"]
            device_ns += sum(o.end - o.start for o in ops)
    if device_ns <= 0:
        return None
    return 100.0 * least / (device_ns / 1e9)
