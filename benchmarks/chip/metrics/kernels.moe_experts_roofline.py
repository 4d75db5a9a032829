"""Kernels: the least time of the held experts' part of decode over the
device time of the ops under the name scope ``moe_experts`` (``spans.py``),
over every traced execution of the decode program, in percent.

The least time of one execution is the larger of the expert FLOPs and the
bytes of the held experts some row routes to (the family's
``expert_step(m, rows)``, under even routing) over the chip's peaks.  Needed
bytes come from the model, not from what the implementation reads, so the
share cannot pass 100%.  The rows decoding at each execution come from the
client's records (``records.decoding_contexts``), as for
``model_step.decode_mfu``.  A model family without experts has no
``expert_step``, and the metric is left out."""

import re

import flops
import manifest
import spans
from records import decoding_contexts

DECODE = re.compile(r"_decode_fn")
SCOPE = "moe_experts"


def read(run):
    step = getattr(manifest.family(run.model.model_type), "expert_step", None)
    tr, t = run.trace, spans.table(run)
    if step is None or tr is None or t is None or run.trace_offset_ns is None:
        return None
    least, device_ns = 0.0, 0.0
    for d in tr.devices:
        for s, e, name in d.modules:
            if not DECODE.search(name):
                continue
            contexts = decoding_contexts(run, run.to_host_s(s))
            ops = [o for o in t.ops_between(d.name, s, e) if spans.in_scope(o, SCOPE)]
            if not contexts or not ops:
                continue
            need = step(run.model, len(contexts))
            least += flops.least_time(need["flops"], need["bytes"],
                                      run.peaks)["seconds"]
            device_ns += sum(o.end - o.start for o in ops)
    if device_ns <= 0:
        return None
    return 100.0 * least / (device_ns / 1e9)
