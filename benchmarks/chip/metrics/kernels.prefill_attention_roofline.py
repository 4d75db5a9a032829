"""Kernels: the least time of the prefill's causal self-attention over the
device time of the ops under the name scope ``prefill_attention``
(``spans.py``), over every traced execution of the prefill program, in
percent.

Each execution of the prefill program (``jit_prefill``) is paired with the
engine's ``prefill`` span that waited on it, for that span's
``prompt_len``: both in order of end time (two prefill workers overlap
their spans, but the device runs their programs one after the other, and
a span ends soon after its program does).  An execution that ended before
its candidate span opened belongs to a span the trace missed, and a span
whose execution the trace missed pairs with nothing.

The least time of one execution of ``n`` valid tokens is the larger of its
causal FLOPs, ``n (n + 1) / 2`` query-key pairs at the family's count per
pair, over peak FLOP/s, and the bf16 bytes of the q, k, v and o rows of its
``n`` tokens in every layer over HBM bandwidth.  The count per pair is the
second difference of the family's ``prefill`` count in ``n`` (its only
quadratic term); q, k, v and o take the plain form's per-head widths where
the family has them (latent attention) and the GQA heads otherwise.  The
work comes from the model, not from the padded bucket the program runs, so
the share cannot pass 100%.  A program without the scope gives ``None``."""

import re

import flops
import spans

PREFILL = re.compile(r"^jit_prefill\b")
SCOPE = "prefill_attention"
SPAN = "prefill"
SLACK_NS = 5e6  # the device's clock may lead the host's by a few ms


def pair_flops(m) -> float:
    """Operations of one query-key pair, all layers."""
    return flops.prefill(m, 2) - 2 * flops.prefill(m, 1) + flops.prefill(m, 0)


def qkvo_bytes(m, n: int) -> float:
    """bf16 bytes of the q, k, v and o rows of ``n`` tokens, all layers."""
    if hasattr(m, "v_head"):  # latent attention, in its plain form
        width = 2 * m.heads * (m.qk_nope + m.qk_rope + m.v_head)
    else:
        width = 2 * (m.heads + m.kv_heads) * m.head_dim
    return float(n * m.layers * width * flops.BF16)


def pair(executions, waits):
    """(execution, span) pairs, both lists in order of end time."""
    out, i = [], 0
    for w in waits:
        while i < len(executions) and executions[i][0] < w.start - SLACK_NS:
            i += 1  # started before this span opened: a span not traced
        if i < len(executions) and executions[i][1] <= w.end + SLACK_NS:
            out.append((executions[i], w))
            i += 1
    return out


def read(run):
    tr, t = run.trace, spans.table(run)
    if tr is None or t is None:
        return None
    m = run.model
    per_pair = pair_flops(m)
    waits = sorted((h for h in t.spans(SPAN) if "prompt_len" in h.stats),
                   key=lambda h: h.end)
    least, device_ns = 0.0, 0.0
    for d in tr.devices:
        execs = sorted(((s, e) for s, e, name in d.modules
                        if PREFILL.search(name)), key=lambda se: se[1])
        for (s, e), w in pair(execs, waits):
            ops = [o for o in t.ops_between(d.name, s, e) if spans.in_scope(o, SCOPE)]
            if not ops:
                continue
            n = int(w.stats["prompt_len"])
            least += flops.least_time(per_pair * n * (n + 1) / 2,
                                      qkvo_bytes(m, n), run.peaks)["seconds"]
            device_ns += sum(o.end - o.start for o in ops)
    if device_ns <= 0:
        return None
    return 100.0 * least / (device_ns / 1e9)
