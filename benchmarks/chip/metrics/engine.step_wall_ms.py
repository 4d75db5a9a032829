"""Engine: host-clock time in which at least one request was decoding
(client records), over the decode steps the engine took in the window
(``/serve{engine#0}/step/duration`` count), in milliseconds."""

from records import decoding_spans


def read(run):
    steps = run.counters1["steps"] - run.counters0["steps"]
    busy = sum(b - a for a, b in decoding_spans(run))
    return busy / steps * 1e3 if steps > 0 and busy > 0 else None
