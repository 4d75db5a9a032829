"""Process start to window start: loading, weights, compiling or loading
every program the cell uses, warm-up, and filling the batch."""


def read(run):
    return run.setup_s
