"""Mean windows per ``run_windows`` call: the batching that catch-up
forces in the open loop."""


def read(run):
    b = run.record.batches
    return sum(x[2] for x in b) / len(b) if b else None
