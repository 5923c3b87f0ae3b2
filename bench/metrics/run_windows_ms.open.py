"""Host ms per ``run_windows`` call in the open loop."""


def read(run):
    b = run.record.batches
    return 1e3 * sum(x[1] - x[0] for x in b) / len(b) if b else None
