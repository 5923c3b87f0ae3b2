"""Host ms per window inside ``run_windows`` (accumulate, assemble,
dispatch, wait, consume) over the measured window."""


def read(run):
    b = run.record.batches
    windows = sum(x[2] for x in b)
    return 1e3 * sum(x[1] - x[0] for x in b) / windows if windows else None
