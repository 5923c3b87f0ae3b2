"""Device ms per online train step (``train_step``), from the trace."""
from trace_reduce import TRAIN_PROGRAM, program_seconds


def read(run):
    if not run.trace:
        return None
    secs, count = program_seconds(run.trace, TRAIN_PROGRAM)
    return 1e3 * secs / count if count else None
