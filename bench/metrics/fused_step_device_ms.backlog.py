"""Device ms per window of the fused window -> decide -> bank program
(``run_many_decide``), from the trace."""
from trace_reduce import FUSED_PROGRAM, program_seconds


def read(run):
    if not run.trace:
        return None
    secs, count = program_seconds(run.trace, FUSED_PROGRAM)
    k = int(run.traffic["k"])
    return 1e3 * secs / (count * k) if count else None
