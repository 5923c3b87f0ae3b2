"""Share (%) of the fused program's device time per window that the chip
would need at least for the window's bytes and operations
(``work.window_work``): max(flops / peak, bytes / bandwidth) over the
measured time. Bytes bound it at these shapes."""
from trace_reduce import FUSED_PROGRAM, program_seconds


def read(run):
    if not run.trace or not run.peaks:
        return None
    secs, count = program_seconds(run.trace, FUSED_PROGRAM)
    if not count or secs <= 0:
        return None
    per_window = secs / (count * int(run.traffic["k"]))
    w, p = run.window_work, run.peaks
    least = max(w["flops"] / p["flops_per_s"], w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / per_window
