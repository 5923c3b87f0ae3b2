"""The whole step's share (%) of the chip's peak: the operations of every
window and train step run inside the traced window (``work.py``), over
the traced window's length times the peak FLOP/s."""
from trace_reduce import FUSED_PROGRAM, TRAIN_PROGRAM, program_seconds


def read(run):
    if not run.trace or not run.peaks or run.trace["window_s"] <= 0:
        return None
    _, steps = program_seconds(run.trace, FUSED_PROGRAM)
    _, trains = program_seconds(run.trace, TRAIN_PROGRAM)
    if not steps:
        return None
    flops = (steps * int(run.traffic["k"]) * run.window_work["flops"]
             + trains * run.train_work["flops"])
    return 100.0 * flops / (run.trace["window_s"] * run.peaks["flops_per_s"])
