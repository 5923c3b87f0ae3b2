"""Environment-windows forwarded per second: all windows of the batches
run in the measured window, over the time from its start to the batch
completion that closes it."""


def read(run):
    rec = run.record
    windows = sum(b[2] for b in rec.batches)
    return windows * rec.n_envs / (rec.t_end - rec.t0)
