"""Set-up seconds: process start to the first measured batch (import,
readings, build, compile and warm-up)."""


def read(run):
    return run.setup["setup_s"]
