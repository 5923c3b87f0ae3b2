"""95th percentile of the event-to-action latency (ms) over every
forwarded env-window of the open loop (an env-window never forwarded
counts as failed). A window's environments are forwarded together, so
this is the tail of some 160 windows a run, and a host stall of a few
hundred ms inside one ``run_windows`` call sets it."""
import numpy as np


def read(run):
    lat = run.latencies_ms
    lat = lat[np.isfinite(lat)]
    return float(np.percentile(lat, 95)) if lat.size else None
