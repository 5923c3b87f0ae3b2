"""Median event-to-action latency (ms) over every forwarded env-window:
from the time the window's last reading was due to the transmit of that
environment's action."""
import numpy as np


def read(run):
    lat = run.latencies_ms
    lat = lat[np.isfinite(lat)]
    return float(np.percentile(lat, 50)) if lat.size else None
