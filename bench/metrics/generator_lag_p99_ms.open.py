"""99th percentile of how late the generator began each window's
deliveries against the window's due time (ms); a starved generator shows
here rather than as a fast system."""
import numpy as np


def read(run):
    lags = [d[2] - d[1] for d in run.record.deliveries]
    return 1e3 * float(np.percentile(lags, 99)) if lags else None
