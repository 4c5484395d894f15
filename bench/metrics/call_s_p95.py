"""95th percentile of the wall time of every call in the window (host
clock): one call is one batch of trials and ends with host arrays."""
import numpy as np


def read(run):
    return float(np.percentile([c["end"] - c["start"] for c in run["calls"]],
                               95))
