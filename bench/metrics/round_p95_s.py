"""round_p95_s: the 95th percentile (linear interpolation) of the durations
of all rounds in the window."""
import numpy as np


def read(run):
    if not run.rounds:
        return None
    return float(np.percentile([r.seconds for r in run.rounds], 95))
