"""Walks the MAV gather marks affected, as a share of the corpus (percent):
the mean over the window's batches of WalkEngine.run_stream's per-batch
affected count (an exact count the engine returns) over the corpus's
walks."""
import numpy as np


def read(ctx):
    aff = ctx["counters"].get("affected_window")
    if aff is None or len(aff) == 0:
        return None
    return 100.0 * float(np.mean(aff)) / ctx["counters"]["n_walks"]
