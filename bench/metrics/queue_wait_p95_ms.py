"""95th percentile of the engine's own admit_t − submit_t: how long a
request waited in the engine's queue before its wave was admitted."""
import numpy as np


def read(obs):
    rec = obs.requests
    if rec is None:
        return None
    wait = (rec["admit"] - rec["submit"]) * 1e3
    wait = wait[np.isfinite(wait)]
    return float(np.percentile(wait, 95)) if len(wait) else None
