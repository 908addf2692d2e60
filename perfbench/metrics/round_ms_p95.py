"""95th percentile of every window round's wall time, round end to round
end, on the host clock."""
import numpy as np


def read(run):
    if run.kind != "federated" or not run.unit_s:
        return None
    return float(np.percentile(np.asarray(run.unit_s) * 1e3, 95))
