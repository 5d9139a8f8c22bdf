"""Load generator: how late the client issued open-loop ops, p99 in ms.

The harness clock's issue time less each op's due time, over the ops due
in the window.  A starved generator shows here, not as a fast system."""
import numpy as np


def read(r: dict):
    late = r.get("lateness_s")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, 99) * 1e3)
