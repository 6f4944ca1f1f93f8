"""90th percentile of the time ``AsyncCluster.submit()`` took, mostly
the wait for the prefill instance's lock: the runtime's ``submit``
spans that end in the window."""
import numpy as np


def read(facts):
    w = [s["dur"] for s in facts["spans"] if s["name"] == "submit"]
    return float(np.percentile(w, 90)) * 1e3 if w else None
