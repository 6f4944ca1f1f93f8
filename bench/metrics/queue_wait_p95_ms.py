"""95th percentile of the time requests waited at the prefill instance
before their first chunk ran: the runtime's ``queued`` spans that end in
the window (AsyncCluster, wall clock)."""
import numpy as np


def read(facts):
    w = [s["dur"] for s in facts["spans"] if s["name"] == "queued"]
    return float(np.percentile(w, 95)) * 1e3 if w else None
