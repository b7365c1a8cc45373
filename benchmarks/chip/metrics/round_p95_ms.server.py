"""95th percentile over every aggregation round of the traced window:
from the ingest call of the upload that triggers the round until the new
global and the round's dispatch payloads are ready (host clock).  A
per-layer metric: a window of ``run_seconds`` holds some tens of rounds at
this cell's rate, too few for an end-to-end tail."""
import numpy as np


def read(run):
    rounds = run.c.get("spans", {}).get("round")
    if run.trace is None or not rounds:
        return None
    return float(np.percentile(rounds, 95)) * 1e3
