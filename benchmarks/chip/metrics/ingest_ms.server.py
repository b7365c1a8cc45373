"""Mean host milliseconds of one ``ingest_payload`` call that does not
trigger an aggregation (the benchmark's ``bench.ingest`` span, host
clock), in the traced window."""
import numpy as np


def read(run):
    v = run.c.get("spans", {}).get("ingest")
    return float(np.mean(v)) * 1e3 if v else None
