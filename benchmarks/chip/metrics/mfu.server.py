"""The whole absorb path's share of the chip's peak over the traced
window: the least time of every ingest (payload read once, buffer row
written once) and every aggregation (see ``agg_roofline.server``) in the
window, at the HBM bandwidth or the bf16 peak whichever binds, over the
window's seconds.  The path is bound by bandwidth, so the bytes set it;
the name keeps ``mfu`` because it is the whole step's share of the peak
that bounds every kernel's roofline share."""
from benchmarks.chip import costs


def read(run):
    c = run.c
    if run.trace is None or run.peaks is None or "absorbed" not in c:
        return None
    least = (c["absorbed"] * costs.least_seconds(c["ingest_least_bytes"], 0,
                                                 run.peaks)
             + c["rounds"] * costs.least_seconds(c["agg_least_bytes"],
                                                 c["agg_flops"], run.peaks))
    return 100.0 * least / c["window_s"]
