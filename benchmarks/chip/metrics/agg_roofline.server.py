"""Share of its roofline that the aggregation program reaches: the least
time of one Eq. (4)-(8) aggregation (every buffered row read once at the
buffer's width, the f32 global read once and written once, at the HBM
bandwidth; its FLOPs at the bf16 peak, whichever is longer) times the
aggregations traced, over the device time of every execution of the
aggregation program in the trace, its kernels and copies alike."""
from benchmarks.chip import costs

MODULES = ("jit__seafl_aggregate_flat_from_params_jit",)


def read(run):
    if run.trace is None or run.peaks is None or "agg_least_bytes" not in run.c:
        return None
    n = run.trace.module_count(MODULES)
    t = run.trace.module_seconds(MODULES)
    if not n or not t:
        return None
    least = costs.least_seconds(run.c["agg_least_bytes"], run.c["agg_flops"],
                                run.peaks)
    return 100.0 * least * n / t
