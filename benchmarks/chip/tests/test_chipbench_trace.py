"""The trace reduction, on a trace recorded on one TPU v5e: three rounds of
(the SEAFL aggregation at K = 10, P = 2**20; a jitted matmul; a scanned
matmul), each inside ``bench.*`` spans, 10 ms of host sleep between
rounds.  Expected values are read off the trace's events by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import trace as tr  # noqa: E402

PROBE = Path(__file__).resolve().parent / "data" / "v5e_agg_probe.xplane.pb"
AGG = ("jit__seafl_aggregate_flat_from_params_jit",)


@pytest.fixture(scope="module")
def probe():
    ev = tr.load(str(PROBE))
    ev["spans"].append((tr.WINDOW_SPAN, 44_000_000.0, 28_500_000.0))
    return tr.Trace(ev)


def test_loader_keeps_modules_ops_and_bench_spans(probe):
    ev = probe.events
    assert len(ev["modules"]) == 9
    assert len(ev["ops"]) == 81
    assert [s[0] for s in ev["spans"][:6]] == ["bench.agg", "bench.mm"] * 3
    assert {m[1] for m in ev["modules"]} == {
        AGG[0], "jit__lambda", "jit_scan_fn"}


def test_module_time_is_the_sum_of_its_executions(probe):
    assert probe.module_count(AGG) == 3
    assert probe.module_seconds(AGG) == pytest.approx(
        (423_566 + 423_296 + 420_056) * 1e-9)
    assert probe.module_count(("jit_scan_fn",)) == 3


def test_busy_is_the_union_of_ops_inside_the_window(probe):
    kernels = 653_855 + 599_520     # the six kernel ops, summed by hand
    assert probe.window_s == pytest.approx(0.0285)
    assert kernels * 1e-9 < probe.busy_s < 1_697_729 * 1e-9
    assert probe.busy_s == pytest.approx(0.001516973)


def test_top_ops_and_idle_gaps(probe):
    top = probe.top_ops(2)
    assert top[0] == ["weighted_aggregate.1", pytest.approx(653_855e-9)]
    assert top[1] == ["similarity_partials_from_params.1",
                      pytest.approx(599_520e-9)]
    gaps = dict(probe.idle_gaps())
    # the two 10 ms sleeps lie outside every bench span
    assert gaps["no bench span"] > 0.02
    assert sum(gaps.values()) == pytest.approx(
        probe.window_s - probe.busy_s)


def test_window_clips_events_outside_it():
    ev = tr.load(str(PROBE))
    ev["spans"].append((tr.WINDOW_SPAN, 50_000_000.0, 10_000_000.0))
    t = tr.Trace(ev)
    assert t.module_count(AGG) == 1           # only the second round
    assert t.busy_s < 0.0006


def test_one_window_span_is_required():
    ev = tr.load(str(PROBE))
    with pytest.raises(ValueError):
        tr.Trace(ev)


def test_capture_records_bench_spans_on_the_host():
    import jax
    import jax.numpy as jnp

    out: dict = {}
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((256,))
    f(x).block_until_ready()
    try:
        with tr.capture(out):
            with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
        ev = tr.load(out["path"])
    finally:
        tr.discard(out)
    names = [s[0] for s in ev["spans"]]
    assert tr.WINDOW_SPAN in names and "bench.step" in names
    t = tr.Trace(ev)
    assert t.window_s > 0 and t.span_seconds("bench.step")


def _innermost(spans, mid):
    """The shortest span open at ``mid``, by brute force."""
    open_ = [(e - s, nm) for nm, s, e in spans if s <= mid < e]
    return min(open_)[1] if open_ else "no bench span"


def test_idle_gaps_name_the_innermost_open_span():
    import random

    rnd = random.Random(7)
    spans, t = [], 0.0
    for r in range(40):                      # rounds holding nested calls
        r0 = t
        for _ in range(rnd.randint(0, 5)):
            s = t + rnd.uniform(0, 50)
            t = s + rnd.uniform(1, 50)
            spans.append((rnd.choice(["bench.ingest", "bench.eval"]), s, t))
        t += rnd.uniform(0, 50)
        spans.append(("bench.round", r0, t))
        t += rnd.uniform(0, 30)
    ops = []
    x = 0.0
    while x < t:
        x += rnd.uniform(0, 20)
        d = rnd.uniform(0, 10)
        ops.append((0, "op", x, d))
        x += d
    ev = {"modules": [], "ops": ops,
          "spans": [(nm, s, e - s) for nm, s, e in spans]
          + [(tr.WINDOW_SPAN, 0.0, t)]}
    trace = tr.Trace(ev)
    busy = trace._busy[0]
    edges = [0.0] + [v for iv in busy for v in iv] + [t]
    want: dict = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            nm = _innermost(spans, (a + b) / 2)
            want[nm] = want.get(nm, 0.0) + (b - a) * 1e-9
    got = dict(trace.idle_gaps(10))
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k])
