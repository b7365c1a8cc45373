#!/usr/bin/env python3
"""The program's own ``seafl.*`` spans in one traced benchmark run.

``trace.load`` keeps only the benchmark's ``bench.*`` host spans, so no
metric of ``BENCHMARK.json`` reads a span that the program opens through
``Telemetry.span``.  This script makes one ``--trace 1`` run exactly as
``run.py`` does, with the host events named ``seafl.*`` kept besides, and
prints ``run.py``'s result line with one more key, ``spans``:

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \\
        --seconds <s>

For the length of that one run it widens ``trace.SPAN_PREFIX`` to both
prefixes (``idle_gaps`` then names each gap by the innermost span of
either) and keeps the ``Run`` that the metrics read; it edits no file of
the harness.  The readers below take that ``Run``, as a metric file's
``read`` does:

* ``self_seconds(trace, prefix)``: host seconds of the window in which the
  innermost open span's name starts with ``prefix``.  The window is cut at
  every span edge (spans clipped to the window); each piece goes to the
  span innermost at its midpoint, or to ``no span``.
* ``ingest_decode_ms``: mean of the ``seafl.ingest.decode`` spans inside
  the window, in ms.
* ``batch_us_per_sample``: the ``seafl.client.batches`` spans inside the
  window, summed, over the samples the window trained, in us.
* ``sim_self_ms``: ``self_seconds(trace, "seafl.sim.")`` over the count of
  ``seafl.server.aggregate`` spans inside the window, in ms.
* ``coarse_idle_share``: the share of the device's idle seconds whose
  innermost span is ``bench.ingest``, ``bench.round`` or none.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import device, run, trace as tr  # noqa: E402

PREFIXES = ("bench.", "seafl.")
NO_SPAN = "no span"
COARSE = ("bench.ingest", "bench.round", "no bench span")


def self_by_span(trace) -> dict:
    """Host seconds of the window by the innermost open span's name."""
    spans = sorted(((max(s, trace.start), -min(s + d, trace.end), nm)
                    for nm, s, d in trace.events["spans"]
                    if nm != tr.WINDOW_SPAN and s + d > trace.start
                    and s < trace.end))            # outer first at one start
    edges = sorted({trace.start, trace.end}
                   | {x for s, e, _ in spans for x in (s, -e)})
    stack: list = []
    nxt = 0
    tot: dict = defaultdict(float)
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        while nxt < len(spans) and spans[nxt][0] <= mid:
            stack.append((-spans[nxt][1], spans[nxt][2]))
            nxt += 1
        stack = [x for x in stack if x[0] > mid]
        tot[stack[-1][1] if stack else NO_SPAN] += (b - a) * 1e-9
    return dict(tot)


def self_seconds(trace, prefix: str) -> float:
    return sum(v for k, v in self_by_span(trace).items()
               if k.startswith(prefix))


def ingest_decode_ms(r):
    if r.trace is None:
        return None
    d = r.trace.span_seconds("seafl.ingest.decode")
    return 1e3 * sum(d) / len(d) if d else None


def batch_us_per_sample(r):
    if r.trace is None or not r.c.get("samples"):
        return None
    t = sum(r.trace.span_seconds("seafl.client.batches"))
    return 1e6 * t / r.c["samples"] if t else None


def sim_self_ms(r):
    if r.trace is None:
        return None
    n = len(r.trace.span_seconds("seafl.server.aggregate"))
    t = self_seconds(r.trace, "seafl.sim.")
    return 1e3 * t / n if n and t else None


def coarse_idle_share(r):
    if r.trace is None:
        return None
    gaps = r.trace.idle_gaps(10**9)
    idle = sum(v for _, v in gaps)
    return sum(v for k, v in gaps if k in COARSE) / idle if idle else None


READERS = {"ingest_decode_ms": ingest_decode_ms,
           "batch_us_per_sample": batch_us_per_sample,
           "sim_self_ms": sim_self_ms,
           "coarse_idle_share": coarse_idle_share}


@contextlib.contextmanager
def _program_spans(kept: list):
    """Within the block, ``trace.load`` keeps both prefixes and every
    ``run.Run`` built is appended to ``kept``."""
    prefix, init = tr.SPAN_PREFIX, run.Run.__init__

    def init_and_keep(self, *a, **kw):
        init(self, *a, **kw)
        kept.append(self)

    tr.SPAN_PREFIX, run.Run.__init__ = PREFIXES, init_and_keep
    try:
        yield
    finally:
        tr.SPAN_PREFIX, run.Run.__init__ = prefix, init


def traced_run(workload: str, seed: int, seconds: float, *, t0: float = T0,
               **run_kw) -> dict:
    """``run.run(..., trace_on=True)``'s line, with ``spans`` added."""
    kept: list = []
    with _program_spans(kept):
        line = run.run(workload, seed, seconds, True, t0=t0, **run_kw)
    r = kept[-1]
    own = sorted(self_by_span(r.trace).items(), key=lambda kv: -kv[1])
    line["spans"] = {name: f(r) for name, f in READERS.items()}
    line["spans"].update(idle_gaps=r.trace.idle_gaps(20), self_s=own[:20])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        line = traced_run(args.workload, args.seed, args.seconds)
    except device.NoChip as e:
        print(f"[chipbench] {e}; no result", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
