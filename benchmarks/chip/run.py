#!/usr/bin/env python3
"""The on-chip benchmark of the SEAFL server and FL loop: one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One run is one process.  It refuses to run without a TPU (exit 3, no
result line), turns on JAX's persistent compilation cache, builds the cell
from the seed, warms up the cell's own shapes, measures for ``--seconds``
(``--trace 1``: profiles a shorter window of the same loop instead), frees
the program's state, checks what the window produced against the plain
reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` a
``breakdown``), then ``compared``, each number checked beside its limit.

Cells, configurations, traffic mixes and metrics are found by the names
in ``BENCHMARK.json``; see ``spec.py``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import device, spec  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class Run:
    """What a metric's ``read(run)`` sees: the driver's counters and host
    spans (``c``), the trace reduction (``trace``, None with ``--trace
    0``), the chip's peaks and the cell's files."""

    def __init__(self, cell, config, traffic, counters, trace, peaks):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.c, self.trace, self.peaks = counters, trace, peaks


class CompileCounter:
    """Counts compilations and persistent-cache loads while ``on``."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_kw):
        if self.on and name == COMPILE_EVENTS[0]:
            self.n += 1

    def _event(self, name, **_kw):
        if self.on and name == COMPILE_EVENTS[1]:
            self.n += 1


def driver_of(traffic: dict):
    return importlib.import_module(
        f"benchmarks.chip.drivers.{traffic['driver']}")


def run(workload: str, seed: int, seconds: float, trace_on: bool, *,
        bench=None, files=None, require_chip: bool = True,
        compile_cache: bool = True, t0: float = T0) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    Tests pass ``bench`` (the parsed spec), ``files`` (``config``,
    ``traffic`` and ``limits`` dicts in place of the cell's files) and
    ``require_chip=False`` to drive a run at a small size on the CPU."""
    bench = spec.load() if bench is None else bench
    cell = spec.cell(bench, workload)
    files = files or {}
    config = files.get("config") or spec.config(bench, cell)
    traffic = files.get("traffic") or spec.traffic(cell)
    limits = files.get("limits") or spec.limits(cell)
    import jax
    from jax.profiler import TraceAnnotation

    from benchmarks.chip import trace as tr

    devs = (device.require_chips(cell["chips"]) if require_chip
            else jax.devices()[:cell["chips"]])
    peaks = device.peaks(devs[0].device_kind) if require_chip else None
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    drv = driver_of(traffic)
    st = drv.setup(config, traffic, seed)
    setup_s = time.perf_counter() - t0
    counter.on = True
    reduced = None
    if trace_on:
        out: dict = {}
        try:
            with tr.capture(out):
                with TraceAnnotation(tr.WINDOW_SPAN):
                    res = drv.window(st, min(seconds,
                                             traffic["trace_seconds"]))
            t_red = time.perf_counter()
            reduced = tr.Trace(tr.load(out["path"]))
            print(f"[chipbench] trace read in "
                  f"{time.perf_counter() - t_red:.1f} s", file=sys.stderr)
        finally:
            tr.discard(out)
    else:
        res = drv.window(st, seconds)
    counter.on = False
    res.update(setup_s=setup_s, memory_peak_bytes=device.peak_bytes(devs),
               window_compiles=counter.n)
    dev = device.line(devs)
    drv.release(st)
    gc.collect()
    compared = drv.check(st)
    correct = all(compared[k] <= limits[k] for k in compared)
    r = Run(cell, config, traffic, res, reduced, peaks)
    kind = "per_layer" if trace_on else "end_to_end"
    metrics = {}
    for m in spec.metrics(bench, cell, kind):
        v = spec.reader(m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        dev.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        line["breakdown"] = {"device_ops": reduced.top_ops(10),
                             "idle_gaps": reduced.idle_gaps(10)}
    line["window_compiles"] = counter.n
    line["resident_state_bytes"] = res["resident"]
    line["compared"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in compared.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except device.NoChip as e:
        print(f"[chipbench] {e}; no result", file=sys.stderr)
        return 3
    for k, v in line["compared"].items():
        print(f"[chipbench] compared {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
