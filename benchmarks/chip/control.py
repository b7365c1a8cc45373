#!/usr/bin/env python3
"""Readings from which the limits of a cell's ``correct`` are set.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 1,2,3 --seconds 3 [--control 1] [--trace 0]

For each seed, in one process: the cell's set-up and a short window at the
cell's own load, placed over its chips as ``run.py`` places a run, then
the numbers ``correct`` compares, read for the program against the plain
reference and, with ``--control 1``, for the control (the reference in
bfloat16 put in the program's place) against the same reference.  Prints
one JSON line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import device, spec  # noqa: E402
from benchmarks.chip.run import driver_of, placement  # noqa: E402


def readings(workload: str, seed: int, seconds: float, with_control: bool,
             *, bench=None, files=None, detail: bool = False,
             fault: str = "") -> dict:
    import jax

    bench = spec.load() if bench is None else bench
    cell = spec.cell(bench, workload)
    files = files or {}
    config = files.get("config") or spec.config(bench, cell)
    traffic = files.get("traffic") or spec.traffic(cell)
    drv = driver_of(traffic)
    with placement(jax.devices()[:cell["chips"]]):
        st = drv.setup(config, traffic, seed)
        drv.window(st, seconds)
        drv.release(st)
        gc.collect()
        out = {"seed": seed, "program": drv.check(st, detail)}
        if with_control:
            out["control"] = drv.control(st, detail)
        for f in filter(None, fault.split(",")):
            out[f] = drv.control(st, detail, fault=f)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--detail", type=int, choices=(0, 1), default=0,
                    help="1 reads every number, with the worst leaves")
    ap.add_argument("--fault", default="",
                    help="also read faults planted in the reference, "
                         "comma-separated (loop cells: half_batch, nudge)")
    args = ap.parse_args(argv)
    bench = spec.load()
    try:
        device.require_chips(spec.cell(bench, args.workload)["chips"])
    except device.NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for s in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(s), args.seconds,
                                  bool(args.control), bench=bench,
                                  detail=bool(args.detail),
                                  fault=args.fault)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
