"""Profile a window and reduce the trace to device time.

The benchmark puts a ``jax.profiler.TraceAnnotation`` named ``bench.<what>``
around every call it makes into a layer, and one named ``bench.window``
around the whole traced window.  From the XPlane trace it keeps:

* per device (``/device:TPU:<n>`` planes), the ``XLA Modules`` line (one
  event per program execution, named ``jit_<function>``) and the ``XLA
  Ops`` line (one event per operation, named by its HLO instruction);
* on the host, every ``bench.*`` span.

Device and host events share one clock in the trace.  Busy time is the
union of the device's operation intervals inside the window; an idle gap is
named by the innermost ``bench.*`` span open at its midpoint.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_FINGERPRINT = re.compile(r"\(\d+\)$")


@contextlib.contextmanager
def capture(out: dict):
    """Trace the body; ``out['path']`` is the ``.xplane.pb`` file after it.
    The directory lives under ``$TMPDIR`` and is removed by
    :func:`discard`."""
    import jax

    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out["dir"] = tdir
        found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        out["path"] = found[0] if found else None


def discard(out: dict) -> None:
    if out.get("dir"):
        shutil.rmtree(out["dir"], ignore_errors=True)


def _op_name(hlo: str) -> str:
    """``%fusion.7 = f32[10] fusion(...)`` -> ``fusion.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str) -> dict:
    """Events of one trace file: ``modules`` and ``ops`` as (device,
    name, start_ns, dur_ns), ``spans`` as (name, start_ns, dur_ns)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ev: dict = {"modules": [], "ops": [], "spans": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    ev["modules"] += [
                        (dev, _FINGERPRINT.sub("", e.name), e.start_ns,
                         e.duration_ns) for e in line.events]
                elif line.name == OPS_LINE:
                    ev["ops"] += [(dev, _op_name(e.name), e.start_ns,
                                   e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev["spans"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX)]
    return ev


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """The reduction of one traced window."""

    def __init__(self, events: dict):
        self.events = events
        wins = [s for s in events["spans"] if s[0] == WINDOW_SPAN]
        if len(wins) != 1:
            raise ValueError(f"want one {WINDOW_SPAN} span, found "
                             f"{len(wins)}")
        _, start, dur = wins[0]
        self.start, self.end = float(start), float(start) + float(dur)
        self.window_s = dur * 1e-9
        devices = sorted({d for d, *_ in events["ops"]}) or [0]
        self.devices = devices
        self._busy = {d: self._clip(_union(
            (s, s + t) for dv, _, s, t in events["ops"] if dv == d))
            for d in devices}

    def _clip(self, merged):
        return [[max(s, self.start), min(e, self.end)] for s, e in merged
                if e > self.start and s < self.end]

    def _inside(self, s, t) -> bool:
        return s >= self.start and s + t <= self.end

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        tot = sum(e - s for d in self.devices for s, e in self._busy[d])
        return tot * 1e-9 / len(self.devices)

    def module_seconds(self, patterns) -> float:
        """Device seconds of the program executions whose name holds one of
        ``patterns``, summed over the devices."""
        return sum(t for _, n, s, t in self.events["modules"]
                   if self._inside(s, t) and any(p in n for p in patterns)
                   ) * 1e-9

    def module_count(self, patterns) -> int:
        return sum(1 for _, n, s, t in self.events["modules"]
                   if self._inside(s, t) and any(p in n for p in patterns))

    def span_seconds(self, name: str) -> list[float]:
        return [t * 1e-9 for n, s, t in self.events["spans"]
                if n == name and self._inside(s, t)]

    def top_ops(self, n: int = 10) -> list:
        tot: dict = defaultdict(float)
        for _, name, s, t in self.events["ops"]:
            if self._inside(s, t):
                tot[name] += t * 1e-9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle seconds of device 0 inside the window, summed by the
        innermost ``bench.*`` span open at each gap's midpoint."""
        busy = self._busy[self.devices[0]]
        edges = [self.start] + [x for iv in busy for x in iv] + [self.end]
        # One sweep over the gaps in time order with a stack of the open
        # spans (the host's spans nest); outer before inner at one start.
        spans = sorted((s, -(s + t), nm) for nm, s, t in self.events["spans"]
                       if nm != WINDOW_SPAN)
        stack: list = []
        nxt = 0
        tot: dict = defaultdict(float)
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            while nxt < len(spans) and spans[nxt][0] <= mid:
                stack.append((-spans[nxt][1], spans[nxt][2]))
                nxt += 1
            while stack and stack[-1][0] <= mid:
                stack.pop()
            name = next((nm for end, nm in reversed(stack) if end > mid),
                        "no bench span")
            tot[name] += (b - a) * 1e-9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]
