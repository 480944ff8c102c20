"""Reduction of a profiler trace to the numbers the per-layer readers use.

A trace is read into a flat list of events ``(plane, line, name, start_ns,
duration_ns)`` and reduced from that list alone, so the reduction can be
checked on a small recorded trace (``bench/tests/data``).  Device planes
are those named ``/device:<accelerator>:<n>``; on each, the ``XLA Modules``
line holds one event per executable run and the ``XLA Ops`` line one per
operation.  Host spans are the benchmark's own annotations (names that
start with one of ``HOST_PREFIXES``) on the host plane.

- busy: the union of the operation intervals, clipped to the window (the
  host span ``bench.window``), averaged over the device planes;
- module time: the summed durations of each executable's runs, keyed by
  the executable's name without its unique suffix (``jit_pilot_fn``);
- top device operations: summed durations by ``<module>/<operation>``,
  the operation named by its HLO instruction name (``while.4``);
- idle by host activity: each gap between busy intervals inside the
  window, charged to the innermost host span that covers its midpoint
  (``idle`` where none does), summed by span name.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

HOST_PREFIXES = ("bench.", "engine.", "segments.", "index.")
WINDOW_SPAN = "bench.window"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[\(\[].*$")

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def module_name(name: str) -> str:
    """``jit_pilot_fn(1234)`` -> ``jit_pilot_fn``."""
    return _SUFFIX.sub("", name).strip()


def load_xplane(path: str) -> List[Event]:
    """The events the reduction needs from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        dev = is_device_plane(plane.name)
        host = plane.name.startswith("/host:")
        if not (dev or host):
            continue
        for line in plane.lines:
            if dev and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                if host and not ev.name.startswith(HOST_PREFIXES):
                    continue
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None


def read_events(path: Path) -> List[Event]:
    """Events saved as ``{"events": [[plane, line, name, start, dur]]}``,
    gzip-compressed where the name ends in ``.gz``."""
    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    return [tuple(e) for e in json.loads(raw)["events"]]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _innermost(spans: List[Tuple[float, float, str]],
               points: List[float]) -> List[str]:
    """For each point (ascending), the name of the shortest span that
    covers it, or ``idle``; one sweep over the spans sorted by start."""
    spans = sorted(spans)
    out, active, j = [], [], 0
    for t in points:
        while j < len(spans) and spans[j][0] <= t:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[1] >= t]
        out.append(min(active, key=lambda sp: sp[1] - sp[0])[2]
                   if active else "idle")
    return out


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # mean over the device planes
    n_devices: int
    module_s: Dict[str, float] = field(default_factory=dict)
    module_runs: Dict[str, int] = field(default_factory=dict)
    top_ops: List[list] = field(default_factory=list)
    idle_by_host: List[list] = field(default_factory=list)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def summarize(events: Sequence[Event], top: int = 10) -> TraceSummary:
    """Reduce the events of one traced window (module docstring)."""
    win = [e for e in events if e[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = win[0][3], win[0][3] + win[0][4]
    host = [(s, s + d, n) for p, ln, n, s, d in events
            if not is_device_plane(p) and n != WINDOW_SPAN]
    planes = sorted({p for p, *_ in events if is_device_plane(p)})
    module_s: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    busy_total = 0.0
    for plane in planes:
        mods = sorted((s, s + d, module_name(n)) for p, ln, n, s, d in events
                      if p == plane and ln == MODULE_LINE
                      and s < w1 and s + d > w0)
        for s, e, n in mods:
            module_s[n] = (module_s.get(n, 0.0)
                           + (min(e, w1) - max(s, w0)) / 1e9)
            module_runs[n] = module_runs.get(n, 0) + 1
        starts = [m[0] for m in mods]
        ops = []
        for p, ln, n, s, d in events:
            if p != plane or ln != OP_LINE:
                continue
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            ops.append((s0, e0))
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            key = f"{mod}/{n.split(' = ')[0].lstrip('%')}"
            op_s[key] = op_s.get(key, 0.0) + (e0 - s0) / 1e9
        if not ops:          # a device plane without an op line: modules
            ops = [(max(s, w0), min(e, w1)) for s, e, _ in mods]
        busy = _union(ops)
        busy_total += sum(e - s for s, e in busy) / 1e9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for (g0, g1), name in zip(zip(edges[0::2], edges[1::2]),
                                  _innermost(host, [(a + b) / 2 for a, b in
                                                    zip(edges[0::2],
                                                        edges[1::2])])):
            if g1 > g0:
                idle[name] = idle.get(name, 0.0) + (g1 - g0) / 1e9
    n_dev = max(1, len(planes))
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])[:top]]
    return TraceSummary(window_s=(w1 - w0) / 1e9, busy_s=busy_total / n_dev,
                        n_devices=len(planes), module_s=module_s,
                        module_runs=module_runs, top_ops=rank(op_s),
                        idle_by_host=rank(idle))
