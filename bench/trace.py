"""Reduce a ``jax.profiler`` trace to the numbers the benchmark reports.

``reduce_events`` is the arithmetic: the device's busy time as the union
of its operations' intervals inside the window, averaged over devices;
device time per operation and per compiled module; and the longest idle
gaps, each labelled by the innermost benchmark host span that covers
its midpoint. ``reduce_xplane`` reads a profile file into those events.
Both see only the window, which the benchmark marks with a host span.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class DeviceEvent:
    name: str
    module: str
    start_ns: float
    dur_ns: float


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                   # averaged over devices
    n_devices: int
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    modules: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def module_seconds(self, pattern: str) -> float:
        """Device seconds of every module whose name contains
        ``pattern`` (averaged over devices)."""
        return sum(s for m, s in self.modules.items() if pattern in m)

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


def _merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(mid: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    best: Optional[Tuple[float, str]] = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= mid <= e:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    return best[1][len(SPAN_PREFIX):] if best else "host:outside-spans"


def reduce_events(ops: Dict[str, List[DeviceEvent]],
                  modules: Dict[str, List[DeviceEvent]],
                  spans: Sequence[Tuple[str, float, float]],
                  window: Tuple[float, float], top: int = 10
                  ) -> TraceSummary:
    """``ops`` and ``modules``: per device, its operation and module
    events; ``spans``: host spans ``(name, start_ns, end_ns)``;
    ``window``: ``(start_ns, end_ns)``. Events are clipped to the
    window."""
    w0, w1 = window
    if w1 <= w0:
        raise ValueError(f"empty trace window {window}")
    n_dev = max(len(ops), 1)
    busy = 0.0
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for evs in ops.values():
        clipped = []
        for ev in evs:
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.dur_ns, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            key = f"{ev.module}/{ev.name}" if ev.module else ev.name
            per_op[key] = per_op.get(key, 0.0) + (e - s) * 1e-9 / n_dev
        merged = _merge(clipped)
        busy += sum(e - s for s, e in merged) * 1e-9
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                gaps.append((_label((s + edge) / 2, spans), (s - edge) * 1e-9))
            edge = max(edge, e)
    per_mod: Dict[str, float] = {}
    for evs in modules.values():
        for ev in evs:
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.dur_ns, w1)
            if e > s:
                per_mod[ev.name] = per_mod.get(ev.name, 0.0) \
                    + (e - s) * 1e-9 / n_dev
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n_dev, n_devices=len(ops),
        device_ops=sorted(per_op.items(), key=lambda kv: -kv[1]),
        modules=per_mod, idle_gaps=gaps[:top])


def _stat(ev, key: str):
    for k, v in getattr(ev, "stats", ()):
        if k == key:
            return v
    return None


_OP = re.compile(r"^%?([^ ]+) = .*?\b([a-z][\w\-]*)\(")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


def op_label(text: str) -> str:
    """``name:opcode`` of an HLO op's text (``%copy.12 = f32[..] copy(..)``
    gives ``copy.12:copy``); the text itself when it does not parse."""
    m = _OP.match(text)
    return f"{m.group(1)}:{m.group(2)}" if m else text


def _ops_in_modules(events, modules: List[DeviceEvent]) -> List[DeviceEvent]:
    """Op events named by their label and by the module whose execution
    holds their start (the trace gives ops no module of their own)."""
    import bisect
    mods = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    out = []
    for ev in events:
        module = _stat(ev, "hlo_module") or ""
        if not module:
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i >= 0 and ev.start_ns <= mods[i].start_ns + mods[i].dur_ns:
                module = _MODULE.match(mods[i].name).group(1)
        out.append(DeviceEvent(op_label(ev.name), str(module), ev.start_ns,
                               ev.duration_ns))
    return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def reduce_xplane(path: str, top: int = 10) -> TraceSummary:
    """Read a profile written by ``jax.profiler.start_trace`` and reduce
    it over the window its ``bench.window`` host span marks."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    ops: Dict[str, List[DeviceEvent]] = {}
    mods: Dict[str, List[DeviceEvent]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mod_evs = [DeviceEvent(ev.name, "", ev.start_ns, ev.duration_ns)
                       for ev in (lines["XLA Modules"].events
                                  if "XLA Modules" in lines else ())]
            mods[plane.name] = mod_evs
            ops[plane.name] = _ops_in_modules(
                lines["XLA Ops"].events if "XLA Ops" in lines else (),
                mod_evs)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} "
                         f"{WINDOW_SPAN} spans, not one")
    if not ops:
        raise ValueError("the trace holds no TPU device plane")
    return reduce_events(ops, mods, spans, windows[0], top=top)
