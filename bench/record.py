"""What one run records: set-up time, the measured window, host spans
around each call into the program, samples, counts and the compared
numbers. Drivers fill a :class:`Record`; metric readers read it.

Spans are ``jax.profiler.TraceAnnotation``s too, so a traced run's idle
gaps can be attributed to what the host was doing. The annotation is
the same call whether or not a trace is being taken.
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

import jax

from . import trace as trace_mod

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record:
    """One run's measurements. ``t_start`` is the process's start on the
    ``time.perf_counter`` clock."""

    def __init__(self, t_start: float, trace: bool, log=print):
        self.t_start = t_start
        self.trace = trace
        self.log = log
        self.setup_s: Optional[float] = None
        self.window: Optional[Tuple[float, float]] = None
        self.spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        self.samples: Dict[str, List[float]] = defaultdict(list)
        self.counts: Dict[str, float] = defaultdict(float)
        self.checks: List[Tuple[str, float, float]] = []
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes: Optional[int] = None
        self.trace_summary: Optional[trace_mod.TraceSummary] = None
        self.info: Dict[str, Any] = {}
        self._in_window = False
        self._compiles = 0
        self._trace_dir: Optional[str] = None
        self._window_ann = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE_EVENT and self._in_window:
            self._compiles += 1

    # -- spans ------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """Time a call into the program; kept when inside the window."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name):
            yield
        if self._in_window:
            self.spans[name].append((t0, time.perf_counter()))

    def span_seconds(self, name: str) -> List[float]:
        return [e - s for s, e in self.spans.get(name, ())]

    # -- the window ---------------------------------------------------------
    def begin_window(self) -> float:
        """End set-up and open the measured window; returns its start."""
        if self.trace:
            # device activity and the benchmark's own annotations only:
            # the Python tracer would slow every call of the host path
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._window_ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self._window_ann.__enter__()
        t = time.perf_counter()
        self.setup_s = t - self.t_start
        self._in_window = True
        self.window = (t, t)
        return t

    def end_window(self) -> float:
        t = time.perf_counter()
        self._in_window = False
        self.window = (self.window[0], t)
        self._window_ann.__exit__(None, None, None)
        if self.trace:
            jax.profiler.stop_trace()
        self.counts["compiles_in_window"] = self._compiles
        return t

    @property
    def in_window(self) -> bool:
        return self._in_window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def reduce_trace(self) -> None:
        """Reduce the window's trace (traced runs only) and delete it."""
        if not self.trace:
            return
        try:
            self.trace_summary = trace_mod.reduce_xplane(
                trace_mod.find_xplane(self._trace_dir))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def read_memory_peak(self, devices) -> None:
        """Peak bytes on the fullest device so far; read before any
        reference runs on the chip."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def check(self, name: str, value: float, limit: float) -> None:
        """A compared number and its limit; the run is correct when every
        value is at most its limit."""
        self.checks.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim
                                         in self.checks)
