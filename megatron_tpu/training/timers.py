"""Named span timers.

Equivalent of megatron/timers.py (304 LoC): hierarchical named timers with
a log level gate and elapsed reporting. CUDA-sync start/stop becomes a host
sync via jax.block_until_ready on demand (it waits for the device:
chip_smoke.py's device phase checks that on every run).

Every start/stop pair is also a `jax.profiler.TraceAnnotation` of the
timer's name, so while a trace is being captured (--profile, SIGUSR1:
telemetry/tracing/capture.py) the phases the loop times appear on the
`/host:CPU` plane of the same `.xplane.pb` as the device's operations, on
the profiler's clock: one list of spans, read by `tools/trace_report.py`
and the benchmark alike (docs/observability.md "Runtime traces"). With no
capture running an annotation costs well under a microsecond.

The serving engine's loop takes the same class (inference/engine.py: a
`serve-tick` with its `tick-*` phases), and three things with it that the
train loop does not use: a pair is a context manager (`with
timers("tick-pre"):`), `start(**args)` / `stop(**args)` put arguments on
the span (`step_num` makes it a `StepTraceAnnotation`, the marker
`train-pass` is), and every timer keeps its OWN time, its spans less the
spans of this `Timers` that ran inside them, so that nested phases sum to
the span that holds them.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation


class _Timer:
    def __init__(self, name: str, open_timers: List["_Timer"]):
        self.name = name
        self._start: Optional[float] = None
        self._elapsed = 0.0
        self._count = 0
        self._last = 0.0
        self._span: Optional[TraceAnnotation] = None
        # own time: the Timers' spans open now, innermost last; what ran
        # inside this one since its start; this timer's spans less those
        self._open = open_timers
        self._inside = 0.0
        self._own = 0.0

    def start(self, **args):
        """Open the span; `args` go to the trace as its arguments (with
        `step_num` it is a step marker, as `train-pass` is)."""
        if self._start is not None:
            raise RuntimeError(f"timer {self.name} already started")
        self._span = (StepTraceAnnotation if "step_num" in args
                      else TraceAnnotation)(self.name, **args)
        self._span.__enter__()
        self._open.append(self)
        self._start = time.perf_counter()

    def stop(self, **args):
        """Close the span; `args` are what only its end knows."""
        if self._start is None:
            raise RuntimeError(f"timer {self.name} not started")
        self._lap()
        self._start = None
        if args:
            self._span.set_metadata(**args)
        self._span.__exit__(None, None, None)
        self._span = None
        self._own += self._last - self._inside
        self._inside = 0.0
        self._open.remove(self)
        if self._open:
            self._open[-1]._inside += self._last

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *_exc):
        self.stop()

    def _lap(self):
        self._last = time.perf_counter() - self._start
        self._elapsed += self._last
        self._count += 1

    def elapsed(self, reset: bool = True) -> float:
        running = self._start is not None
        if running:
            # the clock laps; the trace span stays one span
            self._lap()
        out = self._elapsed
        if reset:
            self._elapsed = 0.0
            self._count = 0
        if running:
            self._start = time.perf_counter()
        return out


    def last(self) -> float:
        """Duration of the most recently completed span (not reset by
        elapsed() — the telemetry journal reads per-step spans while the
        log-interval window keeps accumulating)."""
        return self._last

    def own(self) -> float:
        """Seconds of this timer's completed spans less the spans of the
        same Timers that ran inside them; cumulative, never reset."""
        return self._own


class _DummyTimer:
    def start(self, **args):
        pass

    def stop(self, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        pass

    def own(self) -> float:
        return 0.0

    def elapsed(self, reset: bool = True) -> float:
        return 0.0

    def last(self) -> float:
        return 0.0


class Timers:
    """timers('span', level)(start/stop); below-threshold spans are no-ops
    (ref: Timers with --timing_log_level).

    Span truthfulness: a start/stop pair measures host wall-clock only, so
    a span around an async dispatch (device_put, jitted call) measures the
    DISPATCH, not the work. Spans that must cover the work either sync
    inside the span (the train loop's `batch-transfer` span holds a
    block_until_ready; `forward-backward-optimizer` holds the metrics
    host-fetch in the synchronous loop) or are split into an honest
    dispatch span plus a landed/completion span credited via record() from
    wherever the completion is actually observed (the async loop's
    prefetcher measures transfer time on its worker thread and the loop
    credits it at pop time; the lagged metrics fetch is recorded as
    `metrics-fetch`)."""

    def __init__(self, log_level: int = 0):
        self.log_level = log_level
        self._timers: Dict[str, _Timer] = {}
        self._dummy = _DummyTimer()
        self._open: List[_Timer] = []

    def __call__(self, name: str, level: int = 0):
        if level > self.log_level:
            return self._dummy
        if name not in self._timers:
            self._timers[name] = _Timer(name, self._open)
        return self._timers[name]

    def record(self, name: str, seconds: float, level: int = 0) -> None:
        """Credit an externally measured duration as one completed span of
        `name` (level-gated like __call__). For spans whose wall-clock is
        observed somewhere a start/stop pair cannot reach: another thread
        (the prefetcher's device transfers) or a pipelined completion (the
        async loop's lagged metrics fetch). Must be called from the loop
        thread — _Timer is not thread-safe."""
        if level > self.log_level or seconds < 0:
            return
        t = self(name)
        t._last = seconds
        t._elapsed += seconds
        t._own += seconds
        t._count += 1

    def own_s(self) -> Dict[str, float]:
        """{span: its own seconds so far} (_Timer.own), every timer."""
        return {n: t.own() for n, t in self._timers.items()}

    def elapsed_ms(self, names=None, reset: bool = True) -> Dict[str, float]:
        """{span: accumulated ms since last reset} (for writer scalars)."""
        names = names if names is not None else sorted(self._timers)
        return {n: self._timers[n].elapsed(reset) * 1000.0
                for n in names if n in self._timers}

    def last_s(self, name: str) -> float:
        """Most recent completed span of `name` in SECONDS (0.0 for a
        never-stopped or below-log-level timer) — per-step telemetry."""
        t = self._timers.get(name)
        return t.last() if t is not None else 0.0

    def log_string(self, names=None, normalizer: float = 1.0,
                   reset: bool = True) -> str:
        names = names if names is not None else sorted(self._timers)
        parts = []
        for n in names:
            if n in self._timers:
                ms = self._timers[n].elapsed(reset) * 1000.0 / normalizer
                parts.append(f"{n}: {ms:.2f}")
        return "time (ms) | " + " | ".join(parts) if parts else ""
