"""The program starts its traces with `jax.profiler.start_trace(dir)` and
the profiler's defaults, which include a Python tracer that records every
Python call of every thread: it slows the host path that the serving
cells exist to measure and makes the file tens of times larger. The
children switch that tracer off; the device planes are unchanged."""

from __future__ import annotations


def without_python_tracer() -> None:
    import jax

    start_trace = jax.profiler.start_trace

    def start(log_dir, *args, profiler_options=None, **kwargs):
        if profiler_options is None:
            profiler_options = jax.profiler.ProfileOptions()
            profiler_options.python_tracer_level = 0
        return start_trace(log_dir, *args,
                           profiler_options=profiler_options, **kwargs)

    jax.profiler.start_trace = start
