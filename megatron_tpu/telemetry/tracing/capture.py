"""Start and stop a jax.profiler capture: the one place that does.

The train loop's ``--profile`` window, its SIGUSR1 window and the
server's ``/admin/profile`` all capture through here, so they agree on
what a trace holds: the device planes, and on ``/host:CPU`` the host
tracer's events, among them the program's own spans (the loop's timers
and ``train-pass``, training/timers.py). The Python tracer is off. It
records every Python call of every thread, which slowed the host path a
trace exists to show and made a second of trace tens of megabytes; the
program spans say what the host was doing, and nothing read the rest.

Unlike its siblings this module imports jax (they read traces anywhere;
this one makes them, in the process that holds the device), so the
package does not import it for you.
"""

from __future__ import annotations

import jax


def start(log_dir: str) -> None:
    """Begin a capture into ``log_dir`` (``<log_dir>/plugins/profile/
    <session>/<host>.xplane.pb``). Raises what the profiler raises: its
    session is one per process, and a second start fails."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)


def stop() -> None:
    """End the capture and write its files."""
    jax.profiler.stop_trace()
