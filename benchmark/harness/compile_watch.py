"""Every compile request of this process, with the wall time it ended, in
a file the parent reads: a compilation inside the measured window voids
the run. A persistent-cache hit counts too — it means a program was first
asked for inside the window, which warm-up exists to prevent."""

from __future__ import annotations

import json
import threading
import time

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def install(path: str) -> None:
    import jax.monitoring

    lock = threading.Lock()
    out = open(path, "a", buffering=1)  # noqa: SIM115 - lives with the process

    def on_duration(name: str, secs: float, **_kw) -> None:
        if name == _BACKEND_COMPILE:
            with lock:
                out.write(json.dumps({"t": time.time(), "s": secs}) + "\n")

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def read(path: str) -> list:
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except FileNotFoundError:
        return []
