"""What the host itself works a tick: over the `serve-tick` spans the
trace holds whole (one `step()` of the engine's loop, on the profiler's
clock), the median of the tick less its `tick-read` spans (the one phase
in which the loop waits for the device). What remains is admission, the
chunk's and the decode step's preparation and dispatch, pages, tokens to
requests, the journal: the serving twin of `train_host_ms_per_step`.
Beside `decode_step_ms_p50` it says how far the loop is from host-bound."""

from benchmark.harness import stats
from benchmark.harness.trace import serve_ticks


def read(run):
    ticks = serve_ticks.of_run(run)
    if not ticks:
        return None
    return stats.median([(t["tick_ps"] - t["read_ps"]) * 1e-9
                         for t in ticks])
