"""How long a request waited for a slot: the 95th percentile of `queue_s`
(submit to the slot's assignment, on the engine's clock) over the
`serve_request` journal records of requests retired inside the window.
With `prefill_s` it makes up `ttft_s`: under a load that fills the slots
it says which of the two a slow first token waited for."""

from benchmark.harness import stats


def read(run):
    values = [r["queue_s"] * 1e3 for r in run.engine_requests
              if r.get("queue_s") is not None]
    # five beyond it: the window holds 180 to 220 retirements, a few more
    # or fewer with where the seed put the arrivals
    return stats.percentile(values, 95, beyond=5) if values else None
