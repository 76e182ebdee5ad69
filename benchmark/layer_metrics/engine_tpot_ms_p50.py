"""Median time per output token after the first, on the engine's clock:
`tpot_s` of the `serve_request` journal records of requests retired
inside the window."""

from benchmark.harness import stats


def read(run):
    values = [r["tpot_s"] * 1e3 for r in run.engine_requests
              if r.get("tpot_s") is not None]
    return stats.median(values) if values else None
