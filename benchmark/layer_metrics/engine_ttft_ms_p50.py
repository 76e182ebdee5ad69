"""Median time to first token on the engine's clock, from `submit()`:
`ttft_s` of the `serve_request` journal records of requests retired
inside the window (the traced run sets the journal)."""

from benchmark.harness import stats


def read(run):
    values = [r["ttft_s"] * 1e3 for r in run.engine_requests
              if r.get("ttft_s") is not None]
    return stats.median(values) if values else None
