"""How late the benchmark's own generator sent its requests: send time
minus due time, the largest over the window's requests. Latency is timed
from the due time, so a late generator worsens `request_ms_*` rather than
flattering the server; this says by how much at most."""


def read(run):
    values = [r["lateness_s"] * 1e3 for r in run.requests
              if "lateness_s" in r]
    return max(values) if values else None
