"""What the server adds around the engine: the median of `handler_s -
engine_s` over the `serve_reply` journal records of the window's requests
(the handler's time from the request's first bytes to the reply's last,
less the time from the first of its prompts' submits to the last of their
retirements): parse, tokenise, the handler thread's wake-up behind the
loop's interpreter lock, detokenise, JSON, the socket."""

from benchmark.harness import serve_journal, stats


def read(run):
    values = [(r["handler_s"] - r["engine_s"]) * 1e3
              for r in serve_journal.replies(run)
              if r.get("engine_s") is not None and "handler_s" in r]
    return stats.median(values) if values else None
