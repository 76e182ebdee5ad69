"""The one general traffic generator: requests and arrivals from a mix's
parameters and a seed, and the client that offers them over HTTP.

A mix is a data file (benchmark/traffic/<mix>.json). For serving:

    {"driver": "serve_open",            open loop: Poisson arrivals of
                                        a fixed count (see `arrivals`)
     "rate_rps": 11.2,                  fixed offered rate (no search)
     "prompt_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                       "min": 16, "max": 512},
     "new_tokens": {...same form...},
     "stratify": 40,                    see draw_lengths
     "lead_s": 8, "trail_s": 15}        load before / after the window

    {"driver": "serve_closed", "clients": 8, ...}   closed loop

The same seed gives byte-identical requests and due times. Latency is
timed from when a request was DUE, not from when it was sent, so a stall
charges the requests queued behind it, and how late the generator itself
ran is reported (`lateness_s`) instead of flattering the server.
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import statistics
import time
from typing import Any, Dict, List, Optional

_NORMAL = statistics.NormalDist()


def _quantile(spec: Dict[str, Any], u: float) -> int:
    """Inverse CDF of a length distribution at u in (0, 1), clipped."""
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    if dist == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    elif dist == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return int(min(max(math.floor(x), spec["min"]), spec["max"]))


def draw_lengths(rng: random.Random, spec: Dict[str, Any], n: int,
                 stratify: int = 1) -> List[int]:
    """n lengths from `spec`. With stratify = k > 1 they come in shuffled
    blocks of k, one from each k-quantile of the distribution: every
    window of a run then holds the mix's distribution, tails included,
    and two seeds differ in order and jitter, not in how much work they
    drew. A tail percentile of latency otherwise mostly measures which
    lengths a seed happened to draw."""
    out: List[int] = []
    k = max(int(stratify), 1)
    while len(out) < n:
        block = [_quantile(spec, (i + rng.random()) / k) for i in range(k)]
        rng.shuffle(block)
        out.extend(block)
    return out[:n]


def make_requests(traffic: Dict[str, Any], vocab_size: int, seed: int,
                  n: int) -> List[Dict[str, Any]]:
    """n requests: prompt token ids (what the null tokenizer takes, no
    shared prefixes) and the number of tokens to generate."""
    rng = random.Random(f"requests:{seed}")
    stratify = traffic.get("stratify", 1)
    prompts = draw_lengths(rng, traffic["prompt_tokens"], n, stratify)
    news = draw_lengths(rng, traffic["new_tokens"], n, stratify)
    out = []
    for p, g in zip(prompts, news):
        # id 0 and the top id stay out: the tokenizer's specials
        out.append({"prompt": [rng.randrange(1, vocab_size - 1)
                               for _ in range(p)], "new_tokens": g})
    return out


def arrivals(rate_rps: float, segments: List[tuple],
             seed: int) -> List[float]:
    """Due times of a Poisson process at rate_rps over consecutive
    segments [(start, end), ...], given its count in each: round(rate x
    length) points, uniform over the segment, which is what a Poisson
    process is once its count is known. Fixing the count at its mean
    makes every seed offer the same amount of work; the seed places it."""
    if rate_rps <= 0:
        raise ValueError("rate_rps must be positive")
    rng = random.Random(f"arrivals:{seed}")
    out: List[float] = []
    for start, end in segments:
        n = round(rate_rps * (end - start))
        out += sorted(start + rng.random() * (end - start) for _ in range(n))
    return out


def request_body(req: Dict[str, Any], logprobs: bool = False) -> bytes:
    body = {"prompts": [" ".join(map(str, req["prompt"]))],
            "tokens_to_generate": int(req["new_tokens"]), "top_k": 1}
    if logprobs:
        body["logprobs"] = True
    return json.dumps(body).encode()


async def http_request(host: str, port: int, path: str, body: bytes,
                       timeout: float, method: str = "PUT") -> tuple:
    """(status, body bytes) of one request; (0, error text) when the
    server refused, broke the connection or ran past `timeout`."""
    async def go():
        reader, writer = await asyncio.open_connection(host, port)
        try:
            writer.write(
                f"{method} {path} HTTP/1.1\r\nHost: {host}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
                .encode() + body)
            await writer.drain()
            raw = await reader.read(-1)  # the server closes when done
        finally:
            writer.close()
        head, _, payload = raw.partition(b"\r\n\r\n")
        return int(head.split(None, 2)[1]), payload

    try:
        return await asyncio.wait_for(go(), timeout)
    except (OSError, asyncio.TimeoutError, ValueError, IndexError) as e:
        return 0, f"{type(e).__name__}: {e}".encode()


def _new_tokens_in(reply: bytes, prompt_len: int) -> Optional[int]:
    try:
        return len(json.loads(reply)["text"][0].split()) - prompt_len
    except (ValueError, KeyError, IndexError, TypeError):
        return None


async def _one(host, port, req, due, t0, timeout, record) -> None:
    """Send one request now; its record is timed from `due`."""
    sent = time.monotonic() - t0
    status, reply = await http_request(host, port, "/api",
                                       request_body(req), timeout)
    done = time.monotonic() - t0
    got = _new_tokens_in(reply, len(req["prompt"])) if status == 200 else None
    record.update(
        due_s=due, lateness_s=sent - due, done_s=done, status=status,
        latency_s=done - due, prompt_tokens=len(req["prompt"]),
        asked_tokens=req["new_tokens"], new_tokens=got,
        ok=status == 200 and got == req["new_tokens"])
    if not record["ok"]:
        record["error"] = reply[:200].decode(errors="replace")


async def run_open_loop(host: str, port: int, traffic: Dict[str, Any],
                        vocab_size: int, seed: int, seconds: float,
                        timeout: float = 120.0) -> Dict[str, Any]:
    """Arrivals at traffic["rate_rps"] for lead_s + seconds + (at most)
    trail_s. The measured requests are those DUE inside the
    window; load goes on after it so that they finish under the load
    they arrived in, and stops once the last of them has."""
    lead, trail = traffic.get("lead_s", 0.0), traffic.get("trail_s", 0.0)
    end = lead + seconds
    dues = arrivals(traffic["rate_rps"],
                    [(0.0, lead), (lead, end), (end, end + trail)], seed)
    requests = make_requests(traffic, vocab_size, seed, len(dues))
    records: List[Dict[str, Any]] = [{} for _ in dues]
    measured = [i for i, a in enumerate(dues) if lead <= a < end]
    tasks: List[asyncio.Task] = []
    t0 = time.monotonic()
    for i, due in enumerate(dues):
        delay = due - (time.monotonic() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        if due >= end and all(records[j] for j in measured):
            break  # the trail has done its work
        tasks.append(asyncio.create_task(_one(
            host, port, requests[i], due, t0, timeout, records[i])))
    if measured:
        await asyncio.wait([tasks[i] for i in measured if i < len(tasks)])
    for task in tasks:  # the unmeasured tail: drop it
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return {"t0_monotonic": t0, "window": (lead, end),
            "records": [records[i] for i in measured],
            "offered": len(measured)}


async def run_closed_loop(host: str, port: int, traffic: Dict[str, Any],
                          vocab_size: int, seed: int, seconds: float,
                          timeout: float = 300.0) -> Dict[str, Any]:
    """traffic["clients"] callers, each sending its next request when the
    last has returned, for lead_s + seconds; requests still in flight at
    the end are dropped. The records are of the requests that COMPLETED
    inside the window."""
    lead = traffic.get("lead_s", 0.0)
    clients = int(traffic["clients"])
    end = lead + seconds
    # more than any run can use: the pool is cut by time, not by count
    pool = make_requests(traffic, vocab_size, seed,
                         int(traffic.get("pool", 64 * clients)))
    records: List[Dict[str, Any]] = []
    t0 = time.monotonic()
    next_index = 0

    async def client() -> None:
        nonlocal next_index
        while time.monotonic() - t0 < end and next_index < len(pool):
            req = pool[next_index]
            next_index += 1
            record: Dict[str, Any] = {}
            await _one(host, port, req, time.monotonic() - t0, t0, timeout,
                       record)
            records.append(record)

    tasks = [asyncio.create_task(client()) for _ in range(clients)]
    await asyncio.sleep(max(end - (time.monotonic() - t0), 0.0))
    for task in tasks:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    inside = [r for r in records if r and lead <= r["done_s"] < end]
    return {"t0_monotonic": t0, "window": (lead, end), "records": inside,
            "offered": len(inside)}
