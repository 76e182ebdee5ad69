"""A serving cell: boot the server child, warm up, offer the mix's load
over HTTP, trace a few seconds of it, drain, and hold the replies to the
plain reference.

The mix ("driver": "serve_open" or "serve_closed") gives the traffic; the
configuration's `program.flags` give the deployment (engine, slots, page
pool). The parent never initialises a JAX backend.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

from benchmark.harness import common, compile_watch, spec, stats, traffic
from benchmark.harness.trace import reduce as trace_reduce

CHILD = "benchmark.harness.serve_child"
HOST = "127.0.0.1"
RECOMPILES = "engine_decode_recompiles_total"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind((HOST, 0))
        return s.getsockname()[1]


def http_get(url: str, timeout: float = 5.0) -> tuple:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()
    except OSError as e:  # not listening yet: model load, compile
        return 0, str(e)


def _counter(metrics_text: str, name: str) -> Optional[float]:
    for line in metrics_text.splitlines():
        if line.startswith(name):
            return float(line.split()[-1])
    return None


def plan_server(cell: spec.Cell, seed: int, trace: bool, rehearse: bool,
                run_dir: str, port: int) -> Dict[str, Any]:
    serve = cell.config["program"]["serve"]
    # the deployment's flags; the child puts the architecture's before them
    argv = [
        "--tokenizer_type", "null", "--host", HOST, "--port", str(port),
        "--seed", str(seed), "--serve_warmup",
        "--serve_profile_dir", os.path.join(run_dir, "trace"),
    ] + list(serve["flags"])
    return {"run_dir": run_dir, "chips": cell.chips, "rehearse": rehearse,
            "repo": spec.REPO, "argv": argv, "config": cell.config,
            "reference": cell.reference_path(),
            "seq_length": serve["seq_length"], "journal": bool(trace)}


def check_requests(cell: spec.Cell, seed: int) -> List[Dict[str, Any]]:
    """A seeded handful of short prompts, asked with `logprobs` before
    the window: they warm the request path and are what the reference
    scores. All of one length, so the reference compiles once."""
    chk = cell.traffic["check"]
    mix = {"prompt_tokens": {"dist": "fixed", "value": chk["prompt_tokens"]},
           "new_tokens": {"dist": "fixed", "value": chk["new_tokens"]}}
    return traffic.make_requests(mix, cell.config["vocab_size"],
                                 seed + 7919, chk["requests"])


async def _ask_all(port: int, bodies: List[bytes], timeout: float) -> list:
    return await asyncio.gather(*[
        traffic.http_request(HOST, port, "/api", b, timeout)
        for b in bodies])


async def _offer(cell: spec.Cell, port: int, seed: int, seconds: float,
                 trace_dir: Optional[str]) -> Dict[str, Any]:
    mix = cell.traffic
    vocab = cell.config["vocab_size"]
    loop = (traffic.run_open_loop if mix["driver"] == "serve_open"
            else traffic.run_closed_loop)
    load = asyncio.create_task(loop(HOST, port, mix, vocab, seed, seconds))
    if trace_dir is not None:
        # a few seconds from inside the window, taken by the server's own
        # /admin/profile: it closes at timeout_s with what it saw
        await asyncio.sleep(mix.get("lead_s", 0.0) + mix["trace_after_s"])
        body = json.dumps({"steps": 10000, "timeout_s": mix["trace_s"],
                           "dir": trace_dir}).encode()
        await traffic.http_request(
            HOST, port, "/admin/profile", body, mix["trace_s"] + 120.0,
            method="POST")
    return await load


def _compare_logprobs(replies: list, reference: list, checks: list,
                      tolerance: float) -> tuple:
    """(problems, the largest |server - reference| log-probability)."""
    problems, worst = [], 0.0
    for i, (reply, ref) in enumerate(zip(replies, reference)):
        got = reply["logprobs"][0][: len(ref)]
        diff = max(abs(a - b) for a, b in zip(got, ref))
        worst = max(worst, diff)
        if not diff <= tolerance:
            problems.append(
                f"check request {i}: log-probabilities differ from the "
                f"reference by {diff:.4f} (allowed {tolerance})")
    if len(replies) != len(checks) or len(reference) != len(checks):
        problems.append("not every check request was answered and scored")
    return problems, worst


def tokens_per_s(records: List[dict]) -> float:
    """Prompt plus generated tokens of the requests completed inside the
    window, from the first completion in it to the last, over the time
    between the two: a request cut by either edge adds no noise."""
    done = sorted((r for r in records if r["ok"]), key=lambda r: r["done_s"])
    if len(done) < 3:
        raise stats.TooFewSamples(
            f"{len(done)} requests completed inside the window")
    tokens = sum(r["prompt_tokens"] + r["new_tokens"] for r in done[1:])
    return tokens / (done[-1]["done_s"] - done[0]["done_s"])


class Server:
    """The server child, from boot to drain. `with Server(...) as s:` has
    it ready (its own warm-up done, /readyz 200); `s.stop()` sends the
    SIGTERM that makes it drain and write its result."""

    def __init__(self, cell: spec.Cell, seed: int, trace: bool,
                 rehearse: bool, run_dir: str):
        self.port = _free_port()
        self.base = f"http://{HOST}:{self.port}"
        self.log_path = os.path.join(run_dir, "child.log")
        plan = plan_server(cell, seed, trace, rehearse, run_dir, self.port)
        plan_path = os.path.join(run_dir, "plan.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        self.proc = common.start_child(CHILD, plan_path, self.log_path,
                                       cell.chips, rehearse)

    def __enter__(self) -> "Server":
        deadline = time.time() + 1000.0
        while http_get(self.base + "/readyz")[0] != 200:
            if self.proc.poll() is not None or time.time() > deadline \
                    or self._warmup_failed():
                self.__exit__()
                common.finish_child(self.proc, 1.0, self.log_path,
                                    "the server")
                raise common.RunFailed("the server never became ready")
            time.sleep(0.5)
        return self

    def _warmup_failed(self) -> bool:
        """The server reports a failed warm-up on stderr and keeps
        /readyz red for good: do not wait out the deadline for it."""
        with open(self.log_path, errors="replace") as f:
            return "warmup failed" in f.read()

    def recompiles(self) -> Optional[float]:
        return _counter(http_get(self.base + "/metrics")[1], RECOMPILES)

    def stop(self) -> None:
        self.proc.send_signal(signal.SIGTERM)
        common.finish_child(self.proc, 240.0, self.log_path, "the server")

    def __exit__(self, *_exc) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def warm_up(cell: spec.Cell, port: int, seed: int) -> tuple:
    """The check requests (asked with `logprobs`) and one prompt of the
    mix's greatest length, before the window: every shape the window
    will use has run. Returns (check requests, their replies, problems)."""
    checks = check_requests(cell, seed)
    longest = traffic.make_requests(
        {"prompt_tokens": {"dist": "fixed", "value":
                           cell.traffic["prompt_tokens"]["max"]},
         "new_tokens": {"dist": "fixed", "value": 8}},
        cell.config["vocab_size"], seed + 104729, 1)
    replies = asyncio.run(_ask_all(
        port, [traffic.request_body(r, logprobs=True) for r in checks]
        + [traffic.request_body(r) for r in longest], 600.0))
    problems = []
    if any(status != 200 for status, _ in replies):
        problems.append("a warm-up request failed: "
                        + str([s for s, _ in replies]))
    return checks, [json.loads(body) for status, body
                    in replies[: len(checks)] if status == 200], problems


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        rehearse: bool, started: float) -> common.Run:
    if cell.traffic["driver"] == "serve_open" and not cell.traffic.get(
            "rate_rps"):
        raise spec.SpecError(
            f"the open-loop mix {cell.traffic_name!r} fixes no rate_rps: "
            "find the knee once (benchmark/tools/knee_sweep.py) and write "
            "four fifths of it into a mix of its own")
    run_dir = common.fresh_run_dir(cell.name)
    with Server(cell, seed, trace, rehearse, run_dir) as server:
        ready_s = time.time() - started
        checks, check_replies, problems = warm_up(cell, server.port, seed)
        before = server.recompiles()
        out = asyncio.run(_offer(
            cell, server.port, seed, seconds,
            os.path.join(run_dir, "trace") if trace else None))
        lead, end = out["window"]
        wall0 = time.time() - (time.monotonic() - out["t0_monotonic"]) + lead
        after = server.recompiles()
        if before is None or after != before:
            problems.append(f"{RECOMPILES} moved: {before} -> {after}")
        with open(os.path.join(run_dir, "check_sequences.json"), "w") as f:
            json.dump([list(map(int, r["text"][0].split()))
                       for r in check_replies], f)
        server.stop()
    with open(os.path.join(run_dir, "result.json")) as f:
        result = json.load(f)
    # bf16 weights and activations against the float32 reference: a
    # log-probability near -10 agrees to the mix's tolerance (set from
    # chip runs, PERF.md); a lower precision or a dropped term does not
    mismatches, worst_logprob = _compare_logprobs(
        check_replies, result.get("reference_logprobs", []), checks,
        cell.traffic["check"]["logprob_tolerance"])
    problems += mismatches
    compiles = compile_watch.read(os.path.join(run_dir, "compiles.jsonl"))
    n_compiles = sum(wall0 <= c["t"] <= wall0 + seconds for c in compiles)

    records = out["records"]
    bad = [r for r in records if not r["ok"]]
    # a refused, failed or timed-out request misses any latency limit
    latency_ms = [r["latency_s"] * 1e3 if r["ok"] else float("inf")
                  for r in records]
    run_ = common.Run(
        cell=cell, seconds=seconds, device=result["device"],
        memory_peak_bytes=result["memory_peak_bytes"],
        setup_s=wall0 - started,
        end_to_end={
            "request_ms_p50": lambda: stats.median(latency_ms),
            "request_ms_p95": lambda: stats.percentile(latency_ms, 95),
            "serve_tokens_per_s": lambda: tokens_per_s(records),
        },
        attempted=len(records), failed=len(bad), problems=problems,
        requests=records, compiles_in_window=n_compiles)
    run_.extras["logprob_worst_diff"] = worst_logprob
    run_.compared["logprob_gap"] = {
        "value": worst_logprob,
        "at_most": cell.traffic["check"]["logprob_tolerance"]}
    boot = result["boot"]
    # where the server's boot goes (wall clock, same host): process start
    # and imports, JAX finding the device, init_params, then engine build,
    # page pool and the warm-up's compile or cache load
    run_.extras["boot_s"] = {
        "to_child_start": boot["child_start"] - started,
        "import_and_devices": boot["devices_found"] - boot["child_start"],
        "to_init": boot["init_start"] - boot["devices_found"],
        "init_params": boot["init_end"] - boot["init_start"],
        "engine_and_warmup": started + ready_s - boot["init_end"],
        "ready": ready_s}
    if trace:
        run_.trace = trace_reduce.reduce_trace(os.path.join(run_dir, "trace"))
        run_.engine_requests = _engine_requests(
            os.path.join(run_dir, "tele", "events.jsonl"),
            wall0, wall0 + seconds)
    return run_


def _engine_requests(path: str, start: float, end: float) -> List[dict]:
    """The engine's `serve_request` journal records of requests retired
    inside the window (journal time is the wall clock)."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # a torn last line
                if rec.get("kind") == "serve_request" \
                        and start <= rec.get("ts", 0) <= end:
                    out.append(rec)
    except FileNotFoundError:
        pass
    return out
