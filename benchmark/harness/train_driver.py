"""A training cell: plan the job from the configuration and the mix, run
it in the child that holds the chip(s), reduce its step records.

The mix (benchmark/traffic/<mix>.json, "driver": "train") gives the job:
sequence length, micro and global batch, the trainer's flags, the corpus,
the warm-up steps before the window and the steps to trace.
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List

from benchmark.harness import common, compile_watch, peaks, spec, stats
from benchmark.harness.trace import reduce as trace_reduce

CHILD = "benchmark.harness.train_child"


def plan_job(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             rehearse: bool, run_dir: str) -> Dict[str, Any]:
    mix = cell.traffic
    warmup = int(mix["warmup_steps"])
    # more steps than any program could take in the window: the trainer
    # is stopped by the clock, and running out of steps voids the run
    iters = warmup + math.ceil((seconds + 10) * mix["max_steps_per_s"])
    # the job's flags; the child puts the architecture's before them
    argv = [
        "--micro_batch_size", str(mix["micro_batch_size"]),
        "--global_batch_size", str(mix["global_batch_size"]),
        "--train_iters", str(iters), "--log_interval", "100",
        "--data_path", os.path.join(run_dir, "corpus"),
        "--split", "100,0,0", "--eval_interval", "10000000",
        "--eval_iters", "0", "--seed", str(seed),
        "--telemetry_dir", os.path.join(run_dir, "tele"),
    ] + list(mix["flags"])
    if trace:
        first = warmup + int(mix["trace_after_steps"])
        argv += ["--profile", "--profile_step_start", str(first),
                 "--profile_step_end", str(first + int(mix["trace_steps"])),
                 "--profile_dir", os.path.join(run_dir, "trace")]
    return {"run_dir": run_dir, "chips": cell.chips, "rehearse": rehearse,
            "seed": seed, "seconds": seconds, "warmup_steps": warmup,
            "train_iters": iters, "argv": argv, "config": cell.config,
            "reference": cell.reference_path(), "trace": trace,
            "seq_length": mix["seq_length"], "corpus": mix["corpus"],
            "data_path": os.path.join(run_dir, "corpus")}


def tokens_per_s(steps: List[dict], window_start: float) -> float:
    """Tokens of the steps that finished inside the window over the time
    from the window's start, itself a step boundary, to the last of
    them: both ends are moments at which a step's metrics had been
    fetched from the device, so no part of a step is cut off."""
    if not steps:
        raise stats.TooFewSamples("no step finished inside the window")
    return (sum(s["ntokens"] for s in steps)
            / (steps[-1]["t"] - window_start))


def check(result: dict, plan: dict, inside: List[dict],
          mix: dict) -> tuple:
    """(problems, the numbers compared, each beside its limit)."""
    problems = []
    steps = result["steps"]
    first = steps[0]["loss"] if steps else float("nan")
    ref = result["reference_first_loss"]
    # bf16 weights and activations with float32 accumulation against the
    # float32 reference, averaged over a batch of thousands of tokens:
    # the two agree to the mix's tolerance (set from chip runs, PERF.md);
    # dropping a term of the loss or a norm moves it by far more
    compared = {"first_loss_gap": {
        "value": abs(first - ref), "at_most": mix["first_loss_tolerance"]}}
    if not abs(first - ref) <= mix["first_loss_tolerance"]:
        problems.append(f"first loss {first} vs reference {ref}")
    losses = [s["loss"] for s in inside]
    if losses:
        compared["loss_fall_in_window"] = {
            "value": first - max(losses),
            "at_least": mix["loss_must_fall_by"]}
    if not all(math.isfinite(x) for x in losses):
        problems.append("a loss inside the window is not finite")
    elif losses and max(losses) > first - mix["loss_must_fall_by"]:
        problems.append(f"loss inside the window reaches {max(losses)}, "
                        f"not {mix['loss_must_fall_by']} below the first "
                        f"step's {first}")
    if len(steps) >= plan["train_iters"]:
        problems.append("the trainer ran out of steps before the window "
                        "closed (raise max_steps_per_s in a new mix)")
    return problems, compared


def compiles_inside(compiles: list, inside: List[dict], window: tuple) -> int:
    """Compile requests the benchmark's listener saw inside the window,
    or, if more, those the step journal reports for its steps."""
    return max(sum(window[0] <= c["t"] <= window[1] for c in compiles),
               sum(int(s["compiles"] or 0) for s in inside))


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        rehearse: bool, started: float) -> common.Run:
    run_dir = common.fresh_run_dir(cell.name)
    plan = plan_job(cell, seed, seconds, trace, rehearse, run_dir)
    plan_path = os.path.join(run_dir, "plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    log_path = os.path.join(run_dir, "child.log")
    proc = common.start_child(CHILD, plan_path, log_path, cell.chips,
                              rehearse)
    common.finish_child(proc, 1100.0, log_path, "the trainer")
    with open(os.path.join(run_dir, "result.json")) as f:
        result = json.load(f)
    if result["window_start"] is None:
        raise common.RunFailed("the trainer ended before its warm-up did")
    wall0, mono0 = result["window_start"]
    inside = [s for s in result["steps"]
              if mono0 < s["t"] <= mono0 + seconds]
    compiles = compile_watch.read(os.path.join(run_dir, "compiles.jsonl"))
    problems, compared = check(result, plan, inside, cell.traffic)
    out = common.Run(
        cell=cell, seconds=seconds, device=result["device"],
        memory_peak_bytes=result["memory_peak_bytes"],
        setup_s=wall0 - started,
        end_to_end={"train_tokens_per_s":
                    lambda: tokens_per_s(inside, mono0)},
        attempted=len(inside), failed=0, problems=problems,
        compared=compared, steps=inside,
        compiles_in_window=compiles_inside(
            compiles, inside, (wall0, wall0 + seconds)))
    marks = result["marks"]
    first = result["steps"][0]["t"] - mono0 + wall0  # first step's end
    # where set-up goes (wall clock): imports and the device, the corpus,
    # the trainer's own start (dataset index, init of the state), the
    # benchmark's copy of weights and first batch, the first step (compile
    # or cache load), the remaining warm-up steps
    out.extras["setup_split_s"] = {
        "import_and_devices": marks["devices_found"] - started,
        "corpus": marks["corpus_built"] - marks["devices_found"],
        "trainer_start": marks["state_ready"] - marks["corpus_built"],
        "snapshot": marks["snapshot_done"] - marks["state_ready"],
        "first_step": first - marks["snapshot_done"],
        "warmup_steps": wall0 - first}
    # model FLOP/s utilization, noted and not a metric: it is the rate
    # above times two constants (the architecture's own count of model
    # FLOPs a token, the peak of benchmark/peaks.json), so it can never
    # move apart from train_tokens_per_s
    if "train_flops_per_token" in result and inside and (
            result["device"]["platform"] == "tpu"):
        peak = peaks.peaks_for(result["device"]["kind"])["bf16_flops_per_s"]
        out.extras["mfu_pct"] = (
            100.0 * tokens_per_s(inside, mono0)
            * result["train_flops_per_token"]
            / (result["device"]["count"] * peak))
    out.extras["reference"] = {
        "first_loss": result["steps"][0]["loss"],
        "reference_first_loss": result["reference_first_loss"],
        "seconds": result["reference_s"]}
    if trace:
        out.trace = trace_reduce.reduce_trace(os.path.join(run_dir, "trace"))
    return out
