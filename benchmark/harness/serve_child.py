"""The process that holds the chip in a serving cell: the user's entry
point, `tools/run_text_generation_server.main(argv)`, behind a few lines.

    python -m benchmark.harness.serve_child <plan.json>

What the wrapper adds, and why the CLI cannot: the traced run sets the
process-global event journal (the CLI has no flag for it), so that the
engine's `serve_request` records exist; the weights `init_params` returns
are kept by reference, so that after the server has drained the plain
reference can score the check requests under the same weights; and the
device summary and peak memory are written where the parent finds them
(the server exposes neither). The end-to-end run sets no journal.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time


def _load_entry_point(repo: str):
    path = os.path.join(repo, "tools", "run_text_generation_server.py")
    spec = importlib.util.spec_from_file_location(
        "run_text_generation_server", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_logprobs(reference, params, sequences: list,
                       config: dict) -> list:
    """log p(token i+1 | tokens up to i) under the configuration's plain
    reference, for each check sequence (prompt then the server's
    continuation)."""
    import jax
    import jax.numpy as jnp

    weights = reference.from_program_params(params)
    score = jax.jit(
        lambda w, t: reference.next_token_logprobs(w, t, config))
    return [[float(x) for x in score(weights, jnp.asarray(seq, jnp.int32))]
            for seq in sequences]


def main(plan_path: str) -> int:
    boot = {"child_start": time.time()}
    from benchmark.harness import child, devices, spec

    plan, found = child.begin(plan_path)
    run_dir = plan["run_dir"]
    boot["devices_found"] = time.time()
    reference = spec.load_module(plan["reference"])
    argv = (reference.program_flags(plan["config"], plan["seq_length"])
            + plan["config"]["program"]["flags"] + plan["argv"])

    if plan["journal"]:
        from megatron_tpu.telemetry.journal import (
            EventJournal, set_global_journal,
        )

        set_global_journal(EventJournal(
            os.path.join(run_dir, "tele", "events.jsonl")))

    from megatron_tpu.models import params as params_mod

    kept = {}
    init_params = params_mod.init_params

    def init_and_keep(*args, **kwargs):
        import jax

        boot["init_start"] = time.time()
        kept["params"] = jax.block_until_ready(init_params(*args, **kwargs))
        boot["init_end"] = time.time()
        return kept["params"]

    params_mod.init_params = init_and_keep

    _load_entry_point(plan["repo"]).main(argv)  # until SIGTERM

    result = {"device": found, "boot": boot,
              "memory_peak_bytes": devices.memory_peak_bytes()}
    check_path = os.path.join(run_dir, "check_sequences.json")
    if os.path.exists(check_path):
        with open(check_path) as f:
            sequences = json.load(f)
        result["reference_logprobs"] = reference_logprobs(
            reference, kept["params"], sequences, plan["config"])
    child.write_result(run_dir, result)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
