"""Temporaries of the compiled train step on one chip, by the compiler's
account of the program that ran: `temp_bytes` of the `step_program`
record the trainer writes to its journal after a run that opened a trace
window (training/pretrain.py `_journal_step_program`). The part of
step_hbm_gb that is activations and workspace and not state, so the part
that recompute, chunking and kernel changes move."""

from benchmark.harness.trace import named


def read(run):
    if not run.steps:
        return None
    programs = [r for r in named.journal(named.run_files(run)[1])
                if r.get("kind") == "step_program"]
    return programs[-1]["temp_bytes"] / 1e9 if programs else None
