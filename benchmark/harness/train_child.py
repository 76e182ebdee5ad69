"""The process that holds the chip(s) in a training cell: the trainer's
own entry point, `pretrain_gpt.main(argv)`, in-process, with the
benchmark's clock on each completed step.

    python -m benchmark.harness.train_child <plan.json>

The plan (written by the parent, benchmark/harness/train_driver.py) holds
the trainer's flags, the corpus to draw from the seed, the warm-up and
window lengths and where to write the result. From the program this takes
the entry point, the step journal (`EventJournal.emit("step", ...)`: the
benchmark stamps its own clock as each record arrives, which is when the
step's metrics have been fetched from the device) and
`TrainLoop.train`, wrapped once to copy the initial weights and the first
batch for the reference check; nothing else of the program is wrapped
(what the compiled step needs on a chip is the journal's own
`step_program` record). The trainer is stopped by its own exit: a SIGTERM
at the end of the window, which its signal handler turns into
drain-and-return.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time


def build_corpus(prefix: str, spec: dict, vocab_size: int, seed: int) -> int:
    """Seeded synthetic documents through the repo's indexed-dataset
    writer: log-normal lengths, each document walking one seeded cycle of
    token ids from a random start (so the next token is a function of the
    current one and the loss falls within the warm-up), end-of-document id
    appended. The mix's optional "reserved_ids" (default 0) keeps that
    many ids just under the end-of-document id out of the cycle, free for
    a configuration's own tokens (a mask token): the corpus never emits
    them. Returns the number of tokens written."""
    import numpy as np

    from megatron_tpu.data.indexed_dataset import (
        index_file_path, make_builder,
    )

    rng = np.random.default_rng(seed)
    eod = vocab_size - 1
    cycle = rng.choice(eod - spec.get("reserved_ids", 0),
                       size=spec["cycle"], replace=False)
    lengths = np.exp(rng.normal(np.log(spec["doc_tokens_median"]),
                                spec["doc_tokens_sigma"],
                                size=4 * spec["tokens"]
                                // spec["doc_tokens_median"]))
    lengths = np.clip(lengths.astype(np.int64), spec["doc_tokens_min"],
                      spec["doc_tokens_max"])
    lengths = lengths[: int(np.searchsorted(np.cumsum(lengths),
                                            spec["tokens"])) + 1]
    starts = rng.integers(0, spec["cycle"], size=len(lengths))
    builder = make_builder(prefix, vocab_size=vocab_size)
    for n, at in zip(lengths, starts):
        doc = cycle[(at + np.arange(n)) % spec["cycle"]]
        builder.add_doc(np.append(doc, eod))
    builder.finalize(index_file_path(prefix))
    return int(lengths.sum() + len(lengths))


class StepClock:
    """The benchmark's observer on the trainer's step journal."""

    def __init__(self, warmup_steps: int, seconds: float):
        self.warmup_steps = warmup_steps
        self.seconds = seconds
        self.steps: list = []
        self.window_start = None  # (time.time(), time.monotonic())
        self._timer = None

    def install(self) -> None:
        from megatron_tpu.telemetry.journal import EventJournal

        emit = EventJournal.emit
        clock = self

        def stamped_emit(journal, kind, **fields):
            if kind == "step":
                clock.on_step(time.monotonic(), fields)
            return emit(journal, kind, **fields)

        EventJournal.emit = stamped_emit

    def on_step(self, now: float, rec: dict) -> None:
        self.steps.append({
            "t": now, "iteration": rec.get("iteration"),
            "ntokens": rec.get("ntokens"), "step_ms": rec.get("step_ms"),
            "data_wait_ms": rec.get("data_wait_ms"),
            "loss": rec.get("loss"), "compiles": rec.get("compiles", 0)})
        if rec.get("iteration") == self.warmup_steps:
            # the window opens as the last warm-up step completes
            self.window_start = (time.time(), now)
            self._timer = threading.Timer(
                self.seconds, os.kill, (os.getpid(), signal.SIGTERM))
            self._timer.daemon = True
            self._timer.start()


def snapshot_at_train_start(kept: dict, marks: dict) -> None:
    """Wrap TrainLoop.train once: copy the initial weights to the host
    and draw the first global batch, for the reference check."""
    import jax
    import numpy as np

    from megatron_tpu.training import pretrain as pretrain_mod

    train = pretrain_mod.TrainLoop.train

    def train_with_snapshot(loop, train_iter_factory, *args, **kwargs):
        marks["state_ready"] = time.time()
        kept["params"] = jax.device_get(loop.state.params)
        gbs = loop.cfg.training.global_batch_size
        first = next(iter(train_iter_factory(0, gbs)))
        kept["batch"] = {k: np.asarray(v) for k, v in first.items()}
        marks["snapshot_done"] = time.time()
        return train(loop, train_iter_factory, *args, **kwargs)

    pretrain_mod.TrainLoop.train = train_with_snapshot


def reference_first_loss(reference, kept: dict, config: dict) -> float:
    """The configuration's plain float32 reference's loss on the first
    global batch under the initial weights."""
    import jax
    import jax.numpy as jnp

    weights = reference.from_program_params(kept["params"])
    batch = kept["batch"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(batch["tokens"].shape, jnp.float32)
    loss = jax.jit(lambda w, t, y, m: reference.lm_loss(w, t, y, m, config))(
        weights, jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"]),
        jnp.asarray(mask))
    return float(loss)


def main(plan_path: str) -> int:
    marks = {"child_start": time.time()}
    from benchmark.harness import child, devices, spec

    plan, found = child.begin(plan_path)
    run_dir = plan["run_dir"]
    marks["devices_found"] = time.time()
    config = plan["config"]
    reference = spec.load_module(plan["reference"])
    argv = (reference.program_flags(config, plan["seq_length"])
            + config["program"]["flags"] + plan["argv"])

    corpus_tokens = build_corpus(
        plan["data_path"], plan["corpus"], config["vocab_size"],
        plan["seed"])
    marks["corpus_built"] = time.time()

    clock = StepClock(plan["warmup_steps"], plan["seconds"])
    clock.install()
    kept: dict = {}
    snapshot_at_train_start(kept, marks)

    import pretrain_gpt

    state = pretrain_gpt.main(argv)
    del state  # the reference below needs the room
    ids = [kept["batch"][k] for k in ("tokens", "labels")]
    result = {
        "device": found, "memory_peak_bytes": devices.memory_peak_bytes(),
        "marks": marks, "corpus_tokens": corpus_tokens,
        "steps": clock.steps, "window_start": clock.window_start,
        # smallest and largest id the first global batch holds (a sliced
        # vocabulary is a smaller vocabulary: none may reach vocab_size)
        "first_batch_ids": [int(min(a.min() for a in ids)),
                            int(max(a.max() for a in ids))]}
    flops = getattr(reference, "train_flops_per_token", None)
    if flops is not None:
        result["train_flops_per_token"] = flops(config, plan["seq_length"])

    t0 = time.monotonic()
    result["reference_first_loss"] = reference_first_loss(
        reference, kept, config)
    result["reference_s"] = time.monotonic() - t0
    child.write_result(run_dir, result)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
