"""The serving cells end to end at toy widths on the CPU, and the proof
that the harness is driven by data: a configuration of another block
type with its plain reference, traffic mixes, a per-layer metric and two
cells, added as files in a temporary directory, run without a change to
the harness."""

import hashlib
import json
import os
import random
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark_contract import SERVED_CELL, added_tree  # noqa: E402
from test_benchmark_rehearse_train import REPO, TOY, rehearse  # noqa: E402

sys.path.insert(0, REPO)

from benchmark.harness import served_check, spec, traffic  # noqa: E402

BENCHMARK = os.path.join(REPO, "BENCHMARK.json")
CANDIDATES = os.path.join(REPO, "benchmark", "candidates.json")


def _served(path):
    with open(path) as f:
        return [(path, w["name"]) for w in json.load(f)["workloads"]
                if w["name"].startswith("serve_")]


# the served cells of the benchmark, the one kept ready beside it, and
# the one the rehearsed PR adds (test_benchmark_contract.py `added_tree`)
SERVED = (_served(BENCHMARK) + _served(CANDIDATES)
          + [("rehearsed", SERVED_CELL)])

TOY_DIR = os.path.join(REPO, "tests", "benchmark", "toy")
ADDED_DIR = os.path.join(REPO, "tests", "benchmark", "added")


def _harness_digest():
    """One hash over every file under benchmark/ (caches apart)."""
    h = hashlib.sha256()
    for folder, dirs, files in sorted(os.walk(os.path.join(REPO,
                                                           "benchmark"))):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            with open(os.path.join(folder, name), "rb") as f:
                h.update(name.encode() + f.read())
    return h.hexdigest()


def test_open_loop_serving_cell_end_to_end():
    line = rehearse("toy_instruct", trace=0, seconds=4)
    assert set(line["metrics"]) == {"request_ms_p50", "request_ms_p95",
                                    "setup_s"}
    assert line["attempted"] >= 200          # a p95 needs them
    m = line["metrics"]
    assert 0 < m["request_ms_p50"]["value"] <= m["request_ms_p95"]["value"]


def test_open_loop_serving_cell_traced_reads_the_engines_journal():
    line = rehearse("toy_instruct", trace=1, seconds=4)
    assert {"engine_ttft_ms_p50.instruct", "engine_tpot_ms_p50",
            "gen_lateness_ms_max"} <= set(line["metrics"])
    assert "device_idle_pct.instruct" not in line["metrics"]
    # the generator kept its schedule on this host
    assert line["metrics"]["gen_lateness_ms_max"]["value"] < 100


# the timed path broken underneath, in the child that serves: every token
# altered where it is produced (prefill's first token and the decode
# step's). The test puts this on the child's path; no option of the
# harness or of the program switches it on.
ALTERED_TOKEN = '''
import sys
if any("serve_child" in a for a in sys.orig_argv):
    import jax.numpy as jnp
    import megatron_tpu.inference.paging.engine as engine

    sample = engine.sample_logits_batched

    def altered(logits, *args, **kwargs):
        toks = sample(logits, *args, **kwargs)
        return jnp.where(toks + 1 < logits.shape[-1] - 1, toks + 1, 1)

    engine.sample_logits_batched = altered
'''


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path):
    """The harness's look for a chip skipped (--rehearse), the rest of a
    run driven as ever: the served tokens are one id off the model's, so
    the reference finds its best token's logit far above each, and the
    line says `correct` false with the number beside its limit."""
    (tmp_path / "sitecustomize.py").write_text(ALTERED_TOKEN)
    env = dict(os.environ, PYTHONPATH=str(tmp_path) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--spec", TOY, "--workload", "toy_instruct", "--seed", "11",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    gap = line["compared"]["logit_gap"]
    assert gap["value"] > 10 * gap["at_most"]
    assert line["correct"] is False
    assert "under the reference's best" in line["problems"][0]
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "benchmark: compared logit_gap value")


def _toy_sequences(reference, weights, config, lowp, n=6, prompt=24,
                   served=40):
    """Greedy continuations of seeded prompts under `logits(lowp=)`: what
    a program of that precision would serve."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    step = jax.jit(lambda w, t: reference.logits(w, t, config, lowp=lowp))
    out = []
    for req in traffic.make_requests(
            {"prompt_tokens": {"dist": "fixed", "value": prompt},
             "new_tokens": {"dist": "fixed", "value": served}},
            config["vocab_size"], 5, n):
        tokens = np.zeros(prompt + served, np.int32)
        tokens[:prompt] = req["prompt"]
        for i in range(prompt, prompt + served):
            tokens[i] = int(jnp.argmax(step(weights, jnp.asarray(tokens))
                                       [i - 1]))
        out.append({"tokens": tokens.tolist(), "prompt_tokens": prompt})
    return out


def test_the_control_one_precision_lower_is_not_correct():
    """The control at a size a test can hold: the reference with fp8
    operands put in the program's place serves tokens whose logits lie
    under the reference's best by more than the bfloat16 program's do,
    and `decide` fails it at a limit that passes the other. (The limits
    of the cells are set from chip readings at their own sizes: PERF.md.)"""
    import jax.numpy as jnp

    cell = spec.Cell(TOY, "toy_instruct")
    reference = spec.load_module(cell.reference_path())
    weights = reference.make_weights(cell.config, 17)
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    worst = {}
    for name, lowp in (("program", bf16), ("control", reference.fp8)):
        sequences = _toy_sequences(reference, weights, cell.config, lowp)
        check = served_check.score(reference, weights, sequences,
                                   cell.config, control=True)
        worst[name] = max(r["served"]["widest"] for r in check["sequences"])
        # the control as the tool reads it, with no decoding: the token
        # the lower precision puts first at each position of these tokens
        assert max(r["control"]["widest"]
                   for r in check["sequences"]) > 0
        limit = 0.004
        problems, compared = served_check.decide(check, len(sequences),
                                                 limit)
        assert compared["logit_gap"] == {"value": worst[name],
                                         "at_most": limit}
        assert bool(problems) == (name == "control"), (name, worst)
        # and as tools/serve_control.py judges it: the control's reading
        # through the same comparison comes out as not correct
        said, compared = served_check.decide(check, len(sequences), limit,
                                             who="control")
        assert said and compared["logit_gap"]["value"] > limit
    assert worst["control"] >= 3 * worst["program"]


@pytest.mark.parametrize("spec_path, cell_name", SERVED,
                         ids=lambda v: os.path.basename(v))
def test_the_plan_of_a_served_cell_of_the_benchmark(spec_path, cell_name,
                                                    tmp_path):
    """The real cells' plan, read from BENCHMARK.json, as far as the CPU
    can take it: the server's flags are the deployment's, the window's
    requests and due times come from the seed alone (a seed over 2**31
    too), the open loop offers enough requests for its 95th percentile,
    every request fits the engine, and the sample `correct` scores holds
    the longest request. The rehearsed PR's served cell is held to all
    of it, the two facts of an accepted mix's size too: its mix carries
    them inside the 256 positions the toy serves."""
    from benchmark.harness import serve_driver, stats

    if spec_path == "rehearsed":
        spec_path = added_tree(tmp_path / "added_pr")
    cell = spec.Cell(spec_path, cell_name)
    seconds = cell.spec["run_seconds"]
    plan = serve_driver.plan_server(cell, 2**31 + 7, False, False,
                                    str(tmp_path), 1234)
    flags = cell.config["program"]["serve"]["flags"]
    assert plan["argv"][-len(flags):] == flags and plan["seed"] == 2**31 + 7
    assert plan["control"] is False and not plan["journal"]
    mix, vocab = cell.traffic, cell.config["vocab_size"]
    limit = int(flags[flags.index("--serve_max_seq_len") + 1])
    if mix["driver"] == "serve_open":
        lead = mix["lead_s"]
        segments = [(0.0, lead), (lead, lead + seconds)]
        dues = traffic.arrivals(mix["rate_rps"], segments, 2**31 + 7)
        assert dues == traffic.arrivals(mix["rate_rps"], segments, 2**31 + 7)
        in_window = sum(d >= lead for d in dues)
        assert in_window == round(mix["rate_rps"] * seconds)
        stats.percentile(list(range(in_window)), 95)  # enough beyond it
        requests = traffic.make_requests(mix, vocab, 2**31 + 7, len(dues))
        # every answer of the window under the cap is of a length of its
        # own: no statistic of the window sits on the edge of a group
        news = traffic.draw_lengths(random.Random(1), mix["new_tokens"],
                                    in_window, mix["stratify"])
        under = [n for n in news if n < mix["new_tokens"]["max"]]
        assert len(set(under)) == len(under) >= 0.95 * in_window
    else:
        requests = traffic.make_requests(mix, vocab, 2**31 + 7,
                                         64 * mix["clients"])
    assert requests == traffic.make_requests(mix, vocab, 2**31 + 7,
                                             len(requests))
    assert requests != traffic.make_requests(mix, vocab, 7, len(requests))
    lengths = [len(r["prompt"]) + r["new_tokens"] for r in requests]
    assert max(lengths) <= limit
    assert all(0 < t < vocab - 1 for r in requests for t in r["prompt"])
    records = [{"ok": True, "due_s": float(i), "tokens": r["prompt"]
                + [1] * r["new_tokens"]} for i, r in enumerate(requests)]
    sampled = served_check.sample(records, 2**31 + 7,
                                  mix["check"]["requests"])
    assert len(sampled) == mix["check"]["requests"]
    assert len(sampled[0]["tokens"]) == max(lengths)
    assert sampled == served_check.sample(records, 2**31 + 7,
                                          mix["check"]["requests"])
    assert 0 < mix["check"]["logit_gap_tolerance"] < 1


def _added_spec(tmp_path):
    """The toy benchmark plus what a later PR would add, all of it new
    files (tests/benchmark/added) and new entries: a configuration of
    another block type with the reference it names, two traffic mixes, a
    per-layer metric with its reader, and two cells."""
    root = tmp_path / "added"
    shutil.copytree(TOY_DIR, root)
    shutil.copytree(ADDED_DIR, root, dirs_exist_ok=True)
    with open(root / "spec.json") as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-falcon", "source": "none",
                            "file": "toy-falcon.json", "reduced": [],
                            "why": "added"})
    spec["workloads"] += [
        {"name": "added_serve", "config": "toy-falcon",
         "traffic": "added_serve", "chips": 1, "why": "added"},
        {"name": "added_train", "config": "toy-falcon",
         "traffic": "added_train", "chips": 1, "why": "added"}]
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("added_serve")
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("added_train")
    spec["per_layer"] += [
        {"name": "requests_done_count", "unit": "requests",
         "better": "higher", "source": "host_clock", "layer": "generator",
         "moves": "serve_tokens_per_s", "workloads": ["added_serve"]},
        {"name": "engine_ttft_ms_p50.added", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "engine",
         "moves": "serve_tokens_per_s", "workloads": ["added_serve"]}]
    with open(root / "spec.json", "w") as f:
        json.dump(spec, f)
    return str(root / "spec.json")


def _child_log(cell):
    with open(os.path.join(REPO, "runs", "benchmark", cell,
                           "child.log")) as f:
        return f.read()


def test_a_cell_of_another_block_type_added_as_files_is_served(tmp_path):
    """No file of the harness knows the Falcon block: the server runs it
    from the flags its reference file gives, and `correct` holds the
    replies to that file's forward pass."""
    before = _harness_digest()
    line = rehearse("added_serve", trace=1, seconds=5,
                    spec=_added_spec(tmp_path))
    assert set(line["metrics"]) == {"requests_done_count",
                                    "engine_ttft_ms_p50.added"}
    assert line["metrics"]["requests_done_count"] == {
        "value": float(line["attempted"]), "unit": "requests"}
    assert "paged KV" in _child_log("added_serve")
    with open(os.path.join(REPO, "runs", "benchmark", "added_serve",
                           "plan.json")) as f:
        plan = json.load(f)
    assert plan["reference"].endswith(
        os.path.join("added", "reference", "toyfalcon.py"))
    assert not plan["reference"].startswith(REPO)
    assert _harness_digest() == before


def test_a_cell_of_another_block_type_added_as_files_is_trained(tmp_path):
    line = rehearse("added_train", trace=0, seconds=3,
                    spec=_added_spec(tmp_path))
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    with open(os.path.join(REPO, "runs", "benchmark", "added_train",
                           "result.json")) as f:
        child = json.load(f)
    # the trainer built the Falcon block (tied head 512*64; a layer: two
    # LayerNorms 2*128, q and o 2*4096, k and v 2*2048, MLP 2*16384; the
    # last LayerNorm 128), and its first loss is the added reference's on
    # the same batch
    assert "params: 123,520" in _child_log("added_train")
    assert abs(child["steps"][0]["loss"]
               - child["reference_first_loss"]) < 0.02
    # a reference that says nothing of FLOPs: the run notes no MFU
    assert "train_flops_per_token" not in child


def test_a_configuration_that_names_no_reference_is_refused(tmp_path):
    spec_path = _added_spec(tmp_path)
    root = os.path.dirname(spec_path)
    with open(os.path.join(root, "toy-falcon.json")) as f:
        config = json.load(f)
    for name, said in (("nowhere", "no reference/nowhere.py"),
                       (None, "names no \"reference\"")):
        config["reference"] = name
        with open(os.path.join(root, "toy-falcon.json"), "w") as f:
            json.dump(config, f)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
             "--spec", spec_path, "--workload", "added_train", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--rehearse"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2 and proc.stdout == ""
        assert said in proc.stderr
