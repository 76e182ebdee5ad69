"""Unit tests of the benchmark's yardstick (benchmark/harness): the
arithmetic, the generator, the trace reduction and the contract of the
files, none of which needs a device."""

import asyncio
import hashlib
import json
import os
import random
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import (  # noqa: E402
    common, peaks, spec, stats, traffic,
)
from benchmark.harness.trace import reduce, xplane  # noqa: E402
from benchmark.reference import mistral as flops  # noqa: E402 - its counts

BENCHMARK = os.path.join(REPO, "BENCHMARK.json")
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
CPU_TRACE = os.path.join(REPO, "tests", "fixtures", "tiny_cpu.xplane.pb")
# the toy cells of the CPU rehearsals: they keep the serving drivers and
# readers under test while no serving cell is in BENCHMARK.json
TOY = os.path.join(REPO, "tests", "benchmark", "toy", "spec.json")

with open(BENCHMARK) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]


# --- percentiles ------------------------------------------------------------

@pytest.mark.parametrize("n, q, ok", [
    (199, 95, False),   # 9 samples beyond the rank: a maximum in disguise
    (200, 95, True),    # 10 beyond
    (20, 50, True),
    (19, 50, False),
    (12, 95, False),
])
def test_percentile_refuses_fewer_than_ten_samples_beyond(n, q, ok):
    values = list(range(1, n + 1))
    if ok:
        got = stats.percentile(values, q)
        assert sum(v > got for v in values) >= 10
        assert sum(v <= got for v in values) >= n * q / 100.0
    else:
        with pytest.raises(stats.TooFewSamples):
            stats.percentile(values, q)


def test_median_and_empty():
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4, 1, 2, 3]) == 2.5
    with pytest.raises(stats.TooFewSamples):
        stats.median([])


# --- FLOPs ------------------------------------------------------------------

def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_flops_per_token_against_a_hand_count_for_mistral_7b_d2():
    cfg = _config("mistral-7b-d2")
    # one layer: q 2*4096*4096, k and v 2*4096*1024 each, o 2*4096*4096,
    # gate+up 2*4096*28672, down 2*14336*4096
    matmuls = 33_554_432 + 2 * 8_388_608 + 33_554_432 \
        + 234_881_024 + 117_440_512
    # causal, window 4096 = sequence 4096: a query sees (S+1)/2 keys on
    # average; QK^T and PV are 2*128 each per key and head, 32 heads
    attention = 4 * 128 * 32 * 2048.5
    forward = 2 * (matmuls + attention) + 2 * 4096 * 32000
    assert forward == 1_201_684_480
    assert flops.forward_flops_per_token(cfg, 4096) == forward
    assert flops.train_flops_per_token(cfg, 4096) == 3 * forward
    assert flops.num_params(cfg) == 698_372_096
    # the window bites past 4096: keys per query stop growing
    assert flops.attended_keys_mean(8192, 4096) == pytest.approx(
        (4096 * 4097 / 2 + 4096 * 4096) / 8192)
    assert flops.attended_keys_mean(8192, None) == 4096.5


def test_served_config_sizes():
    cfg = _config("mistral-7b-d8-serve")
    assert flops.num_params(cfg) == 2_007_044_096
    assert flops.kv_bytes_per_token(cfg) == 32 * 1024


# --- peaks ------------------------------------------------------------------

def test_peaks_table_knows_the_v5e_and_refuses_the_unknown():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(peaks.UnknownDevice):
            peaks.peaks_for(kind)


# --- the generator ----------------------------------------------------------

def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def _digest(mix, seed):
    arrivals = traffic.arrivals(mix.get("rate_rps", 5.0),
                                [(0.0, 10.0), (10.0, 50.0)], seed)
    assert len(arrivals) == round(mix.get("rate_rps", 5.0) * 50)
    requests = traffic.make_requests(mix, 32000, seed, len(arrivals))
    blob = json.dumps([arrivals, requests]).encode()
    return hashlib.sha256(blob).hexdigest(), requests


@pytest.mark.parametrize("mix_name", ["instruct", "longprompt"])
def test_generator_is_byte_identical_for_one_seed(mix_name):
    mix = _mix(mix_name)
    a, requests = _digest(mix, 11)
    b, _ = _digest(mix, 11)
    c, _ = _digest(mix, 12)
    assert a == b and a != c
    lens = mix["prompt_tokens"]
    assert all(lens["min"] <= len(r["prompt"]) <= lens["max"]
               for r in requests)
    assert all(mix["new_tokens"]["min"] <= r["new_tokens"]
               <= mix["new_tokens"]["max"] for r in requests)
    assert all(0 < t < 31999 for r in requests for t in r["prompt"])


def test_stratified_lengths_hold_the_distribution_in_every_seed():
    spec_ = _mix("instruct")["new_tokens"]
    p95s, totals = [], []
    for seed in range(8):
        got = traffic.draw_lengths(random.Random(seed), spec_, 400, 40)
        p95s.append(sorted(got)[379])
        totals.append(sum(got))
    # without stratification these spread by several percent
    assert (max(p95s) - min(p95s)) / min(p95s) < 0.04
    assert (max(totals) - min(totals)) / min(totals) < 0.01
    assert sorted(traffic.draw_lengths(random.Random(0), spec_, 400, 40)
                  )[200] == pytest.approx(256, abs=8)


async def _stub_server(delay_s, new_tokens):
    """Answers PUT /api like the server: the prompt plus new tokens."""
    async def handle(reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        n = int(re.search(rb"Content-Length: (\d+)", head).group(1))
        body = json.loads(await reader.readexactly(n))
        await asyncio.sleep(delay_s)
        text = body["prompts"][0] + " 7" * new_tokens(body)
        payload = json.dumps({"text": [text]}).encode()
        writer.write(b"HTTP/1.0 200 OK\r\nContent-Length: %d\r\n\r\n%s"
                     % (len(payload), payload))
        await writer.drain()
        writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1]


def test_latency_is_timed_from_the_due_time_and_lateness_is_reported():
    async def go():
        server, port = await _stub_server(
            0.05, lambda body: body["tokens_to_generate"])
        async with server:
            import time

            t0 = time.monotonic()
            record = {}
            # due 0.3 s ago: a generator that ran late
            await traffic._one("127.0.0.1", port,
                               {"prompt": [5, 6, 7], "new_tokens": 4},
                               -0.3, t0, 5.0, record)
            return record

    r = asyncio.run(go())
    assert r["ok"] and r["new_tokens"] == 4 and r["prompt_tokens"] == 3
    assert 0.3 <= r["lateness_s"] < 0.4
    # the stall is charged to the request: >= 0.3 late + 0.05 served
    assert r["latency_s"] >= 0.35
    assert r["latency_s"] == pytest.approx(r["done_s"] - r["due_s"])


def test_open_loop_measures_the_requests_due_in_the_window_and_counts_failures():
    mix = {"rate_rps": 150.0, "stratify": 4, "lead_s": 0.3, "trail_s": 0.5,
           "prompt_tokens": {"dist": "uniform", "min": 2, "max": 6},
           "new_tokens": {"dist": "fixed", "value": 3}}

    async def go():
        # every reply is one token short: HTTP 200, and still a failure
        server, port = await _stub_server(
            0.01, lambda body: body["tokens_to_generate"] - 1)
        async with server:
            return await traffic.run_open_loop(
                "127.0.0.1", port, mix, 100, 3, 1.0, timeout=5.0)

    out = asyncio.run(go())
    lead, end = out["window"]
    assert (lead, end) == (0.3, 1.3)
    assert out["offered"] == len(out["records"]) == 150   # rate x window
    assert all(lead <= r["due_s"] < end for r in out["records"])
    assert not any(r["ok"] for r in out["records"])
    assert all(r["status"] == 200 and r["new_tokens"] == 2
               for r in out["records"])


def test_closed_loop_keeps_its_clients_busy_and_counts_completions_inside():
    mix = {"clients": 3, "lead_s": 0.2, "stratify": 3,
           "prompt_tokens": {"dist": "uniform", "min": 2, "max": 6},
           "new_tokens": {"dist": "fixed", "value": 2}}

    async def go():
        server, port = await _stub_server(
            0.05, lambda body: body["tokens_to_generate"])
        async with server:
            return await traffic.run_closed_loop(
                "127.0.0.1", port, mix, 100, 5, 1.0, timeout=5.0)

    out = asyncio.run(go())
    # 3 clients x 1 s / 0.05 s a request, less overheads
    assert 30 <= len(out["records"]) <= 60
    assert all(r["ok"] and 0.2 <= r["done_s"] < 1.2 for r in out["records"])


def test_unreachable_server_is_a_failed_request_not_an_exception():
    status, body = asyncio.run(traffic.http_request(
        "127.0.0.1", 1, "/api", b"{}", 2.0))
    assert status == 0 and body


# --- the trace reduction ----------------------------------------------------

def _ev(name, start, dur, **stats_):
    return xplane.Event(name, start, dur, stats_)


def test_self_time_busy_union_and_exposed_collectives_on_made_up_events():
    ops = [
        _ev("while.1", 0, 1000),
        _ev("fusion.2", 100, 300),          # inside the while
        _ev('%pallas.3 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), '
            'custom_call_target="tpu_custom_call"', 400, 200),  # in the while
        _ev("%all-reduce.4 = f32[4096]{0} all-reduce(f32[4096]{0} %g)",
            1200, 300),                     # alone on the device: exposed
        _ev("fusion.5", 2000, 100),
    ]
    plane = xplane.Plane("/device:TPU:0", [
        xplane.Line(reduce.OP_LINE, ops),
        xplane.Line(reduce.MODULE_LINE, [_ev("jit_step(1)", 0, 1500),
                                         _ev("jit_other(2)", 1990, 200)]),
    ], {})
    got = reduce.reduce_device(plane)
    assert got["busy_ps"] == 1000 + 300 + 100
    assert got["by_name"]["while.1"] == 500     # 1000 - 300 - 200
    assert got["by_name"]["pallas.3 bf16[8,128] custom-call "
                          "tpu_custom_call"] == 200
    assert got["by_name"]["all-reduce.4 f32[4096] all-reduce"] == 300
    assert got["kernel_ps"] == 200
    assert got["collective_ps"] == got["collective_exposed_ps"] == 300
    assert got["gaps"] == [("before jit_step", 200),
                           ("before jit_other", 500)]
    # a collective under compute on another line is hidden
    assert reduce.total(reduce.subtract(
        reduce.merge([(0, 100)]), reduce.merge([(20, 60)]))) == 60
    # two runs of a program are its first and its last: none is whole
    assert got["runs"] == 0 and got["kernel_in_runs_ps"] == 0


def test_kernel_time_per_step_counts_only_the_runs_the_trace_holds_whole():
    kernel = ('%k = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), '
              'custom_call_target="tpu_custom_call"')
    # a trace that starts inside one step and stops inside another: five
    # runs of the step, the first and the last cut; a kernel of 40 at
    # +10 in each whole run and of 15 in each cut one
    runs = [_ev("jit_train_step(7)", 0, 60)] + [
        _ev("jit_train_step(7)", 100 * i, 100) for i in (1, 2, 3)] + [
        _ev("jit_train_step(7)", 400, 30), _ev("jit_convert(9)", 90, 5)]
    ops = [_ev(kernel, 20, 15), _ev(kernel, 405, 15)] + [
        _ev("fusion.1", 100 * i, 100) for i in (1, 2, 3)] + [
        _ev(kernel, 100 * i + 10, 40) for i in (1, 2, 3)]
    plane = xplane.Plane("/device:TPU:0", [
        xplane.Line(reduce.OP_LINE, ops),
        xplane.Line(reduce.MODULE_LINE, runs)], {})
    got = reduce.reduce_device(plane)
    assert got["kernel_ps"] == 2 * 15 + 3 * 40
    assert got["runs"] == 3 and got["kernel_in_runs_ps"] == 3 * 40
    assert [m.start_ps for m in reduce.whole_runs(runs)] == [100, 200, 300]
    assert reduce.whole_runs([]) == []


def test_decoder_reads_the_recorded_cpu_trace_and_finds_no_device_in_it():
    planes = xplane.load_planes(CPU_TRACE)
    assert [p.name for p in planes] == [
        "/host:metadata", "/host:CPU", "Task Environment"]
    host = planes[1]
    assert [len(ln.events) for ln in host.lines] == [17, 20, 20]
    steps = [ev for ev in host.lines[0].events
             if ev.name == "PjitFunction(fixture_step)"]
    assert steps and all(ev.duration_ps > 0 for ev in steps)
    assert "profile_start_time" in planes[2].stats
    # planes and lines that are not asked for are not decoded
    assert xplane.load_planes(CPU_TRACE, lambda n: n == "/host:CPU",
                              lambda n: n == "python")[0].lines[1].events == []
    # a traced run without a device plane has nothing to report
    assert reduce.reduce_trace(CPU_TRACE) is None


TPU_TRACES = sorted(f for f in os.listdir(FIXTURES)
                    if f.endswith(".xplane.pb")) if os.path.isdir(FIXTURES) else []


@pytest.mark.parametrize("name", TPU_TRACES or ["none recorded"])
def test_reduction_of_a_recorded_tpu_trace(name):
    if not TPU_TRACES:
        pytest.skip("no TPU trace is checked in")
    got = reduce.reduce_trace(os.path.join(FIXTURES, name))
    assert got is not None and got["devices"] >= 1
    assert 0 < got["busy_s"] <= got["window_s"]
    assert 0 <= got["kernel_s"] <= got["busy_s"]
    assert got["collective_exposed_worst_s"] <= got["window_s"]
    assert 1 <= len(got["device_ops"]) <= 10
    assert len(got["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in got["device_ops"])
    seconds = [s for _, s in got["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    # what each recording is known to hold (PERF.md section 5)
    if name.startswith("train_seq4k"):
        assert got["devices"] == 1 and got["kernel_s"] > 0
        # the recording keeps every run of the step (the first and the
        # last of them cut by the trace's edges) and the operations of the
        # first alone, so the whole runs hold no kernel here
        assert got["runs"] == 9 and got["kernel_s_per_run"] == 0
        assert got["collective_exposed_worst_s"] == 0
        assert "tpu_custom_call" in got["device_ops"][0][0]
        assert got["idle_gaps"][0][0] == "before jit_train_step"
    elif name.startswith("train_tp2dp2"):
        assert got["devices"] == 4 and got["runs"] == 3
        assert 0 < got["collective_exposed_worst_s"] < got["window_s"]
    elif name.startswith("serve_longprompt"):
        # the prefill chunk runs no Pallas kernel and copies the page pool
        assert got["kernel_s"] == 0
        assert "bf16[8,8000,16,8,128]" in got["device_ops"][0][0]
        assert got["idle_gaps"][0][0] == "before jit_chunk_step"


# --- the files, against the contract ----------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_has_the_contracts_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(BENCHMARK) < 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in SPEC["configs"]]
             + [w["traffic"] for w in SPEC["workloads"]])
    assert all(NAME.match(n) for n in names), names
    for group in (metrics, SPEC["workloads"], SPEC["configs"]):
        assert len({x["name"] for x in group}) == len(group)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(len(CELLS) // 4, 1)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    command = " ".join(SPEC["command"])
    assert ".." not in command and not any(
        word.startswith("/") for word in SPEC["command"])
    # the full check fits its budget with all 24 cells the contract allows
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs_keep_the_published_widths():
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 32000, "sliding_window": 4096,
                 "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
                 "max_position_embeddings": 32768}
    listed = {c["name"]: c for c in SPEC["configs"]}
    # the served configuration is kept ready for the cells PERF.md names
    for name in ("mistral-7b-d2", "mistral-7b-d8-serve"):
        cfg = _config(name)
        assert {k: cfg[k] for k in published} == published, name
        assert list(cfg["reduced"]) == ["num_hidden_layers"]
        if name in listed:
            assert cfg["source"] == listed[name]["source"]
            assert listed[name]["reduced"] == list(cfg["reduced"])
    assert "mistral-7b-d2" in listed


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_finds_its_files_and_reports_what_the_contract_asks(cell_name):
    cell = spec.Cell(BENCHMARK, cell_name)
    assert cell.traffic["driver"].split("_")[0] in ("train", "serve")
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert callable(cell.reader(m["name"]))
    # the architecture is a file found by the configuration's name for it
    assert cell.reference_path() == os.path.join(
        REPO, "benchmark", "reference", "mistral.py")
    if cell.traffic["driver"] == "train":
        flags = spec.load_module(cell.reference_path()).program_flags(
            cell.config, cell.traffic["seq_length"])
        assert flags[flags.index("--num_layers") + 1] == "2"
        assert "--no_tie_embed_logits" in flags


CANDIDATES = os.path.join(REPO, "benchmark", "candidates.json")


@pytest.mark.parametrize("cell_name", ["serve_mistral7b_instruct",
                                       "serve_mistral7b_longprompt"])
def test_serving_cells_kept_ready_find_their_files(cell_name):
    cell = spec.Cell(CANDIDATES, cell_name)
    assert cell.traffic["driver"] in ("serve_open", "serve_closed")
    assert cell.config["num_hidden_layers"] == 8
    assert {"setup_s"} < {m["name"] for m in cell.end_to_end()}
    for m in cell.per_layer():
        assert callable(cell.reader(m["name"]))
    flags = cell.config["program"]["serve"]["flags"]
    assert "--serve_kv_paging" in flags
    # the longest request of the mix fits the engine's sequence limit
    longest = (cell.traffic["prompt_tokens"]["max"]
               + cell.traffic["new_tokens"]["max"])
    assert longest <= int(flags[flags.index("--serve_max_seq_len") + 1])


def test_every_reader_file_is_named_by_some_metric():
    with open(TOY) as f:
        toy = json.load(f)
    stems = {m["name"].split(".")[0]
             for m in SPEC["per_layer"] + toy["per_layer"]}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(
        REPO, "benchmark", "layer_metrics")) if f.endswith(".py")}
    assert stems == on_disk


# --- readers and the result line, on a made-up run --------------------------

def _fake_run(cell_name, spec_path=BENCHMARK, **fields):
    cell = spec.Cell(spec_path, cell_name)
    base = dict(cell=cell, seconds=10.0,
                device={"platform": "tpu", "kind": "TPU v5 lite",
                        "count": cell.chips},
                memory_peak_bytes=12_000_000_000, setup_s=42.0,
                end_to_end={}, attempted=3, failed=0, problems=[],
                peaks=peaks.peaks_for("TPU v5 lite"))
    base.update(fields)
    return common.Run(**base)


def test_readers_compute_from_the_runs_records_and_return_nothing_on_nothing():
    steps = [{"t": 1.0 + i, "ntokens": 4096, "step_ms": 170.0 + i,
              "data_wait_ms": 1.7, "loss": 3.0, "compiles": 0}
             for i in range(3)]
    trace = {"devices": 1, "window_s": 2.0, "busy_s": 1.5, "kernel_s": 0.3,
             "runs": 8, "kernel_s_per_run": 0.044,
             "collective_exposed_worst_s": 0.2, "device_ops": [],
             "idle_gaps": []}
    run = _fake_run("train_mistral7b_seq4k", steps=steps, trace=trace,
                    step_memory_bytes={"arguments": 9_000_000_000,
                                       "temporaries": 3_000_000_000,
                                       "outputs_not_aliased": 500_000_000},
                    end_to_end={"train_tokens_per_s": lambda: 24000.0})
    read = lambda name: run.cell.reader(name)(run)  # noqa: E731
    assert read("train_step_ms_p50") == 171.0
    assert read("train_data_wait_pct") == pytest.approx(100 * 5.1 / 513)
    assert read("step_hbm_gb") == 12.5
    assert read("kernel_ms_per_step") == pytest.approx(44.0)
    run.trace["kernel_s_per_run"] = None     # no whole run in the trace
    assert read("kernel_ms_per_step") is None
    assert read("device_idle_pct.train") == pytest.approx(25.0)
    assert read("collective_exposed_pct") is None      # one device
    run.trace["devices"] = 4
    assert read("collective_exposed_pct") == pytest.approx(10.0)
    empty = _fake_run("toy_instruct", TOY)
    for m in empty.cell.spec["per_layer"]:
        assert empty.cell.reader(m["name"])(empty) is None, m["name"]
    empty.engine_requests = [{"ttft_s": 0.2, "tpot_s": 0.011},
                             {"ttft_s": 0.4}, {"ttft_s": 0.3, "tpot_s": 0.013}]
    assert empty.cell.reader("engine_ttft_ms_p50.instruct")(empty) == 300.0
    assert empty.cell.reader("engine_tpot_ms_p50")(empty) == 12.0


def _run_py():
    path = os.path.join(REPO, "benchmark", "run.py")
    import importlib.util

    s = importlib.util.spec_from_file_location("benchmark_run_py", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def test_result_line_holds_the_contracts_keys_and_names_the_device():
    run_py = _run_py()
    steps = [{"t": 1.0, "ntokens": 4096, "step_ms": 170.0,
              "data_wait_ms": 1.0, "loss": 3.0, "compiles": 0}]
    run = _fake_run("train_mistral7b_seq4k", steps=steps,
                    end_to_end={"train_tokens_per_s": lambda: 24000.0},
                    trace={"devices": 1, "window_s": 2.0, "busy_s": 1.9,
                           "kernel_s": 0.2, "runs": 9,
                           "kernel_s_per_run": 0.02,
                           "collective_exposed_worst_s": 0,
                           "device_ops": [["fusion.1", 1.0]],
                           "idle_gaps": [["before jit_train_step", 0.1]]})
    line = run_py.result_line(run, trace=False)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 42.0, "unit": "s"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 12_000_000_000}
    assert line["correct"] is True
    traced = run_py.result_line(run, trace=True)
    assert "setup_s" not in traced["metrics"]
    assert {"train_step_ms_p50", "device_idle_pct.train",
            "kernel_ms_per_step"} <= set(traced["metrics"])
    assert "step_hbm_gb" not in traced["metrics"]   # nothing to read
    assert traced["device"]["busy_s"] == 1.9
    assert traced["device"]["window_s"] == 2.0
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(traced)
    # an end-to-end metric without a value makes the run incorrect
    run.end_to_end = {"train_tokens_per_s": lambda: stats.percentile([1], 95)}
    assert run_py.result_line(run, trace=False)["correct"] is False


# --- what the command refuses -----------------------------------------------

def _benchmark_only_copy(tmp_path):
    """BENCHMARK.json and the files under `paths`, and nothing else."""
    import shutil

    shutil.copy(BENCHMARK, tmp_path / "BENCHMARK.json")
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_no_result_where_only_the_benchmark_is(tmp_path):
    root = _benchmark_only_copy(tmp_path)
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "nothing to measure" in proc.stderr


def test_no_result_without_a_tpu():
    """Here JAX is held to the CPU: the child that would hold the chip
    says so and the command prints no line (only --rehearse runs, and its
    line names the platform it ran on)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "JAX reports" in proc.stderr
