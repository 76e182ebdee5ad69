"""Unit tests of the benchmark's yardstick (benchmark/harness): the
arithmetic, the generator, the trace reduction, the readers and the
result line, none of which needs a device. (The files against the
contract: test_benchmark_contract.py.)"""

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
from benchmark.harness.trace import (  # noqa: E402
    named, names, reduce, xplane,
)
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


# --- the training corpus ----------------------------------------------------

CORPUS = {"tokens": 20000, "cycle": 64, "doc_tokens_median": 100,
          "doc_tokens_sigma": 1.0, "doc_tokens_min": 8,
          "doc_tokens_max": 1024}
# sha256 of the .bin that build_corpus(CORPUS, vocabulary 512, seed
# 2500000037) wrote on PR 37's parent tree (bd0215a), before the mix's
# "reserved_ids" existed: 20,301 uint16 tokens
CORPUS_AT_PARENT = ("91fe36c32cfd7290eb3f8890096482cd"
                    "2a7ca7083530a92849cf98fda638d67a")


@pytest.mark.parametrize("reserved", [None, 0, 2])
def test_corpus_is_the_parents_bit_for_bit_unless_ids_are_reserved(
        reserved, tmp_path):
    """A mix that reserves no ids (the key absent, or 0) draws its cycle
    from every id under the end-of-document id by the call the parent
    made: the accepted cells' corpora do not move. One that reserves 2
    never emits the two ids just under the end-of-document id, which are
    then a configuration's own (a mask token)."""
    import numpy as np

    from benchmark.harness.train_child import build_corpus

    mix = dict(CORPUS) if reserved is None else dict(
        CORPUS, reserved_ids=reserved)
    prefix = str(tmp_path / "corpus")
    written = build_corpus(prefix, mix, 512, 2500000037)
    with open(prefix + ".bin", "rb") as f:
        data = f.read()
    ids = np.frombuffer(data, np.uint16)
    assert written == len(ids) and ids.max() == 511
    assert len(set(ids.tolist())) == 64 + 1
    same = hashlib.sha256(data).hexdigest() == CORPUS_AT_PARENT
    if not reserved:
        assert same and written == 20301
    else:
        assert not same and not {509, 510} & set(ids.tolist())


def test_reserved_ids_occur_in_no_seeds_corpus_and_unreserved_they_do(
        tmp_path):
    """Over 24 seeds: with two ids reserved neither ever occurs; with
    none reserved some seed's cycle holds one (so the case above can
    fail)."""
    import numpy as np

    from benchmark.harness.train_child import build_corpus

    small = dict(CORPUS, tokens=2000)
    seen = {0: set(), 2: set()}
    for seed in range(2500000100, 2500000124):
        for reserved in seen:
            prefix = str(tmp_path / f"c{seed}_{reserved}")
            build_corpus(prefix, dict(small, reserved_ids=reserved), 512,
                         seed)
            seen[reserved] |= set(np.fromfile(prefix + ".bin",
                                              np.uint16).tolist())
    assert not seen[2] & {509, 510} and 511 in seen[2]
    assert seen[0] & {509, 510}
    assert max(seen[2] - {511}) == 508


# --- the trace reduction ----------------------------------------------------

def _ev(name, start, dur, **stats_):
    return xplane.Event(name, start, dur, stats_)


PALLAS = ('%pallas.3 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), '
          'custom_call_target="tpu_custom_call"')
ALL_REDUCE = "%all-reduce.4 = f32[4096]{0} all-reduce(f32[4096]{0} %g)"


def _made_up_plane():
    ops = [
        _ev("while.1", 0, 1000),
        _ev("fusion.2", 100, 300),          # inside the while
        _ev(PALLAS, 400, 200),              # inside the while
        _ev(ALL_REDUCE, 1200, 300),         # alone on the device: exposed
        _ev("fusion.5", 2000, 100),
    ]
    return xplane.Plane("/device:TPU:0", [
        xplane.Line(reduce.OP_LINE, ops),
        xplane.Line(reduce.MODULE_LINE, [_ev("jit_step(1)", 0, 1500),
                                         _ev("jit_other(2)", 1990, 200)]),
    ], {})


def test_self_time_busy_union_and_exposed_collectives_on_made_up_events():
    got = reduce.reduce_device(_made_up_plane())
    assert got["busy_ps"] == 1000 + 300 + 100
    # a program that names nothing: everything under `other`, every gap
    # under the program that ends it
    assert got["by_name"]["other: while.1"] == 500     # 1000 - 300 - 200
    assert got["by_name"]["other: tpu_custom_call bf16[8,128]"] == 200
    assert got["by_name"]["other: all-reduce f32[4096]"] == 300
    assert got["collective_ps"] == got["collective_exposed_ps"] == 300
    assert got["gaps"] == [("before jit_step", 200),
                           ("before jit_other", 500)]
    assert got["span"] == (0, 2100)
    # a collective under compute on another line is hidden
    assert reduce.total(reduce.subtract(
        reduce.merge([(0, 100)]), reduce.merge([(20, 60)]))) == 60
    # two runs of a program are its first and its last: none is whole
    assert got["runs"] == 0


def test_operations_are_labelled_by_region_and_kernel_and_gaps_by_host_span():
    tf_ops = {"fusion.2": "jit(step)/while/body/jvp(mlp)/dot_general:",
              "fusion.5": "jit(step)/transpose(jvp(mlp))/dot_general:",
              PALLAS: "jit(step)/while/body/attention/flash_fwd/flash_fwd/"
                      "pallas_call:",
              ALL_REDUCE: "jit(step)/optimizer/psum:"}
    # the loop's thread: a pass that holds the wait for data as the first
    # gap opens (at 1000) and nothing but itself as the second does (1500)
    spans = [_ev("train-pass", 50, 1900), _ev("batch-generator", 900, 250)]
    got = reduce.reduce_device(_made_up_plane(), tf_ops, spans)
    # the two mlp operations merge under one label: what they are, not
    # which instruction number the compiler gave them
    assert got["by_name"] == {
        "other: while.1": 500, "mlp: fusion.2": 300, "mlp: fusion.5": 100,
        "attention/flash_fwd: tpu_custom_call bf16[8,128]": 200,
        "optimizer: all-reduce f32[4096]": 300}
    assert got["gaps"] == [("batch-generator", 200), ("train-pass", 500)]
    # a gap that opens after the loop's last span falls to the program
    late = reduce.reduce_device(_made_up_plane(), tf_ops,
                                [_ev("train-pass", 50, 900)])
    assert late["gaps"] == [("before jit_step", 200),
                            ("before jit_other", 500)]
    text = ("%fusion.7 = (bf16[4096,28672]{1,0:T(8,128)(2,1)}, f32[8]{0}) "
            "fusion(bf16[4096,4096]{1,0} %p), kind=kOutput")
    assert names.op_label(text, "jit(s)/jvp(mlp)/mul:") == (
        "mlp: fusion bf16[4096,28672]")
    assert names.op_label(text.replace("fusion.7", "fusion.8"), "") == (
        "other: fusion bf16[4096,28672]")


def test_busy_time_is_each_devices_own_over_its_own_window():
    """Two devices that run the same 1000 ps of work 400 ps apart: over a
    window from the first operation of any to the last of any each would
    read 29 % idle; over its own window each is busy all the time."""
    def device(at):
        return reduce.reduce_device(xplane.Plane("/device:TPU:0", [
            xplane.Line(reduce.OP_LINE, [_ev("fusion.1", at, 600),
                                         _ev("fusion.2", at + 600, 400)]),
            xplane.Line(reduce.MODULE_LINE, [_ev("jit_step(1)", at, 1000)]),
        ], {}))

    got = reduce.summarize({0: device(0), 1: device(400)})
    assert got["devices"] == 2
    assert got["busy_s"] == got["window_s"] == pytest.approx(1000e-12)
    assert got["device_ops"] == [["other: fusion.1", pytest.approx(600e-12)],
                                 ["other: fusion.2", pytest.approx(400e-12)]]
    assert reduce.summarize({}) is None


def test_whole_runs_leave_out_the_runs_the_traces_edges_cut():
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
    assert reduce.reduce_device(plane)["runs"] == 3
    assert [m.start_ps for m in reduce.whole_runs(runs)] == [100, 200, 300]
    assert reduce.whole_runs([]) == []
    # by name, only what the whole runs hold counts (named.reduce_device)
    per_run = named.reduce_device(plane, {
        kernel: "jit(train_step)/mlp/grouped_matmul/pallas_call:"})
    assert per_run["kernels"]["grouped_matmul"]["ps"] == 3 * 40
    assert per_run["scopes"]["mlp"] == 3 * 40


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
    assert got["collective_exposed_worst_s"] <= got["window_s"]
    assert 1 <= len(got["device_ops"]) <= 10
    assert len(got["idle_gaps"]) <= 10
    labels = [n for n, _ in got["device_ops"]]
    assert len(set(labels)) == len(labels)
    assert all(isinstance(n, str) and s >= 0 for n, s in got["device_ops"])
    seconds = [s for _, s in got["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    # what each recording is known to hold (PERF.md section 5)
    if name.startswith("train_seq4k"):
        assert got["devices"] == 1
        # the recording keeps every run of the step (the first and the
        # last of them cut by the trace's edges) and the operations of the
        # first alone, so the whole runs hold no kernel here
        assert got["runs"] == 9
        assert got["collective_exposed_worst_s"] == 0
        assert "tpu_custom_call" in got["device_ops"][0][0]
        # PR 22's program named nothing and its loop had no spans
        assert got["device_ops"][1][0] == "other: fusion bf16[2,4096,28672]"
        assert got["idle_gaps"][0][0] == "before jit_train_step"
    elif name.startswith("train_tp2dp2"):
        assert got["devices"] == 4 and got["runs"] == 3
        assert 0 < got["collective_exposed_worst_s"] < got["window_s"]
    elif name.startswith("serve_longprompt"):
        # the prefill chunk runs no Pallas kernel and copies the page pool
        assert not any("tpu_custom_call" in n for n, _ in got["device_ops"])
        assert got["device_ops"][0][0] == "other: fusion bf16[8,8000,16,8,128]"
        assert got["idle_gaps"][0][0] == "before jit_chunk_step"
    elif name.startswith("named_"):
        # every operation under a region, every Pallas call by its kernel,
        # the gaps by what the loop was doing
        regions = named.REGIONS + (named.OTHER,)
        for label, _ in got["device_ops"]:
            where = label.split(":")[0].split("/")
            assert where[0] in regions, label
            assert ("tpu_custom_call" in label) == (len(where) == 2), label
        assert got["device_ops"][0][0].startswith("attention/flash_fwd: ")
        assert got["idle_gaps"][0][0] == "metrics-fetch"


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


def test_readers_compute_from_the_runs_records_and_return_nothing_on_nothing(
        tmp_path, monkeypatch):
    steps = [{"t": 1.0 + i, "ntokens": 4096, "step_ms": 170.0 + i,
              "data_wait_ms": 1.7, "loss": 3.0, "compiles": 0}
             for i in range(3)]
    trace = {"devices": 1, "window_s": 2.0, "busy_s": 1.5,
             "runs": 8, "collective_exposed_worst_s": 0.2, "device_ops": [],
             "idle_gaps": []}
    run = _fake_run("train_mistral7b_seq4k", steps=steps, trace=trace,
                    end_to_end={"train_tokens_per_s": lambda: 24000.0})
    read = lambda name: run.cell.reader(name)(run)  # noqa: E731
    assert read("train_step_ms_p50") == 171.0
    assert read("train_data_wait_pct") == pytest.approx(100 * 5.1 / 513)
    # what the step needs on a chip is the journal's own record of it:
    # arguments + temporaries + the outputs that reuse no argument's room
    journal = tmp_path / "events.jsonl"
    monkeypatch.setattr(named, "run_files",
                        lambda run: (str(tmp_path), str(journal)))
    assert read("step_hbm_gb") is None and read("step_temp_hbm_gb") is None
    journal.write_text(json.dumps(
        {"kind": "step_program", "argument_bytes": 9_000_000_000,
         "temp_bytes": 3_000_000_000, "output_bytes": 9_400_000_000,
         "alias_bytes": 8_900_000_000}) + "\n")
    assert read("step_hbm_gb") == 12.5
    assert read("step_temp_hbm_gb") == 3.0
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
                           "runs": 9,
                           "collective_exposed_worst_s": 0,
                           "device_ops": [["mlp: fusion bf16[8]", 1.0]],
                           "idle_gaps": [["metrics-fetch", 0.1]]})
    line = run_py.result_line(run, trace=False)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 42.0, "unit": "s"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 12_000_000_000}
    assert line["correct"] is True
    traced = run_py.result_line(run, trace=True)
    assert "setup_s" not in traced["metrics"]
    assert {"train_step_ms_p50", "train_data_wait_pct",
            "device_idle_pct.train"} <= set(traced["metrics"])
    assert "step_hbm_gb" not in traced["metrics"]   # nothing to read
    assert traced["device"]["busy_s"] == 1.9
    assert traced["device"]["window_s"] == 2.0
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(traced)
    # an end-to-end metric without a value makes the run incorrect
    run.end_to_end = {"train_tokens_per_s": lambda: stats.percentile([1], 95)}
    assert run_py.result_line(run, trace=False)["correct"] is False


def test_a_training_runs_check_gives_each_number_beside_its_limit():
    from benchmark.harness import train_driver

    mix = {"first_loss_tolerance": 0.005, "loss_must_fall_by": 1.0}
    result = {"steps": [{"loss": 11.25}] * 3, "reference_first_loss": 11.251}
    inside = [{"loss": 9.5}, {"loss": 9.0}]
    problems, compared = train_driver.check(
        result, {"train_iters": 99}, inside, mix)
    assert problems == []
    assert compared == {
        "first_loss_gap": {"value": pytest.approx(0.001), "at_most": 0.005},
        "loss_fall_in_window": {"value": 1.75, "at_least": 1.0}}
    # a reference a bf16 step away, a loss that does not fall: both said,
    # with the numbers that failed
    result["reference_first_loss"] = 11.35
    problems, compared = train_driver.check(
        result, {"train_iters": 99}, [{"loss": 10.5}], mix)
    assert len(problems) == 2
    assert compared["first_loss_gap"]["value"] == pytest.approx(0.1)
    assert compared["loss_fall_in_window"]["value"] == 0.75


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
