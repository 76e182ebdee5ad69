"""training/timers.py: the loop's named span timers. Their clock
semantics are what the step journal and the log window read; since PR 23
each start/stop pair is also a profiler annotation, which must cost
nothing and raise nothing while no trace is being captured."""

import time

import pytest

from megatron_tpu.training.timers import Timers


def test_last_s_is_the_newest_span_and_survives_a_reset():
    timers = Timers(log_level=0)
    t = timers("phase", 0)
    t.start()
    time.sleep(0.01)
    t.stop()
    first = timers.last_s("phase")
    assert first >= 0.01
    t.start()
    t.stop()
    assert 0 <= timers.last_s("phase") < first
    assert timers.elapsed_ms(["phase"], reset=True)["phase"] >= 10.0
    # the log window's reset leaves the newest span for the step journal
    assert timers.last_s("phase") < first
    assert timers.elapsed_ms(["phase"])["phase"] == 0.0
    assert timers.last_s("never-started") == 0.0


def test_elapsed_of_a_running_timer_laps_the_clock_and_keeps_it_running():
    t = Timers()("running", 0)
    t.start()
    time.sleep(0.005)
    lap = t.elapsed(reset=True)
    assert lap >= 0.005
    # still running: a second start is the same error as ever, and the
    # next reading holds only what came after the lap
    with pytest.raises(RuntimeError, match="already started"):
        t.start()
    t.stop()
    assert 0 <= t.elapsed() < lap + 0.005


@pytest.mark.parametrize("misuse", ["double_start", "stop_unstarted"])
def test_misuse_raises(misuse):
    t = Timers()("x", 0)
    if misuse == "double_start":
        t.start()
        with pytest.raises(RuntimeError, match="already started"):
            t.start()
        t.stop()
    else:
        with pytest.raises(RuntimeError, match="not started"):
            t.stop()


def test_level_gate_and_record_and_log_string():
    timers = Timers(log_level=0)
    quiet = timers("detail", 1)          # above the level: a no-op
    quiet.start()
    quiet.stop()
    assert quiet.elapsed() == 0.0 and timers.last_s("detail") == 0.0
    timers.record("detail", 5.0, level=1)
    assert timers.last_s("detail") == 0.0
    timers.record("credited", 0.25)
    timers.record("credited", -1.0)      # a negative reading is dropped
    assert timers.last_s("credited") == 0.25
    assert timers.log_string(normalizer=2.0) == (
        "time (ms) | credited: 125.00")
    assert timers.log_string() == "time (ms) | credited: 0.00"


def test_a_pair_with_no_capture_running_is_cheap_and_silent():
    """No trace is being captured here: the annotation inside each pair
    is inert. A thousand pairs take milliseconds, not seconds."""
    t = Timers()("hot", 0)
    t0 = time.perf_counter()
    for _ in range(1000):
        t.start()
        t.stop()
    assert time.perf_counter() - t0 < 0.5
    assert t._span is None and t._count == 1000


def test_own_time_is_a_span_less_the_spans_inside_it():
    """What the serving engine's phases are booked by: nested pairs of one
    Timers sum to the pair that holds them."""
    timers = Timers()
    with timers("tick"):
        with timers("admit"):
            time.sleep(0.004)
            with timers("evict"):
                time.sleep(0.006)
        with timers("read"):
            time.sleep(0.003)
        with timers("read"):        # a second span of the same timer
            time.sleep(0.002)
    own = timers.own_s()
    assert set(own) == {"tick", "admit", "evict", "read"}
    assert own["evict"] >= 0.006 and own["read"] >= 0.005
    assert 0.004 <= own["admit"] < own["admit"] + own["evict"]
    assert own["admit"] == pytest.approx(
        timers("admit").elapsed(reset=False) - own["evict"])
    assert sum(own.values()) == pytest.approx(
        timers("tick").elapsed(reset=False))
    # a credited span is its own; a gated timer has none; nothing resets it
    timers.record("gap", 0.5)
    assert timers.own_s()["gap"] == 0.5
    assert Timers(log_level=0)("quiet", 1).own() == 0.0
    timers.elapsed_ms(reset=True)
    assert timers.own_s()["evict"] == own["evict"]


def test_a_span_takes_arguments_at_both_ends_and_may_be_a_step_marker():
    timers = Timers()
    t = timers("page-evict")
    t.start(asked=3)
    t.stop(freed=2)
    tick = timers("serve-tick")
    tick.start(step_num=7)
    assert type(tick._span).__name__ == "StepTraceAnnotation"
    tick.stop()
    with timers("quiet", 1) as quiet:      # gated: the same calls, no span
        quiet.start(asked=1)
        quiet.stop(freed=1)
    with pytest.raises(RuntimeError, match="already started"):
        with timers("x"):
            timers("x").start()
    assert timers("x")._start is None      # the context closed it
