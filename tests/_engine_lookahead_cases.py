"""Shared scenarios for the engine's one-tick-ahead loop (docs/serving.md
"Step loop"): the served tokens are the same tokens whichever order the
host reads them in.

Used by tests/test_serving_engine.py (against `generate_tokens`),
tests/test_paging.py and tests/test_jamba.py (the engine on a typed stack
with state rows, against the same engine driven tick by tick). No test
here: pytest collects `test_*.py` alone.
"""

import dataclasses
from typing import Callable, List, Optional

import numpy as np

from megatron_tpu.inference.engine import Request

#: (prompt length, new tokens): every pair sums to 24, so that a one-shot
#: oracle compiles once a sampling mode; four prompts cross an 8-token chunk
SHAPES = [(4, 20), (9, 15), (14, 10), (6, 18), (12, 12), (17, 7)]
#: requests that end by eod in mid-stream: at the oracle's token of this index
EOD_AT = {1: 5, 3: 7}
SAMPLED = dict(temperature=0.8, top_k=8, top_p=0.9)


@dataclasses.dataclass
class Want:
    """What one request must come back with."""
    generated: List[int]
    logprobs: List[float]
    prompt_logprobs: List[float]
    eod: Optional[int] = None


def drive_tick_by_tick(eng) -> None:
    """The old order: every tick is read before the next is dispatched."""
    while True:
        served = eng.step()
        eng._drain("test")
        if served == 0 and not eng._queue:
            return


def prompts(vocab: int, seed: int = 3) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, p).astype(np.int32) for p, _ in SHAPES]


def knobs(i: int, sampled: bool) -> dict:
    return dict(SAMPLED, seed=11 + i) if sampled else {}


def cut_at_eod(full: Want, index: int) -> Want:
    """`full` ended at its token of `index` taken as eod (its first
    occurrence, should it come earlier)."""
    eod = full.generated[index]
    n = full.generated.index(eod) + 1
    return Want(full.generated[:n], full.logprobs[:n], full.prompt_logprobs,
                eod=eod)


def expected(oracle: Callable[[np.ndarray, int, dict], Want], vocab: int,
             sampled: bool) -> List[Want]:
    """The oracle's answer to each request of the scenario; those of EOD_AT
    with one of their own tokens declared eod."""
    out = []
    for i, (prompt, (_, new)) in enumerate(zip(prompts(vocab), SHAPES)):
        want = oracle(prompt, new, knobs(i, sampled))
        out.append(cut_at_eod(want, EOD_AT[i]) if i in EOD_AT else want)
    return out


def submit_staggered(eng, wants: List[Want], vocab: int, sampled: bool,
                     **extra) -> List[Request]:
    """Admissions spread over a run: two at once, two after two ticks, the
    rest after three more; then to the end."""
    reqs = []
    for i, (prompt, (_, new)) in enumerate(zip(prompts(vocab), SHAPES)):
        reqs.append(eng.submit(Request(
            prompt=prompt, max_new_tokens=new, eod=wants[i].eod,
            **knobs(i, sampled), **extra)))
        if i in (1, 3):
            for _ in range(2 if i == 1 else 3):
                eng.step()
    eng.run_until_idle()
    return reqs


def assert_served(reqs: List[Request], wants: List[Want], eng,
                  drained: bool = False) -> None:
    assert not eng._inflight and eng.num_active == 0
    for i, (req, want) in enumerate(zip(reqs, wants)):
        assert req.done.is_set() and req.error is None, (i, req.error)
        assert req.generated == want.generated, i
        np.testing.assert_allclose(req.logprobs, want.logprobs,
                                   rtol=1e-5, atol=1e-5, err_msg=str(i))
        np.testing.assert_allclose(req.prompt_logprobs, want.prompt_logprobs,
                                   rtol=1e-5, atol=1e-5, err_msg=str(i))
    assert eng.stats["decode_recompiles"] == 0
    # a row that ended by eod in mid-stream ran one tick more, and that
    # tick's token reached nobody. Where the eod is a prompt's first token
    # the engine reads it a step after the tick that first served the row,
    # so two ticks ran
    dropped = 0
    for req, want in zip(reqs, wants):
        if want.eod is not None and len(req.generated) < req.max_new_tokens:
            dropped += 2 if len(req.generated) == 1 else 1
    if drained:
        # an eod read at a drain has no later tick to drop from
        assert eng.stats["tokens_dropped_after_eod"] <= dropped
    else:
        assert eng.stats["tokens_dropped_after_eod"] == dropped


def staggered_parity(eng, oracle, vocab: int, sampled: bool) -> List[Request]:
    wants = expected(oracle, vocab, sampled)
    reqs = submit_staggered(eng, wants, vocab, sampled)
    assert_served(reqs, wants, eng)
    # the loop ran ahead: most decode ticks were in the queue before the
    # one before them was read
    assert eng.stats["ticks_dispatched_ahead"] >= 0.7 * eng.stats["ticks"]
    return reqs


def one_shot(cfg, params) -> Callable[[np.ndarray, int, dict], Want]:
    """The oracle of a model `generate_tokens` carries: one request a call."""
    from megatron_tpu.inference.generation import generate_tokens

    def oracle(prompt, new, kw):
        p = len(prompt)
        out = generate_tokens(cfg, params, prompt[None], np.asarray([p]),
                              max_new_tokens=new,
                              **dict(dict(temperature=0.0), **kw))
        return Want([int(t) for t in out.tokens[0, p:]],
                    [float(x) for x in out.logprobs[0, p - 1:]],
                    [float(x) for x in out.logprobs[0, :p - 1]])

    return oracle


def served_alone_tick_by_tick(make) -> Callable[[np.ndarray, int, dict], Want]:
    """An oracle for a model `generate_tokens` cannot carry (state rows):
    the same engine class, one request at a time, every tick read before
    the next is dispatched."""
    eng = make()

    def oracle(prompt, new, kw):
        req = eng.submit(Request(prompt=prompt, max_new_tokens=new, **kw))
        drive_tick_by_tick(eng)
        assert req.error is None, req.error
        return Want(list(req.generated), list(req.logprobs),
                    list(req.prompt_logprobs))

    return oracle
