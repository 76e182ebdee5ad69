"""The serving engine: continuous batching over one shared page pool.

The one-shot path (generation.py) allocates a dense [B, L, S, H] cache per
call and serves one request at a time — decode utilization collapses to a
single sequence's matmul. This engine owns ONE long-lived pool of
fixed-size KV pages (ops/kv_store.py; optionally int8) shared by
`num_slots` sequences, and runs a step loop. Every tick it

  * admits queued requests into free slots: the prompt's span of pages is
    allocated (a young sequence holds the pages it has, not its worst
    case), and requests sharing a prompt prefix alias the same refcounted
    pages through the radix tree (paging/radix.py) and skip prefill for
    the shared span;
  * runs AT MOST ONE chunk of `prefill_chunk` prompt tokens
    (paging/scheduler.py), so one long prompt can never stall the batch;
  * grows each decoding slot one page at a time as its length crosses a
    page boundary. Under memory pressure it first evicts cache-only prefix
    pages (LRU), then preempts the youngest slot (LIFO, so later arrivals
    yield to earlier ones); a preempted request keeps its sampled tokens
    and PRNG chain (Request.resume_key) and resumes by teacher-forced
    recompute of prompt + generated, which is exact;
  * executes ONE batched single-token decode for all slots — one
    jit-compiled step (paging/engine.py builds it) reused across traffic,
    no recompiles after warmup: its shapes, the `[N, max_pages]` device
    page table included, never change.

Sequences of different ages coexist because the attention path masks each
slot to its own valid prefix (per-slot lengths; the paged flash-decode
kernel on TPU, ops/pallas/paged_flash_decode.py).

Per-request sampling params (temperature/top_k/top_p) are traced [N]
arrays, not static — heterogeneous traffic shares the same compiled step
(sampling.sample_logits_batched). Each request carries its own PRNG chain
keyed off its seed, so a request's tokens never depend on which other
slots happen to be active (the interleaved-traffic parity invariant;
tests/test_serving_engine.py).

Parity gate: a request decoded through the engine is token-identical to
generation.generate_tokens — greedy, sampled, int8, ragged, preempted
(tests/test_paging.py) — since masking a step to the valid prefix
contributes exact zeros to the softmax.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import statistics
import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.generation import GenerationOutput
from megatron_tpu.inference.paging import engine as steps
from megatron_tpu.inference.paging.pool import SCRATCH_PAGE, PagePool
from megatron_tpu.inference.paging.radix import RadixPrefixCache
from megatron_tpu.inference.paging.scheduler import (
    ChunkedPrefillQueue, PrefillTask,
)
from megatron_tpu.ops import kv_store, ssm
from megatron_tpu.telemetry import journal as _journal
from megatron_tpu.telemetry.metrics import MetricsRegistry, default_registry
from megatron_tpu.telemetry.tracing import capture
from megatron_tpu.training import resilience
from megatron_tpu.training.timers import Timers

#: jax's profiler session is process-global (one trace at a time), so
#: on-demand captures serialize here — a second /admin/profile while one
#: is running answers 409 instead of corrupting the live session
_PROFILE_LOCK = threading.Lock()


#: The loop's spans, each a pair of the engine's `Timers`
#: (training/timers.py): a `jax.profiler` annotation on the host plane of
#: whatever capture is open, and the accumulator behind
#: `engine_tick_phase_seconds_total{phase}`, `stats["tick_phase_s"]` and the
#: journal's `phase_s` (docs/observability.md "The names in a trace"). A
#: phase's seconds are its spans' OWN time, less the spans inside them, so
#: the phases of a tick sum to it: a read inside a drain inside `tick-pages`
#: is `read`'s. `tick-read` (_fetch) is the only one in which the loop waits
#: for the device; a span around a dispatch measures the dispatch.
TICK = "serve-tick"       # one step(); a step marker, step_num = its number
PRE = "tick-pre"          # _pre_tick: faults, staged weights, deadlines
ADMIT = "tick-admit"      # _admit: slots, page allocation, the radix match
PREFILL = "tick-prefill"  # a chunk's preparation and its dispatch
PAGES = "tick-pages"      # window release, pages under the decode span
PROPOSE = "tick-propose"  # the n-gram drafter's proposals
DECODE = "tick-decode"    # carry, live-block gauge, dispatch, _start_fetch
READ = "tick-read"        # _fetch: the wait for the device
APPLY = "tick-apply"      # tokens to requests, _retire, the journal
DRAIN = "tick-drain"      # _drain, in whichever phase needs true mirrors
EVICT = "page-evict"      # RadixPrefixCache.evict
PREEMPT = "page-preempt"  # _preempt_one
LOOP = "tick-loop"        # between two steps of a running loop (credited,
                          # no annotation: it is the gap between the ticks)
_PHASE_OF = {TICK: "other", PRE: "pre", ADMIT: "admit", PREFILL: "prefill",
             PAGES: "pages", PROPOSE: "propose", DECODE: "decode",
             READ: "read", APPLY: "apply", DRAIN: "drain", EVICT: "evict",
             PREEMPT: "preempt", LOOP: "loop"}

#: a tick is slow when it took longer than both of these: seconds, and a
#: multiple of the median of the last SLOW_TICK_HISTORY ticks
SLOW_TICK_S = 0.25
SLOW_TICK_OVER_MEDIAN = 8.0
SLOW_TICK_HISTORY = 256


class _GcWatch:
    """Seconds the collector paused the process, by `gc.callbacks` (a
    pause holds the interpreter lock, so it stops the loop whichever
    thread set it off). Registered only while a journal is set: the
    journal's `serve_slow_tick` is its one reader."""

    def __init__(self):
        self.seconds = 0.0
        self.watching = False
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0

    def watch(self, on: bool) -> None:
        if on and not self.watching:
            gc.callbacks.append(self)
        elif not on and self.watching:
            gc.callbacks.remove(self)
        self.watching = on


class EngineOverloadedError(RuntimeError):
    """The engine's admission queue is at max_queue: the request was
    rejected, not queued. HTTP serving maps this to 503 + Retry-After."""


class RequestTimeoutError(RuntimeError):
    """A request's deadline expired while it was queued or mid-decode.
    HTTP serving maps this to 504 Gateway Timeout; the fleet router treats
    it as non-retryable (the client's budget is spent either way)."""


@dataclasses.dataclass
class Request:
    """One sequence's lifecycle through the engine."""
    prompt: np.ndarray                 # [p] int32 token ids
    max_new_tokens: int
    temperature: float = 0.0           # 0 = greedy
    top_k: int = 0
    top_p: float = 0.0
    eod: Optional[int] = None
    seed: int = 0
    # relative deadline: seconds after submit() by which the request must
    # COMPLETE. A queued or mid-decode request past it fails with
    # timed_out=True (HTTP 504) — waiters on done.wait() are signalled in
    # bounded time instead of waiting on an abandoned request forever,
    # which also bounds the router's retry worst case. None = no deadline.
    deadline_s: Optional[float] = None
    # engine-filled
    generated: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    # per-request speculative-decoding knob: False pins this request to
    # one token per tick even on a speculating engine (its greedy output
    # is bit-identical either way; the knob exists for traffic classes
    # that want the lowest per-token latency variance). Ignored when the
    # engine was built without `speculative=`.
    spec: bool = True
    # preemption/resume: the PRNG chain state at
    # preemption, so a recompute-resumed request samples the exact
    # tokens it would have sampled without the preemption
    resume_key: Optional[np.ndarray] = None
    # queue-overload rejection marker (submit with max_queue exceeded)
    overloaded: bool = False
    # deadline-expiry marker (engine-set; error carries the detail)
    timed_out: bool = False
    # absolute monotonic deadline (engine-stamped at submit)
    _deadline: Optional[float] = None
    # teacher-forced logprobs of prompt[1:] from the admission prefill
    # (the one-shot path returns these too; generation.py:136-141)
    prompt_logprobs: List[float] = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    error: Optional[str] = None
    # the request's name in the journal (`serve_request`, the server's
    # `serve_reply`): the client's X-Request-Id, else the server's own;
    # `<id>/<k>` for the k-th prompt of a request that carries several
    id: Optional[str] = None
    # latency telemetry (monotonic clock), where the request changes
    # hands: submit(); the slot (the last assignment before the first
    # token: a request preempted mid-prefill waits again, one preempted
    # later keeps its stamps); its first token READ; _finish()
    submit_time: Optional[float] = None
    slot_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    chunks: int = 0          # prefill programs run for it, every life
    prefix_tokens: int = 0   # positions the radix cache saved it
    preemptions: int = 0

    @property
    def tokens(self) -> np.ndarray:
        """prompt + generated (eod included when emitted)."""
        return np.concatenate(
            [np.asarray(self.prompt, np.int32),
             np.asarray(self.generated, np.int32)])

    def _finish(self, error: Optional[str] = None):
        self.error = error
        if self.finish_time is None:
            self.finish_time = time.monotonic()
        self.done.set()


@dataclasses.dataclass
class _InFlight:
    """What one dispatched program still owes the host: the device arrays
    its tokens come back in, and the (slot, request) pairs they are for. A
    row is applied at the read only if the slot still holds that request."""
    rows: List[Tuple[int, Request]]
    out: Any                      # fetched whole, in one device_get
    step: int                     # the engine step that dispatched it
    t0: float
    ahead: bool = False           # in the queue before the last tick was read
    # a prompt's last chunk: its PrefillTask, and the pages held for the
    # radix tree until the prompt's log-probabilities are read
    task: Any = None
    pinned: Tuple[int, ...] = ()


# what the steps of a model that holds a share of its router's experts
# count on the device, in `_step_counts`' order: the key in `stats`, the
# counter on /metrics and its help
_MOE_COUNTS = (
    ("moe_held_rows", "engine_moe_held_rows_total",
     "those of engine_moe_rows_total sent to experts held on this chip"),
    ("moe_rows", "engine_moe_rows_total",
     "(row, choice) pairs the steps routed, over the expert layers (rows "
     "somebody reads x experts a token x expert layers; a model that holds "
     "a share of its router's experts)"),
    ("moe_experts_read", "engine_moe_experts_read_total",
     "held experts a decoding row reached, over the decode ticks and the "
     "expert layers (the router's count: what the experts' kernels may "
     "leave unread)"),
    ("moe_experts_offered", "engine_moe_experts_offered_total",
     "held experts there were for them (held x expert layers a tick)"),
)


class InferenceEngine:
    """Slot scheduler + paged KV pool + radix prefix cache, and the jitted
    chunk/decode steps over them.

    Not thread-safe for concurrent step() calls; submit() may be called
    from any thread (the HTTP handlers), step()/run_until_idle() from one
    driver thread (start() spawns it).
    """

    # the context-parallel ring's transport: the CP engine sets it before
    # this constructor runs, and the step builders route attention by it
    cp_comm = None

    def __init__(self, cfg: ModelConfig, params: Any, num_slots: int = 8,
                 max_seq_len: Optional[int] = None,
                 kv_cache_int8: bool = False,
                 page_size: int = 16, prefill_chunk: int = 32,
                 num_pages: Optional[int] = None,
                 vocab_size: Optional[int] = None, mesh=None,
                 want_logprobs: bool = True,
                 metrics: Optional[MetricsRegistry] = None,
                 flight_recorder=None,
                 force_donate: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 speculative=None,
                 compress_collectives: str = "none",
                 comm_policy=None,
                 comm_chunk: int = 32):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None: unbounded)")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if num_pages is not None and num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is scratch), got {num_pages}")
        # force_donate: override the backend-derived donation choice
        # (None = donate except on XLA:CPU). The jaxpr/donation auditor
        # sets True so CPU-traced audits check the TPU-shipped intent.
        self.force_donate = force_donate
        cfg.refuse_serving()
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_queue = max_queue
        self.page_size = int(page_size)
        self.prefill_chunk = int(prefill_chunk)  # validated by the queue
        self.num_pages = num_pages   # None: sized by _fresh_caches
        self.max_seq_len = self._round_seq_len(
            int(max_seq_len or cfg.seq_length))
        self.max_pages = self.max_seq_len // self.page_size
        if (cfg.position_embedding_type == "absolute"
                and self.max_seq_len > (cfg.max_position_embeddings or 0)):
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        self.kv_cache_int8 = kv_cache_int8
        if cfg.has_ssm:
            # a model with state-space layers (cfg.layer_pattern) carries
            # a recurrent state a sequence beside its keys and values
            # (self.state); every path that cannot hold it raises, by name
            if speculative is not None:
                raise NotImplementedError(
                    "speculative decoding over a model with state-space "
                    "layers: a rejected draft rolls the length back, and "
                    "the recurrent state has no rollback")
            if mesh is not None or self.cp_comm is not None:
                raise NotImplementedError(
                    "sharded serving (a tensor- or context-parallel mesh) "
                    "of a model with state-space layers: the state store "
                    "and the mixer are not sharded; serve it on one chip")
        # migration wire codec for FLOAT caches (fleet/migration.py):
        # "raw" ships native bytes (exact); "int8"/"fp8" quantize via
        # quant/primitives.py (smaller, NOT bit-exact — importers that
        # require token identity recompute-resume instead). int8 caches
        # always ship their own quantized pages + scales verbatim
        # ("int8-native", exact). Operators set this attribute directly.
        self.kv_wire = "raw"
        self.kv_wire_chunk = 32
        self.vocab_size = vocab_size
        self.mesh = mesh
        self.want_logprobs = want_logprobs
        # compressed TP collectives (quant/collectives.py,
        # --serve_compress_collectives): replace the forward's
        # tensor-parallel output reductions + logits gather with explicit
        # low-bit (int8/fp8) collectives. None when the flag is off or
        # the mesh's tensor axis is trivial (dense path unchanged). The
        # plan is STATIC at engine build — compiled into the steps,
        # zero traced args, zero recompiles.
        from megatron_tpu.quant.collectives import (
            forward_comm_bytes, make_tp_comm,
        )

        self.tp_comm = make_tp_comm(mesh, compress_collectives, cfg=cfg,
                                    policy=comm_policy, chunk=comm_chunk)
        if self.tp_comm is not None and speculative is not None:
            raise ValueError(
                "compress_collectives with speculative decoding is not "
                "supported (the spec step is not threaded through the "
                "explicit TP collectives) — drop one of the two")
        # static wire-byte prices for the telemetry counters: what one
        # decode tick (one [N, 1] forward) and one chunk (one [1, C]
        # forward) move in this mode, and what the dense path would have
        # moved (their ratio IS the live compression ratio)
        self._comm_tick_bytes = forward_comm_bytes(
            cfg, self.tp_comm, num_slots, 1)
        self._comm_chunk_bytes = forward_comm_bytes(
            cfg, self.tp_comm, 1, self.prefill_chunk)

        N = num_slots
        # committed placement for params as well as caches: random-init
        # params (tests, bench) are UNCOMMITTED jit outputs while
        # checkpoint-loaded and hot-reloaded params (update_params) are
        # committed device_puts — without this, the first weight swap on a
        # random-init engine would split the decode step's jit cache key
        # and pay one recompile (the smoke test caught exactly that)
        self.params = self._commit(params)
        # self.state: a model with state-space layers' state store, a row
        # a slot, beside the KV pool of its attention layers; None for
        # every other model. The row is zeroed at admission, carried from
        # chunk to chunk of its slot's prompt, advanced by the decode
        # ticks the slot takes part in (those whose row of the decode
        # table holds a page), and dropped with the slot.
        self.caches = self._commit_caches(self._fresh_caches())
        if self.num_pages - 1 < self.max_pages:
            raise ValueError(
                f"num_pages={self.num_pages} cannot hold even one full "
                f"sequence ({self.max_pages} pages of {self.page_size} for "
                f"max_seq_len {self.max_seq_len}, + the scratch page)")
        # speculative decoding (inference/speculative.py): k drafted
        # tokens per slot verified by ONE [N, k+1] target forward per
        # tick, exact accept/reject inside the jitted step. The draft-
        # model drafter keeps a SECOND tree of pools addressed through
        # the same page tables as the target's.
        self.spec = speculative
        self.draft_params = None
        self.draft_caches = None
        self.spec_on = np.ones(N, bool)   # per-request knob mirror
        self._spec_rows_dev = None        # committed device copy
        if speculative is not None:
            from megatron_tpu.inference.speculative import validate_spec

            validate_spec(cfg, speculative)
            if speculative.drafter == "model":
                self.draft_params = self._commit(speculative.draft_params)
                self.draft_caches = self._commit(self._fresh_draft_caches())
        self.slots: List[Optional[Request]] = [None] * N
        self.lengths = np.zeros(N, np.int32)    # valid context per slot
        self.last_tok = np.zeros(N, np.int32)   # sampled, not yet in cache
        self.temps = np.zeros(N, np.float32)
        self.top_ks = np.zeros(N, np.int32)
        self.top_ps = np.zeros(N, np.float32)
        self.keys = np.zeros((N, 2), np.uint32)
        # host page tables: tables[i] is slot i's logical->physical map.
        # Mid-prefill slots keep their REAL row in _pending_rows and a
        # scratch row here, so the shared decode table can never route an
        # idle-drift write into a half-filled (possibly shared) page.
        self.tables = np.zeros((N, self.max_pages), np.int32)
        self._pending_rows = {}
        self._device_table = None
        self._table_dirty = True
        self.prefill_queue = ChunkedPrefillQueue(self.prefill_chunk)
        # admission order for the preemption policy (higher = younger)
        self._admit_seq = [0] * N
        self._admit_counter = 0
        # sliding-window release cursor: first page index of each slot
        # NOT yet released (lengths never shrink below the committed
        # value, so release progress is monotone — the per-tick scan
        # starts here instead of at page 0)
        self._window_cursor = [0] * N

        self._queue: deque[Request] = deque()
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        # device-resident decode carry (last_tok, lengths, keys, temps,
        # top_ks, top_ps): steady-state ticks chain device arrays instead
        # of re-uploading 6 host arrays per token; the events that read or
        # edit the chains on the host drop it (_sync_carry: None ->
        # re-upload from the host mirrors)
        self._carry = None
        # the loop runs one tick ahead of the device (docs/serving.md "Step
        # loop"): a plain decode tick is dispatched, and read only after
        # the next one is in the queue. _inflight holds what is dispatched
        # and unread, oldest first; _owed[i] counts slot i's tokens among
        # it, so a finish by max_new_tokens is known at dispatch; lengths /
        # temps / top_ks / top_ps are true as of the last DISPATCH and go
        # up again when an event edits them (_carry_dirty), while the last
        # tokens and the PRNG chains live on the device alone between
        # drains. The speculative tick is synchronous: nothing of it is
        # ever in flight.
        self._inflight: deque[_InFlight] = deque()
        self._owed = np.zeros(N, np.int32)
        self._carry_dirty = False
        self._step_no = 0
        self._last_read_time = 0.0
        # the loop's spans and their own seconds (the names above); what a
        # tick's end has booked of them so far; when the last tick ended
        # (None after a park: an idle loop's wait is no tick's time); the
        # last ticks' durations, for the median a slow tick is held to;
        # the causes of this tick's drains; the collector's pauses
        self.timers = Timers()
        self._phase_seen: Dict[str, float] = {}
        self._tick_end: Optional[float] = None
        self._tick_walls: deque = deque(maxlen=SLOW_TICK_HISTORY)
        self._tick_drains: List[str] = []
        self._gc = _GcWatch()
        self._gc_seen = 0.0
        weakref.finalize(self, self._gc.watch, False)
        self.pool = PagePool(self.num_pages)
        self.prefix_cache = RadixPrefixCache(
            self.pool, self.page_size, evict_span=self.timers(EVICT))
        # hot weight reload: (params, version, applied_event) staged by
        # update_params(), swapped in BETWEEN decode ticks by the step
        # loop so in-flight slots never see a mid-tick change
        self._pending_params: Optional[tuple] = None
        self.params_version: Optional[Any] = None
        # admissions popped from the queue but not yet landed in a slot —
        # wait_idle() must not report idle while one is mid-assignment
        self._admitting = 0
        # state-migration pause (paused()): while _pause_count > 0 the
        # step loop parks BETWEEN ticks and raises _paused_evt, so an
        # exporter/importer can touch slot state without racing a tick
        self._pause_count = 0
        self._paused_evt = threading.Event()
        # once-jitted page writers (migration import; a finished prompt's
        # row of the carry) — separate jits from the decode step, so
        # imports cost zero decode recompiles
        self._kv_writer = None
        self._carry_row_writer = None
        self._preempt_signalled = False  # preempt_replica fires once
        # last time the engine demonstrably made progress (an admission
        # or decode tick COMPLETED) — readiness uses stalled() to catch a
        # wedged step loop, the failure liveness can't see (the thread is
        # alive, just hung inside a device call)
        self.last_progress_time = time.monotonic()

        # the device programs (paging/engine.py). Both steps write the pool
        # and the state store in place.
        forward = steps.make_forward(cfg, self.tp_comm, self.cp_comm)
        how = dict(vocab_size=vocab_size, want_logprobs=want_logprobs,
                   donate_argnums=(1, 2) if self._donate() else (),
                   shard_outputs=self._jit_sharding_kwargs)
        self._decode_step = steps.build_decode_step(
            cfg, forward, self._rows_decoding(), **how)
        self._chunk_step = steps.build_chunk_step(
            cfg, forward, self.prefill_chunk, **how)
        self._spec_step = None
        self._draft_chunk_step = None
        if self.spec is not None:
            from megatron_tpu.inference.speculative import (
                build_spec_decode_step)

            # donated: the target pools, plus the draft pools for the
            # model drafter (both are updated in place every tick)
            self._spec_step = build_spec_decode_step(
                cfg, self.spec, vocab_size, want_logprobs,
                () if not self._donate()
                else (1, 3) if self._has_draft_model() else (1,))
            if self._has_draft_model():
                self._draft_chunk_step = steps.build_draft_chunk_step(
                    self.spec.draft_cfg, self._donate())
        # observability for tests/metrics: monotonically-growing counters.
        # decode_recompiles counts decode-step compiles BEYOND the warmup
        # one — the "zero recompiles after warmup" invariant (PR 1) as a
        # runtime counter instead of a bench footnote
        self.stats = {"admitted": 0, "retired": 0, "ticks": 0,
                      "rejected": 0, "decode_recompiles": 0,
                      "timeouts": 0, "weight_reloads": 0,
                      "kv_exports": 0, "kv_imports": 0,
                      "decode_live_block_share": 0.0,
                      "ticks_dispatched_ahead": 0, "tick_drains": {},
                      "tokens_dropped_after_eod": 0,
                      "tick_phase_s": {}, "decode_rows": 0,
                      "slow_ticks": 0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefix_tokens_saved": 0, "prefill_tokens": 0,
                      "prefill_chunks": 0, "preemptions": 0,
                      "window_pages_released": 0, "pages_evicted": 0,
                      # prefill_live_block_share joins them at the first
                      # chunk
                      "prefill_blocks_visited": 0, "prefill_blocks_held": 0,
                      "decode_blocks_visited": 0, "decode_blocks_held": 0}
        if self.spec is not None:
            # spec_emitted counts every token the spec path emitted
            # (accepted drafts + the guaranteed token per row per tick);
            # spec_emitted / ticks = effective tokens per target forward
            self.stats.update({"spec_proposed": 0, "spec_accepted": 0,
                               "spec_emitted": 0})
        self._decode_cache_seen = 0  # compiles observed on _decode_step

        # Prometheus collectors (megatron_tpu/telemetry): shared with the
        # serving HTTP layer via the process-default registry unless a
        # test hands in its own. Flight recorder (optional): heartbeat
        # per tick so a wedged device step dumps a stall bundle.
        self.flight_recorder = flight_recorder
        m = metrics if metrics is not None else default_registry()
        self.metrics = m
        self._m_slots = m.gauge("engine_slots_total", "KV-cache slots")
        self._m_active = m.gauge("engine_slots_active",
                                 "slots with a live request")
        self._m_queue = m.gauge("engine_queue_depth",
                                "requests waiting for a slot")
        self._m_admitted = m.counter("engine_requests_admitted_total",
                                     "requests admitted into a slot")
        self._m_retired = m.counter("engine_requests_retired_total",
                                    "requests completed")
        self._m_rejected = m.counter("engine_requests_rejected_total",
                                     "requests rejected (invalid/oversized/"
                                     "failed prefill/queue full)")
        self._m_timeouts = m.counter(
            "engine_requests_timeout_total",
            "requests failed on an expired deadline (queued or mid-decode)")
        self._m_reloads = m.counter(
            "engine_weight_reloads_total",
            "hot weight swaps applied between decode ticks")
        self._m_ticks = m.counter("engine_ticks_total",
                                  "batched decode steps executed")
        self._m_tokens = m.counter("engine_tokens_generated_total",
                                   "tokens sampled across all requests")
        self._m_recompiles = m.counter(
            "engine_decode_recompiles_total",
            "decode-step compiles beyond warmup (invariant: 0)")
        self._m_ttft = m.histogram("engine_ttft_seconds",
                                   "submit -> first generated token")
        self._m_per_token = m.histogram(
            "engine_time_per_output_token_seconds",
            "per-request decode latency per generated token")
        self._m_prefill = m.histogram("engine_prefill_seconds",
                                      "admission prefill wall time")
        self._m_live_blocks = m.gauge(
            "engine_decode_live_block_share",
            "KV blocks the decode kernel visits this tick over the blocks "
            "its page table holds")
        self._m_ahead = m.counter(
            "engine_ticks_dispatched_ahead_total",
            "decode ticks dispatched before the previous tick's tokens "
            "were read")
        self._m_drains = m.counter(
            "engine_tick_drains_total",
            "times the loop read every tick in flight before going on, "
            "by cause", label_names=("cause",))
        self._m_dropped = m.counter(
            "engine_tokens_dropped_after_eod_total",
            "tokens of the one tick a row runs past its end-of-document "
            "token, dropped at the read")
        self._m_tick = m.histogram("engine_decode_tick_seconds",
                                   "batched decode tick wall time")
        self._m_phase = m.counter(
            "engine_tick_phase_seconds_total",
            "the loop thread's time by phase of the tick (own time: a "
            "phase's spans less the spans inside them); `read` is the wait "
            "for the device", label_names=("phase",))
        self._m_rows = m.counter(
            "engine_decode_rows_total",
            "decoding rows summed over the ticks read (over "
            "engine_ticks_total: the mean decoding batch)")
        self._m_spec_proposed = m.counter(
            "engine_spec_proposed_total",
            "draft tokens proposed to the speculative verify step")
        self._m_spec_accepted = m.counter(
            "engine_spec_accepted_total",
            "draft tokens accepted by the exact accept/reject")
        self._m_kv_exports = m.counter(
            "engine_kv_exports_total",
            "request states exported for migration")
        self._m_kv_imports = m.counter(
            "engine_kv_imports_total",
            "migrated request states imported, by resume path",
            label_names=("path",))
        self._m_spec_len = m.histogram(
            "engine_spec_accept_length",
            "accepted drafts per slot per tick (0..k)",
            buckets=(0.5, 1.5, 2.5, 3.5, 4.5, 6.5, 8.5, 12.5, 16.5))
        # compressed-collective accounting (quant/): dense = the bytes a
        # dense TP engine would have moved for the same work, compressed
        # = what this mode moves; dense/compressed = live compression
        # ratio (tools/telemetry_report.py serving section)
        self._m_comm_dense = m.counter(
            "engine_comm_dense_bytes_total",
            "TP-collective wire bytes the dense path would have moved")
        self._m_comm_compressed = m.counter(
            "engine_comm_compressed_bytes_total",
            "TP-collective wire bytes actually moved by this mode")
        if self.tp_comm is not None:
            self.stats.update({"comm_dense_bytes": 0,
                               "comm_compressed_bytes": 0})
            self._journal_comm_policy()
        self._m_slots.set(num_slots)
        self._m_pages_total = m.gauge("engine_pages_total",
                                      "KV pool pages (minus scratch)")
        self._m_pages_free = m.gauge("engine_pages_free",
                                     "KV pool pages on the free list")
        self._m_prefix_hits = m.counter(
            "engine_prefix_cache_hits_total",
            "admissions that aliased cached prefix pages")
        self._m_prefix_misses = m.counter(
            "engine_prefix_cache_misses_total",
            "admissions with no cached prefix")
        self._m_prefix_saved = m.counter(
            "engine_prefix_tokens_saved_total",
            "prefill positions skipped via the prefix cache")
        self._m_preempted = m.counter(
            "engine_preemptions_total",
            "slots preempted under page-pool pressure")
        self._m_chunks = m.counter("engine_prefill_chunks_total",
                                   "chunked-prefill steps executed")
        self._m_prefill_blocks = m.gauge(
            "engine_prefill_live_block_share",
            "KV blocks the last prefill chunk's attention kernel visited "
            "over the blocks its page table holds a query tile")
        self._m_window_released = m.counter(
            "engine_window_pages_released_total",
            "pages freed from behind the sliding attention window")
        self._m_evicted = m.counter(
            "engine_pages_evicted_total",
            "cache-only prefix pages the radix tree gave back to the pool")
        self._m_pages_total.set(self.num_pages - 1)
        self._m_pages_free.set(self.pool.free_pages)
        self._m_state_bytes = m.gauge(
            "engine_state_bytes",
            "recurrent state held beside the KV pages (state-space layers)")
        self._m_state_resets = m.counter(
            "engine_state_resets_total",
            "slot states zeroed at admission (state-space layers)")
        if self.state is not None:
            self.stats["state_resets"] = 0
            self._m_state_bytes.set(ssm.state_bytes(self.state))
            self._zero_state_row = jax.jit(
                ssm.zero_row, donate_argnums=(0,) if self._donate() else ())
        # a model that holds a share of its router's experts: how many of
        # the (row, choice) pairs its steps computed FOR A ROW SOMEBODY
        # READS (a decoding slot's, a chunk's real positions: the others
        # are not routed, ops/moe.py moe_block `rows_read`) went to experts
        # held here, summed over the expert layers; and, of the decode
        # ticks alone, how many of the held experts such a row reached
        # (the matrices the tick's expert kernels had to move) of those
        # there are. Both steps add to one vector of counters on the device
        # (_step_counts: [held, all, experts read, experts offered],
        # uint32, which wraps; None where the steps count nothing), which
        # rides to the host in the fetch of a step's tokens
        # (_apply_counts).
        self._m_moe = [m.counter(name, text) for _, name, text in _MOE_COUNTS]
        self._step_counts = None
        if self.cfg.holds_expert_share:
            for key, _, _ in _MOE_COUNTS:
                self.stats[key] = 0
            self._step_counts = self._commit_small(
                np.zeros(len(_MOE_COUNTS), np.uint32))
            self._counts_seen = np.zeros(len(_MOE_COUNTS), np.uint32)

    # ----- models with state-space layers ---------------------------------

    def _refuse_state_transfer(self, what: str) -> None:
        if self.cfg.has_ssm:
            raise NotImplementedError(
                f"{what} moves keys and values only: a model with "
                "state-space layers also needs its recurrent state at the "
                "span's end, which no snapshot holds yet")

    # ----- cache + shape policy -------------------------------------------

    def _round_seq_len(self, n: int) -> int:
        """Logical capacity is whole pages (the paged kernels' grid is per
        page)."""
        m = self.page_size
        if n % m == 0:
            return n
        rounded = -(-n // m) * m
        import warnings

        warnings.warn(
            f"engine max_seq_len {n} is not a multiple of the page size "
            f"{m}; rounding up to {rounded}", stacklevel=3)
        return rounded

    def _fresh_caches(self):
        """Paged pools: num_pages rows of page_size positions
        (ops/kv_store.py; int8 with per-position scales), of the attention
        layers; with them self.state, the state-space layers' state store
        (ops/ssm.py: a zeroed row a slot; None for a model without)."""
        if self.num_pages is None:
            # default pool = every slot can grow to max_seq_len (+ the
            # scratch page); shrink it to oversubscribe
            self.num_pages = self.num_slots * self.max_pages + 1
        self.state = (self._commit(ssm.create_state(self.cfg, self.num_slots))
                      if self.cfg.has_ssm else None)
        return kv_store.create(self.cfg, self.num_pages, self.page_size,
                               int8=self.kv_cache_int8)

    def _fresh_draft_caches(self):
        """Draft-model page pools (speculative decoding): the draft
        config's own layer/head geometry over the SAME page count and
        page size as the target pools, addressed through the SAME per-
        slot page tables — one allocation/refcount/prefix-aliasing
        story covers both trees (a page shared via the radix cache is
        shared in both pools, since both were written through the same
        table by the original prefill). Always bf16/f32 — the draft is
        small, quantizing it would buy little and cost a second
        quantization seam."""
        return kv_store.create(self.spec.draft_cfg, self.num_pages,
                               self.page_size)

    def _rebuild_caches(self):
        """Replace every donated cache tree after a failed device call
        may have consumed the old buffers (chunk/decode failure
        recovery). Every cached prefix dies with the pool bytes, draft
        state too, and mid-prefill slots lose their computed chunks —
        fail them like the active ones the caller already failed."""
        for i in sorted(self.prefill_queue.slots):
            req = self.slots[i]
            if req is not None:
                self._clear_slot(i)
                req._finish("engine cache rebuilt after a failed step")
        self.prefix_cache.clear()
        self._m_pages_free.set(self.pool.free_pages)
        self.caches = self._commit_caches(self._fresh_caches())
        if self.draft_caches is not None:
            self.draft_caches = self._commit(self._fresh_draft_caches())

    def _capacity_margin(self) -> int:
        """Sequence-capacity headroom a speculating engine reserves: a
        tick writes K/V at positions length..length+k, so the LAST tick
        of a request (length = prompt + max_new - 1) must still fit k
        more positions — admission rejects prompt + max_new past
        max_seq_len - k. 0 when speculation is off."""
        return self.spec.k if self.spec is not None else 0

    # ----- device placement of the steps' arguments -----------------------

    def _donate(self):
        # donate the persistent pool so each step updates it in place;
        # XLA:CPU can't donate and would warn every compile
        if self.force_donate is not None:
            return (1,) if self.force_donate else ()
        return (1,) if jax.default_backend() != "cpu" else ()

    def _commit(self, tree):
        """Place host-built arrays COMMITTED on the device, so a step's
        first call (host-uploaded carry/caches) and its steady state
        (jit outputs, always committed) share ONE jit cache entry. With
        any committed argument in the mix — which checkpoint-loaded
        params always are — mixed committedness otherwise splits the
        decode step into two compiled signatures, i.e. a wasted compile
        per engine that the decode_recompiles counter flags (and did:
        that is how this path was found). Mesh-ambient engines leave
        placement to GSPMD, as before."""
        if self.mesh is not None:
            return tree
        return jax.device_put(
            tree, jax.sharding.SingleDeviceSharding(jax.devices()[0]))

    def _kv_sharding(self):
        """Cache-leaf placement on a mesh engine: every leaf (the pools
        and their int8 scale companions alike) has its kv heads sharded
        over "tensor" when it divides — matching the column-parallel
        wk/wv head sharding so cache writes stay local. None on mesh-less
        engines."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        tp = dict(self.mesh.shape).get("tensor", 1)
        if tp > 1 and self.cfg.n_kv_heads % tp == 0:
            return NamedSharding(self.mesh,
                                 kv_store.partition_spec(heads="tensor"))
        return NamedSharding(self.mesh, P())

    def _commit_caches(self, tree):
        """Mesh engines pin the cache layout explicitly (and the decode/
        chunk jits pin it back via out_shardings): without this the
        first tick's host-uploaded caches and the steady state's jit
        outputs split the decode step's cache key — the same wasted
        compile _commit fixes for single-device engines, which mesh
        engines used to pay (1 decode recompile after warmup)."""
        if self.mesh is None:
            return self._commit(tree)
        sh = self._kv_sharding()
        return jax.tree.map(lambda a: jax.device_put(a, sh), tree)

    def _commit_small(self, tree):
        """Committed replicated placement for the decode carry / page
        tables / knob rows on mesh engines (single-device engines: the
        ordinary commit)."""
        if self.mesh is None:
            return self._commit(tree)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _jit_sharding_kwargs(self, out_template):
        """out_shardings kwargs for the decode/chunk jits on a mesh
        engine: "kv" entries take the pinned cache sharding, everything
        else replicated — so outputs re-enter the next call with
        byte-identical signatures (zero steady-state recompiles). {} on
        mesh-less engines (placement matches _commit already)."""
        if self.mesh is None:
            return {}
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        kv = self._kv_sharding()

        def resolve(tag):
            if tag == "kv":
                return jax.tree.map(lambda _: kv, self.caches)
            return rep

        return {"out_shardings": tuple(resolve(t) for t in out_template)}

    def _rows_decoding(self):
        """The function the decode step reads its table with (the CP
        engine's reads its ranks' local tables). It holds no reference to
        the engine, as nothing the steps close over does."""
        return steps.rows_decoding

    def _has_draft_model(self) -> bool:
        return self.spec is not None and self.spec.drafter == "model"

    # ----- scheduling ------------------------------------------------------

    def submit(self, req: Request) -> Request:
        """Queue a request; returns it (wait on req.done)."""
        req.submit_time = time.monotonic()
        p = len(req.prompt)
        if p == 0:
            req._finish("empty prompt")
            self.stats["rejected"] += 1
            self._m_rejected.inc()
            return req
        if req.max_new_tokens < 1:
            req._finish("max_new_tokens must be >= 1")
            self.stats["rejected"] += 1
            self._m_rejected.inc()
            return req
        margin = self._capacity_margin()
        if p + req.max_new_tokens > self.max_seq_len - margin:
            req._finish(
                f"prompt ({p}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds engine max_seq_len {self.max_seq_len}"
                + (f" minus the speculative headroom {margin}"
                   if margin else ""))
            self.stats["rejected"] += 1
            self._m_rejected.inc()
            return req
        if req.deadline_s is not None:
            if req.deadline_s <= 0:
                req._finish("deadline_s must be > 0 (or None: no deadline)")
                self.stats["rejected"] += 1
                self._m_rejected.inc()
                return req
            req._deadline = req.submit_time + req.deadline_s
        if resilience.fault_armed("reject_admission"):
            # injected overload: every admission answers queue-full while
            # armed (drives the router's retry-on-503 path in tests)
            req.overloaded = True
            req._finish("engine queue full (injected: reject_admission); "
                        "retry later")
            self.stats["rejected"] += 1
            self._m_rejected.inc()
            return req
        with self._cv:
            if (self.max_queue is not None
                    and len(self._queue) >= self.max_queue):
                # bounded admission: overload degrades to fast rejection
                # (HTTP 503 upstream) instead of unbounded queue latency
                req.overloaded = True
                req._finish(
                    f"engine queue full ({self.max_queue} waiting); "
                    + self._overload_detail() + "retry later")
                self.stats["rejected"] += 1
                self._m_rejected.inc()
                return req
            self._queue.append(req)
            self._m_queue.set(len(self._queue))
            self._cv.notify_all()
        return req

    def _overload_detail(self) -> str:
        """Extra cause text for queue-full rejections — subclasses with
        a richer admission model (the CP engine's striped pools) name
        WHAT is actually blocking, so the 503 detail distinguishes
        resource exhaustion from plain queue depth."""
        return ""

    @property
    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    # ----- page accounting -------------------------------------------------

    def _alloc_pages(self, n: int,
                     logical_start: int = 0) -> Optional[List[int]]:
        """n fresh pages, evicting LRU cache-only prefix pages if the
        free list can't cover it. None = still dry (caller defers or
        preempts). logical_start is the logical page index the run
        starts at within its row — ignored here, but the CP engine's
        striped pool draws each page from the rank owning that logical
        slot (inference/context_parallel/pool.py)."""
        pages = self.pool.alloc(n)
        if pages is None:
            self._note_evicted(
                self.prefix_cache.evict(n - self.pool.free_pages))
            pages = self.pool.alloc(n)
        if pages is not None:
            self._m_pages_free.set(self.pool.free_pages)
        return pages

    def _note_evicted(self, freed: int) -> int:
        if freed:
            self.stats["pages_evicted"] += freed
            self._m_evicted.inc(freed)
        return freed

    def _release_slot_pages(self, i: int) -> None:
        row = self._pending_rows.pop(i, self.tables[i])
        live = [int(p) for p in row if p != SCRATCH_PAGE]
        if live:
            self.pool.release(live)
        self.tables[i] = SCRATCH_PAGE
        self._table_dirty = True
        self._m_pages_free.set(self.pool.free_pages)

    def _clear_slot(self, i: int):
        """Reset EVERY per-slot host mirror — a cleared slot must not
        leave sampling knobs behind, or the next carry upload would keep
        the batched sampler's filter branch live for stale rows. (This
        is the whole of the retire-path knob hygiene: every retire /
        timeout / preempt / stop path funnels through here, and
        _carry_dirty sends the cleared knobs and length up before the
        next dispatch (_init_carry), over the device carry that still
        holds the old ones — audited again for the
        speculative rollback path, whose accept/reject cond reads the
        same temps/top_ks/top_ps rows; regression-pinned by
        test_speculative.py's all-greedy filter-dead test.) The slot's
        page references go back to the pool; pages the radix tree also
        holds stay cached for future hits."""
        self._release_slot_pages(i)
        self.prefill_queue.drop_slot(i)
        self._window_cursor[i] = 0
        self.slots[i] = None
        self.lengths[i] = 0
        self.last_tok[i] = 0
        self.temps[i] = 0.0
        self.top_ks[i] = 0
        self.top_ps[i] = 0.0
        self._owed[i] = 0
        self._carry_dirty = True
        if not self.spec_on[i]:
            self.spec_on[i] = True
            self._spec_rows_dev = None

    def _retire(self, i: int):
        req = self.slots[i]
        self._clear_slot(i)
        self.stats["retired"] += 1
        self._m_retired.inc()
        self._m_active.set(self.num_active)
        if req.first_token_time is not None and len(req.generated) > 1:
            # steady-state decode latency: exclude the prefill-produced
            # first token (that's what TTFT measures)
            self._m_per_token.observe(
                (time.monotonic() - req.first_token_time)
                / (len(req.generated) - 1))
        # _clear_slot marked the carry dirty: the device still holds this
        # slot's sampling knobs, and a stale temperature/top_k>0 row would
        # keep the batched sampler's lax.cond filter branch (the [N, V]
        # sort) live for every remaining tick. The zeroed row goes up
        # before the next dispatch, with no drain: a retirement happens at
        # a read, often with the next tick already in flight
        req.finish_time = time.monotonic()
        self._journal_request(req, "ok")
        req._finish()
        self._m_pages_free.set(self.pool.free_pages)

    def _sync_carry(self, cause: str):
        """Make every host mirror true and drop the device carry: read
        what is in flight (_drain, counted under `cause`), then pull the
        per-slot PRNG chains, which live on the device alone between such
        events. For the rare events that read or edit the chains or the
        last tokens on the host (preemption, migration); the next dispatch
        uploads the carry from the mirrors.
        Events that edit lengths and knobs alone mark _carry_dirty."""
        self._drain(cause)
        if self._carry is not None:
            with self.timers(READ):
                self.keys = np.array(self._carry[2])
            self._carry = None

    def _drain(self, cause: str) -> int:
        """Read every tick in flight, oldest first, so that the host
        mirrors and the requests hold what the device has computed.
        Allowed at events that edit rows, never on a tick that only
        decodes; `engine_tick_drains_total{cause}` counts those that found
        something to read. Returns how many programs were read."""
        if not self._inflight:
            return 0
        by = self.stats["tick_drains"]
        by[cause] = by.get(cause, 0) + 1
        self._m_drains.inc(cause=cause)
        self._tick_drains.append(cause)
        n = 0
        span = self.timers(DRAIN)
        span.start(cause=cause)
        try:
            while self._inflight:
                self._read_next()
                n += 1
        finally:
            span.stop()
        return n

    def _read_behind(self, dispatched: int) -> int:
        """The end of a step: read what earlier steps dispatched and leave
        this step's own in flight, so that the device has the next tick
        queued while the host walks this one's tokens; a step that
        dispatched nothing reads everything. Returns `dispatched`, or, for
        such a step, how many programs it read (0 = idle)."""
        n = 0
        while self._inflight and not (
                dispatched and self._inflight[0].step == self._step_no):
            self._read_next()
            n += 1
        return dispatched or n

    # a record has left `_inflight` and its read is not over yet: to
    # wait_idle() the engine is not idle until what the read applies (the
    # last tokens, the counters) is applied
    _reading = False

    def _read_next(self) -> None:
        """Read the oldest program in flight."""
        self._reading = True
        try:
            self._read(self._inflight.popleft())
        finally:
            self._reading = False

    def _drop_inflight(self) -> None:
        """Forget what is in flight without reading it (a failed device
        step, stop()): the caller fails or has failed its requests."""
        for rec in self._inflight:
            if rec.pinned:
                self.pool.release(rec.pinned)
        self._inflight.clear()

    def _admit(self) -> int:
        """Move queued requests into free slots (_try_assign), in arrival
        order, as far as the pool covers their prompts. Returns the number
        admitted this tick. Nothing is read: a prompt's first token comes
        with its last chunk."""
        n = 0
        for i in range(self.num_slots):
            if self.slots[i] is not None:
                continue
            with self._cv:
                req = self._queue.popleft() if self._queue else None
                if req is not None:
                    # visible to wait_idle(): popped but not yet in a slot
                    self._admitting += 1
            if req is None:
                break
            try:
                if not self._try_assign(i, req):
                    # pool can't cover the prompt right now: keep arrival
                    # order (front of the queue) and stop admitting —
                    # active slots retiring will free pages
                    with self._cv:
                        self._queue.appendleft(req)
                        self._m_queue.set(len(self._queue))
                    break
                n += 1
                with self._cv:
                    self._m_queue.set(len(self._queue))
            finally:
                with self._cv:
                    self._admitting -= 1
                self.last_progress_time = time.monotonic()
        return n

    def _try_assign(self, i: int, req: Request) -> bool:
        """Give req slot i: alias cached prefix pages, allocate the rest
        of the prompt span, queue the chunked prefill. False = defer
        (req untouched); a request no idle engine could EVER fit is
        failed loudly instead (returns True: req was consumed)."""
        resumed = req.resume_key is not None or bool(req.generated)
        toks = (np.concatenate([np.asarray(req.prompt, np.int32),
                                np.asarray(req.generated, np.int32)])
                if resumed else np.asarray(req.prompt, np.int32))
        p_ext = len(toks)
        ps = self.page_size
        # the prefix cache gives a model with state-space layers no hit:
        # a hit needs the recurrent state at the prefix's end beside its
        # pages, and no snapshot holds it yet (the tree is never asked,
        # and _finish_prefill enters nothing into it)
        hit_pages, hit_lps = (([], []) if self.cfg.has_ssm
                              else self.prefix_cache.lookup(toks))
        span = len(hit_pages) * ps
        n_prompt_pages = -(-p_ext // ps)
        # retain the hits BEFORE allocating: _alloc_pages may evict
        # cache-only pages, and un-pinned hit pages are exactly that —
        # an eviction here would free a hit page and hand it back as
        # "fresh", mapping one physical page at two logical blocks
        self.pool.retain(hit_pages)
        fresh = self._alloc_pages(n_prompt_pages - len(hit_pages),
                                  logical_start=len(hit_pages))
        if fresh is None:
            self.pool.release(hit_pages)
            if self.num_active == 0:
                req._finish(
                    f"prompt needs {n_prompt_pages} pages but the pool has "
                    f"{self.pool.free_pages} free with no active slots to "
                    f"wait for (num_pages={self.num_pages})")
                self.stats["rejected"] += 1
                self._m_rejected.inc()
                return True
            return False
        self._m_pages_free.set(self.pool.free_pages)

        row = np.zeros(self.max_pages, np.int32)
        row[:len(hit_pages)] = hit_pages
        row[len(hit_pages):n_prompt_pages] = fresh
        self._pending_rows[i] = row
        if self.state is not None:
            # a sequence starts (a preempted one again, from position 0).
            # The tick in flight may still advance the old occupant's row
            # (one that ended by eod runs one tick more): this write is
            # dispatched on the same chain of donated `state` buffers, so
            # it orders after that tick by data dependence
            self.state = self._zero_state_row(self.state, np.int32(i))
            self.stats["state_resets"] += 1
            self._m_state_resets.inc()
        self.slots[i] = req
        if req.first_token_time is None:
            req.slot_time = time.monotonic()
        self._admit_counter += 1
        self._admit_seq[i] = self._admit_counter

        # recompute starts one position INSIDE the shared span so the
        # boundary token's teacher-forced logprob comes from real logits;
        # its K/V write is fenced onto scratch (write_start = span)
        start = max(span - 1, 0)
        task = PrefillTask(
            slot=i, tokens=toks, start=start, off=start,
            write_start=span,
            # a fresh chain stays a device array: reading it back would
            # wait for the tick in flight
            key=(np.asarray(req.resume_key) if req.resume_key is not None
                 else jax.random.PRNGKey(req.seed)),
            resumed=resumed, t_start=time.monotonic())
        if not resumed and span > 0:
            # cached teacher-forced logprobs for tokens 1..span-1; the
            # recomputed chunks continue seamlessly from token `span`
            task.plp_parts.extend(hit_lps)
        self.prefill_queue.add(task)

        if span > 0:
            req.prefix_tokens += start
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += start
            self._m_prefix_hits.inc()
            self._m_prefix_saved.inc(start)
        else:
            self.stats["prefix_misses"] += 1
            self._m_prefix_misses.inc()
        self.stats["admitted"] += 1
        self._m_admitted.inc()
        self._m_active.set(self.num_active)
        return True

    # ----- chunked prefill -------------------------------------------------

    def _prefill_tick(self) -> int:
        """Dispatch at most ONE chunk of the oldest incomplete prefill.
        Returns 1 when a chunk ran (progress signal for run_until_idle).
        Nothing of it is read here: the chunk queues behind the tick in
        flight, its scalars go up with the call (numpy values, no device
        array made one by one), its prompt logprobs stay device arrays
        until the prompt's last chunk, and that chunk's first token is
        read with the ticks (_finish_prefill)."""
        task = self.prefill_queue.peek()
        if task is None:
            return 0
        i = task.slot
        req = self.slots[i]
        C = self.prefill_chunk
        off = task.off
        toks_ext = np.zeros((1, C + 1), np.int32)
        avail = task.tokens[off:off + C + 1]
        toks_ext[0, :len(avail)] = avail
        row = self._pending_rows[i]
        self._note_prefill_blocks(off, task.total)
        try:
            tok, lp, plp, self.caches, self.state, key, *counts = (
                self._chunk_step(
                    self.params, self.caches, self.state,
                    self._chunk_table_arg(row),
                    toks_ext, np.int32(off),
                    np.int32(task.write_start), np.int32(task.total),
                    np.int32(task.total - 1), task.key,
                    np.float32(req.temperature), np.int32(req.top_k),
                    np.float32(req.top_p),
                    None if self.state is None else np.int32(i),
                    *self._counts_arg()))
            self._step_counts, = counts or (None,)
            if self._has_draft_model():
                # mirror the chunk into the draft pools through the same
                # table row and write fences
                self.draft_caches = self._draft_chunk_step(
                    self.draft_params, self.draft_caches,
                    self._chunk_table_arg(row),
                    toks_ext[:, :C], np.int32(off),
                    np.int32(task.write_start), np.int32(task.total))
        except Exception as e:  # noqa: BLE001 - a failing chunk must fail
            # THIS request, not strand it un-signalled and kill the loop
            self._clear_slot(i)
            req._finish(f"prefill failed: {e}")
            self.stats["rejected"] += 1
            self._m_rejected.inc()
            if self._donate():
                # the failed call may have consumed the donated pools
                # (target AND draft trees), and what is in flight with them
                self._drop_inflight()
                for j, other in enumerate(self.slots):
                    if other is not None:
                        self._clear_slot(j)
                        other._finish(f"prefill failed: {e}")
                self._rebuild_caches()
            self._m_active.set(self.num_active)
            return 1
        n = min(C, task.total - off)
        if self.want_logprobs:
            task.plp_parts.append(plp)  # the device's, until the last chunk
        req.chunks += 1
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += n
        self._count_comm(self._comm_chunk_bytes)
        self._m_chunks.inc()
        if self.flight_recorder is not None:
            self.flight_recorder.heartbeat(
                f"prefill chunk slot {i} ({off}+{n}/{task.total})")
        if self.prefill_queue.advance(task, n):
            self._finish_prefill(i, task, tok, lp, key)
        return 1

    def _note_prefill_blocks(self, off: int, total: int) -> None:
        """Set `engine_prefill_live_block_share` for the chunk about to
        run: the trips the chunk kernel's loops take (a query tile's, over
        the blocks its queries see below the prompt's end) over the blocks
        the row's table holds a query tile, from the host's offset and
        length through the kernel's own loop bounds. The twin of
        `engine_decode_live_block_share`; the journal's `serve_ticks`
        carries both counts summed over every chunk."""
        from megatron_tpu.ops.pallas.flash_template import (
            chunk_blocks_visited)

        cfg = self.cfg
        visited, held = chunk_blocks_visited(
            off, self.prefill_chunk, total,
            cfg.num_attention_heads // cfg.n_kv_heads, self.max_pages,
            self.page_size, self._kernel_kv_heads(),
            window=cfg.attention_kind.sliding_window_size)
        self.stats["prefill_blocks_visited"] += visited
        self.stats["prefill_blocks_held"] += held
        self.stats["prefill_live_block_share"] = visited / held
        self._m_prefill_blocks.set(visited / held)

    def _finish_prefill(self, i: int, task: PrefillTask, tok, lp, key):
        """The prompt's last chunk is dispatched: publish the slot's table
        row to the shared decode table, arm the decode mirrors, and write
        the first sampled token and the chain into the slot's row of the
        device carry, so the slot decodes in this step's tick. `tok`, `lp`
        and `key` are device values nobody has read: what the host owes
        the request for them (the token, the logprobs, the radix tree's
        entry) waits in flight and is paid at its read (_read_first)."""
        req = self.slots[i]
        row = self._pending_rows.pop(i)
        self.tables[i] = row
        self._table_dirty = True
        self.lengths[i] = task.total
        self.temps[i] = req.temperature
        self.top_ks[i] = req.top_k
        self.top_ps[i] = req.top_p
        self._carry_dirty = True
        self._write_carry_row(i, tok, key)
        if self.spec is not None:
            self.spec_on[i] = bool(req.spec)
            self._spec_rows_dev = None
        self._owed[i] += 1
        pinned: tuple = ()
        p0 = len(req.prompt)
        if p0 >= self.page_size and not self.cfg.has_ssm:
            # the FULL pages of the ORIGINAL prompt, for the radix tree.
            # They enter it at the read, with their logprobs; held until
            # then, so that neither the window's release nor a retirement
            # hands one back to the pool in between
            pinned = tuple(int(p) for p in row[:p0 // self.page_size])
            self.pool.retain(pinned)
        plps = (list(task.plp_parts)
                if self.want_logprobs and not task.resumed else [])
        rec = _InFlight(rows=[(i, req)],
                        out=self._start_fetch((tok, lp, plps,
                                               self._step_counts)),
                        step=self._step_no, t0=time.monotonic(),
                        task=task, pinned=pinned)
        if self.spec is not None:
            # the speculative tick is synchronous: it proposes from the
            # tokens, so the mirrors must be true before it runs
            self._read(rec)
        else:
            self._inflight.append(rec)

    def _write_carry_row(self, i: int, tok, key) -> None:
        """One row of the device carry takes a finished prompt's first
        token and PRNG chain, both device values: a device-side write
        (as `zero_row` is for the state), so that one prompt's end stalls
        no other row. Lengths and knobs go up from the mirrors
        (_init_carry)."""
        if self._carry_row_writer is None:
            def write_carry_row(last, keys, row, tok, key):
                return last.at[row].set(tok), keys.at[row].set(key)

            # nothing donated: `last` is also the tick in flight's tokens
            self._carry_row_writer = jax.jit(
                write_carry_row,
                **self._jit_sharding_kwargs(("rep", "rep")))
        last, lens, keys, temps, top_ks, top_ps = self._init_carry()
        last, keys = self._carry_row_writer(last, keys, np.int32(i), tok,
                                            key)
        self._carry = (last, lens, keys, temps, top_ks, top_ps)

    # ----- preemption ------------------------------------------------------

    def _preempt_one(self) -> bool:
        """Preempt the youngest active slot (LIFO — later arrivals yield
        pages to earlier ones). Its request re-enters the queue FRONT and
        resumes by exact teacher-forced recompute."""
        with self.timers(PREEMPT):
            return self._preempt_youngest()

    def _preempt_youngest(self) -> bool:
        # the chain to keep is the device's, and what is in flight may
        # end a request: read it before choosing
        self._sync_carry("pages")
        cands = [i for i in range(self.num_slots) if self.slots[i] is not None]
        if not cands:
            return False
        i = max(cands, key=lambda j: self._admit_seq[j])
        req = self.slots[i]
        req.preemptions += 1
        if i not in self.prefill_queue.slots:
            # mid-decode: preserve the PRNG chain so the resumed request
            # samples exactly the tokens it would have sampled
            req.resume_key = self.keys[i].copy()
        self._clear_slot(i)
        with self._cv:
            self._queue.appendleft(req)
            self._m_queue.set(len(self._queue))
        self.stats["preemptions"] += 1
        self._m_preempted.inc()
        self._m_active.set(self.num_active)
        return True

    def _ensure_decode_pages(self) -> None:
        """Before a decode tick, every decodable slot needs real pages
        under its write span (lengths[i] .. lengths[i] + span - 1; span
        is 1 plain, k+1 speculative — rejected drafts roll back the
        length but the pages stay mapped for future growth, and shared
        prefix pages are never in the span). Allocate across page
        boundaries, preempting the youngest slot when the pool is dry.
        Each preemption frees that slot's pages, so this terminates.
        Lengths are those of the last dispatch (a decoding row grows by
        exactly 1 a tick), so this needs no token of the tick in flight;
        only a dry pool reads it, since it may end a request and hand
        its pages back."""
        span = self._decode_write_span()
        ps = self.page_size
        while True:
            rows = self._decode_rows()
            dry = False
            for i in rows:
                first = int(self.lengths[i]) // ps
                last_pg = (int(self.lengths[i]) + span - 1) // ps
                for pg in range(first, last_pg + 1):
                    if self.tables[i, pg] != SCRATCH_PAGE:
                        continue
                    pages = self._alloc_pages(1, logical_start=pg)
                    if pages is None:
                        if (not self._drain("pages")
                                and not self._preempt_one()):
                            # unreachable: slot i itself is preemptible
                            return
                        dry = True
                        break  # re-derive rows (the victim may be gone)
                    self.tables[i, pg] = pages[0]
                    self._table_dirty = True
                if dry:
                    break
            if not dry:
                return

    def _release_window_pages(self) -> None:
        """Sliding-window page release (Mistral; ROADMAP item 1): pages
        every position of which sits fully behind a slot's attention
        window can never be attended again — the decode mask only allows
        k_pos >= length + 1 - window and lengths never shrink below the
        committed value (speculative rollback rolls back only
        UNcommitted draft positions) — so the slot's reference goes back
        to the pool and the table entry parks on scratch (reads of it
        are exactly masked; scratch contents are finite activations, so
        the masked scores stay well-defined). Pages the radix prefix
        cache also holds keep their cache reference: a later request
        sharing the prompt still hits them."""
        window = self.cfg.attention_kind.sliding_window_size
        if window is None:
            return
        ps = self.page_size
        freed = 0
        for i in self._decode_rows():
            limit = int(self.lengths[i]) - int(window)
            if limit < ps:
                continue
            # O(1) amortized: at most one page per slot newly crosses
            # the window per tick, and the cursor never rewinds (a
            # cleared/preempted slot resets it in _clear_slot)
            for pg in range(self._window_cursor[i], limit // ps):
                if self.tables[i, pg] != SCRATCH_PAGE:
                    self.pool.release([int(self.tables[i, pg])])
                    self.tables[i, pg] = SCRATCH_PAGE
                    self._table_dirty = True
                    freed += 1
            self._window_cursor[i] = max(self._window_cursor[i],
                                         limit // ps)
        if freed:
            self.stats["window_pages_released"] += freed
            self._m_window_released.inc(freed)
            self._m_pages_free.set(self.pool.free_pages)

    # ----- stepping --------------------------------------------------------

    def _req_finished(self, req: Request) -> bool:
        return (len(req.generated) >= req.max_new_tokens
                or (req.eod is not None and req.generated
                    and req.generated[-1] == req.eod))

    def step(self) -> int:
        """One engine tick, a `serve-tick` span around the phases of
        `_tick` (the names at the top of this file). Returns the number
        of active slots served + chunks run, or what a step with nothing
        to dispatch read (0 = idle, and nothing in flight)."""
        self._step_no += 1
        tick = self.timers(TICK)
        tick.start(step_num=self._step_no)
        try:
            return self._tick()
        finally:
            tick.stop()
            self._end_tick()

    def _tick(self) -> int:
        """One engine tick: admit, dispatch one prefill chunk and one
        batched decode for every slot whose prompt is fully cached, then
        read the tick before (the loop runs one tick ahead of the device:
        everything before the read works from lengths the host has, and
        happens while the device runs the last tick). Returns slots
        served + chunks run, or what a step with nothing to dispatch
        read (0 = idle, and nothing in flight)."""
        with self.timers(PRE):
            self._pre_tick()  # faults, staged weight swaps, deadlines
        with self.timers(ADMIT):
            self._admit()
        with self.timers(PREFILL):
            chunked = self._prefill_tick()
            if chunked:
                # chunked prefill with no decodable slots is still progress
                # — without this a long multi-chunk prompt would trip the
                # stalled() readiness check while prefilling normally
                self.last_progress_time = time.monotonic()
        with self.timers(PAGES):
            self._release_window_pages()
            self._ensure_decode_pages()
        return self._read_behind(self._decode_phase() + chunked)

    def _decode_phase(self) -> int:
        """`tick-decode` around _decode_tick. A speculating engine's tick
        is read where it is dispatched (its `tick-read` and `tick-apply`
        lie inside this span), and the span says so."""
        span = self.timers(DECODE)
        if self.spec is None:
            span.start()
        else:
            span.start(synchronous=1)
        try:
            return self._decode_tick()
        finally:
            span.stop()

    def _loop_gap(self) -> None:
        """Called by whoever steps in a loop, before a step: the time
        since the last step's end was the loop's own (its lock, its
        checks, a wait for the interpreter lock), and is booked as the
        phase `loop`. A park sets `_tick_end` None: idle is no phase."""
        if self._tick_end is not None:
            self.timers.record(LOOP, time.perf_counter() - self._tick_end)

    def _end_tick(self) -> None:
        """Book the tick that just ended: its phases' own seconds into the
        counters, and a `serve_slow_tick` into the journal if it took both
        SLOW_TICK_S and SLOW_TICK_OVER_MEDIAN times the median of the last
        ticks (its time runs from the last tick's end in a running loop:
        read to read)."""
        self._tick_end = time.perf_counter()
        seen, total = self._phase_seen, self.stats["tick_phase_s"]
        phases = {}
        for name, own in self.timers.own_s().items():
            took = own - seen.get(name, 0.0)
            if took > 0.0:
                seen[name] = own
                phase = _PHASE_OF[name]
                phases[phase] = took
                total[phase] = total.get(phase, 0.0) + took
                self._m_phase.inc(took, phase=phase)
        drains, self._tick_drains = self._tick_drains, []
        j = _journal.get_global_journal()
        self._gc.watch(j is not None)
        wall = sum(phases.values())
        walls = self._tick_walls
        if (wall > SLOW_TICK_S and len(walls) >= 16
                and wall > SLOW_TICK_OVER_MEDIAN * statistics.median(walls)):
            self.stats["slow_ticks"] += 1
            if j is not None:
                with self._cv:
                    queue = len(self._queue)
                j.emit("serve_slow_tick", tick=self._step_no,
                       wall_s=round(wall, 6),
                       phase_s={k: round(v, 6) for k, v in phases.items()},
                       active=self.num_active, queue=queue, drains=drains,
                       gc_s=round(self._gc.seconds - self._gc_seen, 6),
                       pages_free=self.pool.free_pages)
        self._gc_seen = self._gc.seconds
        walls.append(wall)

    def _pre_tick(self) -> None:
        """Per-tick control-plane work shared by every engine subclass:
        serving fault injection (MEGATRON_TPU_FAULT, tick-indexed — a
        SIGKILLed/hung/slowed replica at a deterministic decode tick, so
        the router's failover paths are testable on CPU), staged weight
        swaps, and deadline expiry."""
        # ticks are counted at their read: the tick about to be
        # dispatched is the one after those read and those in flight
        tick = self.stats["ticks"] + sum(
            1 for rec in self._inflight if rec.task is None)
        resilience.maybe_kill("kill_replica", tick)
        if (not self._preempt_signalled
                and resilience.fault_active("preempt_replica", tick)):
            # once per process: ticks only advance on decode, and a
            # second SIGTERM would hit the server's immediate-exit path
            self._preempt_signalled = True
            resilience.maybe_signal("preempt_replica", tick)
        resilience.maybe_hang("hang_replica", tick)
        resilience.maybe_sleep("slow_tick", journal_once=True)
        self._apply_pending_params()
        self._expire_deadlines()

    # ----- hot weight reload ----------------------------------------------

    def update_params(self, params: Any, version: Any = None
                      ) -> threading.Event:
        """Stage a weight swap; the step loop applies it BETWEEN decode
        ticks, so in-flight slots keep decoding without interruption (their
        KV prefixes were computed by the old weights — a drained rolling
        update keeps per-request token identity; docs/serving.md).

        The new tree must match the old one in structure/shape/dtype and is
        committed with the same placement policy as __init__, so the jitted
        decode step's cache key is unchanged — a swap costs ZERO recompiles
        (the live decode_recompiles counter is the regression gate).

        Returns an Event set once the swap has been applied."""
        def check(old, new):
            if (old.shape, old.dtype) != (new.shape, new.dtype):
                raise ValueError(
                    f"update_params shape/dtype mismatch: {old.shape}/"
                    f"{old.dtype} vs {new.shape}/{new.dtype} — a "
                    "mismatched tree would recompile (or garble) the "
                    "decode step")

        jax.tree.map(check, self.params, params)
        applied = threading.Event()
        committed = self._commit(params)
        with self._cv:
            if self._pending_params is not None:
                # a staged-but-unapplied swap is superseded; its waiter
                # unblocks too (the newer weights subsume the older ones)
                self._pending_params[2].set()
            self._pending_params = (committed, version, applied)
            self._cv.notify_all()
        return applied

    def _apply_pending_params(self) -> None:
        with self._cv:
            pending = self._pending_params
            self._pending_params = None
        if pending is None:
            return
        # a swap is announced (applied.set()) only once every token of
        # the old weights has reached its request
        self._drain("weights")
        new, version, applied = pending
        self.params = new
        self.params_version = version
        self.stats["weight_reloads"] += 1
        self._m_reloads.inc()
        j = _journal.get_global_journal()
        if j is not None:
            j.emit("weight_reload", version=version,
                   active=self.num_active)
        applied.set()

    # ----- deadlines -------------------------------------------------------

    def _expire_deadlines(self) -> None:
        """Fail queued and mid-decode requests past their deadline: their
        waiters unblock with timed_out=True within one tick of expiry
        instead of waiting on an abandoned request forever."""
        now = time.monotonic()
        expired = []
        with self._cv:
            for req in [r for r in self._queue
                        if r._deadline is not None and now > r._deadline]:
                self._queue.remove(req)
                expired.append(req)
            if expired:
                self._m_queue.set(len(self._queue))
        for req in expired:
            self._fail_timeout(req, "queued")

        def late(req):
            return (req is not None and req._deadline is not None
                    and now > req._deadline)

        if any(late(req) for req in self.slots):
            # the tick in flight may be the one that ends the request:
            # read it, then fail whoever is still there
            self._drain("deadline")
        for i in range(self.num_slots):
            req = self.slots[i]
            if late(req):
                # same carry hygiene as _retire (_clear_slot marks it)
                self._clear_slot(i)
                self._m_active.set(self.num_active)
                self._fail_timeout(req, "mid-decode")

    def _fail_timeout(self, req: Request, where: str) -> None:
        req.timed_out = True
        self.stats["timeouts"] += 1
        self._m_timeouts.inc()
        self._journal_request(req, "timeout")
        req._finish(
            f"deadline exceeded while {where} (deadline_s="
            f"{req.deadline_s}, generated {len(req.generated)} of "
            f"{req.max_new_tokens} tokens)")

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until no request is queued, mid-admission, or decoding
        (and no weight swap is pending). The drain step of a rolling
        update: stop routing work here, wait_idle, then reload. Returns
        False if `timeout` seconds pass first."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._cv:
                if (not self._queue and self._admitting == 0
                        and self.num_active == 0 and not self._inflight
                        and not self._reading
                        and self._pending_params is None):
                    return True
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.01)

    def _journal_request(self, req: Request, status: str) -> None:
        """Per-request journal record (when a global journal is set):
        the SLO harness and tools/telemetry_report.py read TTFT/TPOT
        percentiles and failure counts off these."""
        j = _journal.get_global_journal()
        if j is None:
            return
        now = req.finish_time or time.monotonic()
        fields = {"id": req.id, "status": status,
                  "prompt_len": len(req.prompt),
                  "new_tokens": len(req.generated), "chunks": req.chunks,
                  "prefix_tokens": req.prefix_tokens,
                  "preemptions": req.preemptions}
        if req.submit_time is not None:
            # where the request's time went, stamp to stamp: queue_s +
            # prefill_s = ttft_s, and ttft_s + (new_tokens - 1) * tpot_s
            # = wall_s (tpot_s keeps the digits that product needs)
            fields["wall_s"] = round(now - req.submit_time, 6)
            if req.slot_time is not None:
                fields["queue_s"] = round(req.slot_time - req.submit_time, 6)
            if req.first_token_time is not None:
                fields["ttft_s"] = round(
                    req.first_token_time - req.submit_time, 6)
                if req.slot_time is not None:
                    fields["prefill_s"] = round(
                        req.first_token_time - req.slot_time, 6)
                if len(req.generated) > 1:
                    fields["tpot_s"] = round(
                        (now - req.first_token_time)
                        / (len(req.generated) - 1), 9)
        j.emit("serve_request", **fields)
        j.emit("serve_ticks", **self._serve_ticks_fields())
        if self.spec is not None:
            # cumulative speculative counters, one snapshot per retired
            # request (like goodput's cumulative records): the report
            # reads the LAST one for accept rate / tokens-per-forward
            j.emit("serve_spec",
                   proposed=self.stats["spec_proposed"],
                   accepted=self.stats["spec_accepted"],
                   emitted=self.stats["spec_emitted"],
                   ticks=self.stats["ticks"], k=self.spec.k,
                   drafter=self.spec.drafter)

    def _serve_ticks_fields(self) -> dict:
        """The loop's cumulative counters, one `serve_ticks` snapshot per
        retired request like `serve_spec`: a reader takes the LAST one, or
        the difference of two (ahead / ticks is the share of decode ticks
        that were in the queue before the one before them was read; rows /
        ticks the mean decoding batch; phase_s where the loop thread's
        time went, as of the last tick that ended; evicted the pages the
        prefix cache gave back; prefill_blocks and decode_blocks the
        [visited, held] behind the two live-block shares)."""
        stats = self.stats
        fields = {
            "ticks": stats["ticks"],
            "ahead": stats["ticks_dispatched_ahead"],
            "drains": dict(stats["tick_drains"]),
            "dropped_after_eod": stats["tokens_dropped_after_eod"],
            "rows": stats["decode_rows"],
            "phase_s": {k: round(v, 6)
                        for k, v in stats["tick_phase_s"].items()},
            "evicted": stats["pages_evicted"],
            "prefill_blocks": [stats["prefill_blocks_visited"],
                               stats["prefill_blocks_held"]],
            "decode_blocks": [stats["decode_blocks_visited"],
                              stats["decode_blocks_held"]]}
        if self.cfg.holds_expert_share:
            fields["moe_rows"] = [stats["moe_held_rows"], stats["moe_rows"]]
            fields["moe_experts"] = [stats["moe_experts_read"],
                                     stats["moe_experts_offered"]]
        return fields

    def _journal_comm_policy(self) -> None:
        """One `comm_policy` record per engine build: which collectives
        run compressed and the static per-tick wire prices — the journal
        side of the engine_comm_*_bytes_total counters (the report
        derives the compression ratio from either)."""
        j = _journal.get_global_journal()
        if j is None or self.tp_comm is None:
            return
        t = self._comm_tick_bytes
        j.emit("comm_policy", mode=self.tp_comm.mode,
               sites=sorted(self.tp_comm.sites), chunk=self.tp_comm.chunk,
               tp=self.tp_comm.tp,
               dense_bytes_per_tick=t["dense"],
               compressed_bytes_per_tick=t["compressed"],
               ratio=round(t["dense"] / max(t["compressed"], 1), 3))

    def _count_comm(self, bytes_pair) -> None:
        """Advance the compressed-collective byte counters by one
        forward's static wire price ({"dense", "compressed"})."""
        if self.tp_comm is None:
            return
        self.stats["comm_dense_bytes"] += bytes_pair["dense"]
        self.stats["comm_compressed_bytes"] += bytes_pair["compressed"]
        self._m_comm_dense.inc(bytes_pair["dense"])
        self._m_comm_compressed.inc(bytes_pair["compressed"])

    def _decode_rows(self):
        """Slot indices the batched decode serves this tick: not those
        still mid-chunked-prefill, and not a request whose tokens in
        flight fill its max_new_tokens: that finish is a count, known
        before its last token is read."""
        busy = self.prefill_queue.slots
        return [i for i, s in enumerate(self.slots)
                if s is not None and i not in busy
                and len(s.generated) + self._owed[i] < s.max_new_tokens]

    def _decode_extra_args(self):
        """The device page table, between the caches and the carry in the
        decode step's (and the speculative step's) arguments."""
        if self._table_dirty or self._device_table is None:
            # a copy goes up: the host edits the table while the tick it
            # went into may still be in flight
            self._device_table = self._commit_small(self.tables.copy())
            self._table_dirty = False
        return (self._device_table,)

    def _chunk_table_arg(self, row):
        """Device form of one pending table row for the chunk step
        ([1, max_pages] here; the CP engine rebuilds it as per-rank
        local tables sharded over the context axis)."""
        return row[None, :]

    def _note_live_blocks(self, active) -> None:
        """Set `engine_decode_live_block_share` for the tick about to
        run: the trips the decode kernel's loops take over the blocks
        the table holds, from the host's lengths. A decoding row reaches
        the kernel with its new token written (length + 1); every other
        slot with the length the step gives a row its table leaves out
        (`masks.decode_idle_length`): no trip. The decoding rows' live
        blocks over slots x blocks, 1 with every slot at its full length
        or window. The journal's `serve_ticks` carries both counts
        summed over every tick."""
        from megatron_tpu.ops.pallas.flash_template import (
            decode_blocks_visited)
        from megatron_tpu.ops.pallas.masks import decode_idle_length

        sq = self._decode_write_span()
        lens = np.full_like(self.lengths, decode_idle_length(sq))
        lens[active] = self.lengths[active] + 1
        visited, held = decode_blocks_visited(
            lens, self.max_pages, self.page_size, self._kernel_kv_heads(),
            sq=sq, window=self.cfg.attention_kind.sliding_window_size)
        self.stats["decode_blocks_visited"] += visited
        self.stats["decode_blocks_held"] += held
        self.stats["decode_live_block_share"] = visited / held
        self._m_live_blocks.set(visited / held)

    def _kernel_kv_heads(self) -> int:
        """KV heads an attention kernel sees a shard: the model's over the
        mesh's `tensor` axis where they divide (ops/attention.py
        `_shard_plan`), else all of them."""
        tp = (dict(self.mesh.shape).get("tensor", 1)
              if self.mesh is not None else 1)
        kv_heads = self.cfg.n_kv_heads
        return kv_heads // tp if kv_heads % tp == 0 else kv_heads

    def _decode_write_span(self) -> int:
        """Cache positions one decode tick writes per slot: 1 plain,
        k+1 speculative (page allocation is sized off this)."""
        return 1 + self._capacity_margin()

    def _spec_rows_arg(self):
        """Committed device copy of the per-request spec knob mask
        (same caching pattern as the device page table — a
        fresh host upload every tick would flip the arg's committedness
        and split the jit cache key)."""
        if self._spec_rows_dev is None:
            self._spec_rows_dev = self._commit(jnp.asarray(self.spec_on))
        return self._spec_rows_dev

    def _propose_ngram(self) -> np.ndarray:
        """Host-side prompt-lookup proposals for every slot (drafter
        'ngram'): [N, k] int32, zeros for idle / spec-off rows (their
        drafts are dead — acceptance is forced to 0)."""
        from megatron_tpu.inference.speculative import ngram_propose

        k, n = self.spec.k, self.spec.ngram
        drafts = np.zeros((self.num_slots, k), np.int32)
        for i in range(self.num_slots):
            req = self.slots[i]
            if req is None or not self.spec_on[i]:
                continue
            drafts[i] = ngram_propose(
                np.concatenate([np.asarray(req.prompt, np.int32),
                                np.asarray(req.generated, np.int32)]),
                k, n)
        return drafts

    def _init_carry(self):
        """The device-resident decode carry, (re)built from the host
        mirrors after a preemption or a migration dropped it — shared by
        the plain and speculative ticks (ONE layout; a carry change
        must hit both paths by construction)."""
        # copies go up, never the mirrors themselves: the host edits them
        # while the tick they went into may still be in flight
        if self._carry is None:
            self._carry = self._commit_small(
                (self.last_tok.copy(), self.lengths.copy(),
                 self.keys.copy(), self.temps.copy(), self.top_ks.copy(),
                 self.top_ps.copy()))
        elif self._carry_dirty:
            # an event edited lengths or knobs (a retirement, a prompt's
            # end): they go up from the mirrors, which hold every live
            # row's length as of the last dispatch and park the idle rows
            # at 0; last tokens and chains stay the device's
            last, _, keys, _, _, _ = self._carry
            lens, temps, top_ks, top_ps = self._commit_small(
                (self.lengths.copy(), self.temps.copy(),
                 self.top_ks.copy(), self.top_ps.copy()))
            self._carry = (last, lens, keys, temps, top_ks, top_ps)
        self._carry_dirty = False
        return self._carry

    def _fail_decode(self, active, e) -> None:
        """Decode-step failure recovery shared by the plain and
        speculative ticks: fail the in-flight requests (their waiters
        must unblock), drop the carry, and restore usable caches —
        donation may have consumed every cache tree. Under asynchronous
        dispatch a device failure surfaces at a dispatch or at the read
        of a later tick: the requests of every tick in flight fail with
        those of `active`, once, and nothing of those ticks is read."""
        rows = set(active)
        for rec in self._inflight:
            rows.update(i for i, req in rec.rows if self.slots[i] is req)
        self._drop_inflight()
        for i in sorted(rows):
            req = self.slots[i]
            self._clear_slot(i)
            req._finish(f"decode step failed: {e}")
        self._m_active.set(self.num_active)
        self._carry = None
        self._rebuild_caches()

    def _decode_tick_spec(self, active) -> int:
        """One speculative decode tick: propose k drafts per slot
        (host n-gram lookup, or the in-step draft-model scan), ONE
        [N, k+1] target verify forward, exact in-step accept/reject,
        then emit 1..k+1 tokens per slot. Rejected drafts roll back by
        the per-slot length alone — their K/V entries sit past the new
        length, masked off and overwritten next tick."""
        spec = self.spec
        last, lens, keys, temps, top_ks, top_ps = self._init_carry()
        pre = (self.params, self.caches)
        if self._has_draft_model():
            pre += (self.draft_params, self.draft_caches)
        pre += self._decode_extra_args()
        tail = (last, lens, keys, temps, top_ks, top_ps,
                self._spec_rows_arg())
        if spec.drafter == "ngram":
            with self.timers(PROPOSE):
                drafts = self._propose_ngram()
            tail += (self._commit(jnp.asarray(drafts)),)
        t_tick = time.monotonic()
        try:
            out = self._spec_step(*pre, *tail)
            if self._has_draft_model():
                (toks, lps, accepts, caches, dcaches, keys, lens,
                 last) = out
                self.draft_caches = dcaches
            else:
                toks, lps, accepts, caches, keys, lens, last = out
            self.caches = caches
            self._carry = (last, lens, keys, temps, top_ks, top_ps)
            with self.timers(READ):
                # the speculative tick is read where it is dispatched
                toks, lps, accepts = jax.device_get((toks, lps, accepts))
        except Exception as e:  # noqa: BLE001 - shared recovery, then
            # surface the error to the driver
            self._fail_decode(active, e)
            raise
        with self.timers(APPLY):
            return self._apply_spec(active, toks, lps, accepts, t_tick)

    def _apply_spec(self, active, toks, lps, accepts, t_tick) -> int:
        """A speculative tick's tokens to their requests."""
        spec = self.spec
        self.stats["ticks"] += 1
        self.stats["decode_rows"] += len(active)
        self._m_ticks.inc()
        self._m_rows.inc(len(active))
        self._m_tick.observe(time.monotonic() - t_tick)
        self._track_decode_recompiles()
        if self.flight_recorder is not None:
            self.flight_recorder.heartbeat(
                f"spec tick {self.stats['ticks']} ({len(active)} active)")
        emitted_total = 0
        for i in active:
            req = self.slots[i]
            a = int(accepts[i])
            # device-side truth: the fed token + a accepted drafts are
            # now valid cache entries; toks[i, a] is next up
            self.lengths[i] += a + 1
            self.last_tok[i] = int(toks[i, a])
            if self.spec_on[i]:
                self.stats["spec_proposed"] += spec.k
                self.stats["spec_accepted"] += a
                self._m_spec_proposed.inc(spec.k)
                self._m_spec_accepted.inc(a)
                self._m_spec_len.observe(a)
            for j in range(a + 1):
                req.generated.append(int(toks[i, j]))
                req.logprobs.append(float(lps[i, j]))
                emitted_total += 1
                if self._req_finished(req):
                    # eod or max_new mid-speculation: later accepted
                    # tokens are "after the end" — a non-speculative
                    # run would never have produced them. The slot
                    # retires below, which resets the (now past-end)
                    # device mirrors with full carry hygiene.
                    break
            if self._req_finished(req):
                self._retire(i)
        self.stats["spec_emitted"] += emitted_total
        self._m_tokens.inc(emitted_total)
        self.last_progress_time = time.monotonic()
        return len(active)

    def _decode_tick(self) -> int:
        """One batched decode for every decodable slot; returns how many
        were served (0 = nothing to decode). The plain tick is dispatched
        here and read later (_read); the speculative tick is whole here:
        the host needs its accepts to know the lengths, the pages and,
        for the n-gram drafter, the tokens of the next one."""
        active = self._decode_rows()
        if not active:
            return 0
        self._note_live_blocks(active)
        if self.spec is not None:
            return self._decode_tick_spec(active)
        carry = self._init_carry()
        ahead = any(rec.task is None for rec in self._inflight)
        t_tick = time.monotonic()
        try:
            toks, lps, self.caches, self.state, keys, lens, *counts = (
                self._decode_step(
                    self.params, self.caches, self.state,
                    *self._decode_extra_args(), *carry,
                    *self._counts_arg()))
            self._step_counts, = counts or (None,)
        except Exception as e:  # noqa: BLE001 - shared recovery, then
            # surface the error to the driver
            self._fail_decode(active, e)
            raise
        # toks/lens/keys chain into the next tick on device; the sampled
        # tokens and logprobs cross to the host when the tick is read, in
        # one fetch whose copy starts behind the step
        self._carry = (toks, lens, keys, *carry[3:])
        out = self._start_fetch((toks, lps if self.want_logprobs else None,
                                 self._step_counts))
        # every decoding row's length grows by exactly 1 a tick, so the
        # next tick's pages, window and live blocks need none of this
        # one's tokens: the fed token is in the cache once the step runs
        self.lengths[active] += 1
        self._owed[active] += 1
        self._inflight.append(_InFlight(
            rows=[(i, self.slots[i]) for i in active], out=out,
            step=self._step_no, t0=t_tick, ahead=ahead))
        return len(active)

    def _counts_arg(self):
        return () if self._step_counts is None else (self._step_counts,)

    def _apply_counts(self, counts) -> None:
        """The device's counts so far (_MOE_COUNTS), as a read step's
        fetch brought them: the counters move by what is new since the
        last read (the device's numbers wrap at 2**32; the difference
        does not care)."""
        if counts is None:
            return
        new = counts - self._counts_seen
        self._counts_seen = counts
        for (key, _, _), metric, n in zip(_MOE_COUNTS, self._m_moe, new):
            metric.inc(int(n))
            self.stats[key] += int(n)

    @staticmethod
    def _start_fetch(out):
        """Start the copy to the host of a program's results, behind the
        program in the device's queue: the read finds them there."""
        for a in jax.tree.leaves(out):
            if isinstance(a, jax.Array):
                a.copy_to_host_async()
        return out

    def _fetch(self, rec: _InFlight):
        """A program's results on the host, in one fetch. This is where
        the loop waits for the device, and where a failed step surfaces."""
        try:
            with self.timers(READ):
                return jax.device_get(rec.out)
        except Exception as e:  # noqa: BLE001 - shared recovery, then
            # surface the error to the driver
            self._inflight.appendleft(rec)  # its requests fail with the rest
            self._fail_decode((), e)
            raise

    def _read(self, rec: _InFlight) -> None:
        """Read one dispatched decode tick: its tokens and logprobs reach
        their requests, and those that end retire. A row applies only if
        its slot still holds the request it was dispatched for: a row that
        ended by eod ran one tick more than it should (only a finish by
        eod needs the token), and that tick's result for it is dropped
        here; its one extra KV position lies in a page the row owned. A
        prompt's last chunk (rec.task) is read by _read_first."""
        if rec.task is not None:
            return self._read_first(rec)
        toks, lps, counts = self._fetch(rec)
        with self.timers(APPLY):
            self._apply(rec, toks, lps)
            self._apply_counts(counts)

    def _read_first(self, rec: _InFlight) -> None:
        """Read a finished prompt's first token: record it and the
        prompt's logprobs, and register the prompt's full pages in the
        radix tree."""
        tok, lp, plps, counts = self._fetch(rec)
        with self.timers(APPLY):
            self._apply_first(rec, tok, lp, plps)
            self._apply_counts(counts)

    def _apply_first(self, rec: _InFlight, tok, lp, plps) -> None:
        (i, req), = rec.rows
        task = rec.task
        rec.out = None  # the device's copies go here, not between phases
        if self.slots[i] is not req:   # the rule of every row in flight
            self.pool.release(rec.pinned)
            return
        self._owed[i] -= 1
        self.last_tok[i] = int(tok)
        req.generated.append(int(tok))
        req.logprobs.append(float(lp))
        if not task.resumed and self.want_logprobs:
            req.prompt_logprobs = [
                float(x) for x in np.concatenate(plps)[:task.total - 1]
            ] if plps else []
        if rec.pinned:
            # only FULL pages of the ORIGINAL prompt enter the tree (the
            # partially-filled tail page stays private — decode writes
            # into it); resumes re-register recomputed pages, and insert
            # skips paths already cached. The tree holds its own
            # references now: the pin goes
            self.prefix_cache.insert(req.prompt, rec.pinned,
                                     req.prompt_logprobs)
            self.pool.release(rec.pinned)
            self._m_pages_free.set(self.pool.free_pages)
        now = time.monotonic()
        self._m_prefill.observe(now - task.t_start)
        if not task.resumed:
            req.first_token_time = now
            if req.submit_time is not None:
                self._m_ttft.observe(now - req.submit_time)
        self._m_tokens.inc()
        self.last_progress_time = now
        if self._req_finished(req):
            self._retire(i)

    def _apply(self, rec: _InFlight, toks, lps) -> None:
        """A read tick's tokens to their requests."""
        rec.out = None  # the device's copies go here, not between phases
        now = time.monotonic()
        self.stats["ticks"] += 1
        self.stats["decode_rows"] += len(rec.rows)
        self._m_ticks.inc()
        self._m_rows.inc(len(rec.rows))
        if rec.ahead:
            self.stats["ticks_dispatched_ahead"] += 1
            self._m_ahead.inc()
        # one tick's wall time: from its dispatch, or, for a tick that
        # queued behind another, from that one's read
        self._m_tick.observe(now - max(rec.t0, self._last_read_time))
        self._last_read_time = now
        self._count_comm(self._comm_tick_bytes)
        self._track_decode_recompiles()
        if self.flight_recorder is not None:
            self.flight_recorder.heartbeat(
                f"tick {self.stats['ticks']} ({len(rec.rows)} active)")
        toks = toks.tolist()
        lps = lps.tolist() if lps is not None else None
        applied = 0
        for i, req in rec.rows:
            if self.slots[i] is not req:
                continue
            applied += 1
            self._owed[i] -= 1
            self.last_tok[i] = toks[i]   # the sampled one is next up
            req.generated.append(toks[i])
            req.logprobs.append(lps[i] if lps is not None else 0.0)
            if self._req_finished(req):
                self._retire(i)
        self._m_tokens.inc(applied)
        dropped = len(rec.rows) - applied
        if dropped:
            self.stats["tokens_dropped_after_eod"] += dropped
            self._m_dropped.inc(dropped)
        self.last_progress_time = time.monotonic()

    def stalled(self, threshold_s: float) -> bool:
        """True when the engine has pending work (active slots or queued
        requests) but has made no progress for `threshold_s` — the hung-
        step-loop signal readiness probes use. An IDLE engine is never
        stalled, however long it sits."""
        with self._cv:
            busy = (self.num_active > 0 or bool(self._queue)
                    or self._admitting > 0 or bool(self._inflight))
        return (busy and
                time.monotonic() - self.last_progress_time > threshold_s)

    def capture_trace(self, out_dir: str, ticks: int = 4,
                      timeout_s: float = 30.0) -> dict:
        """On-demand profiler capture of >= `ticks` decode ticks under
        live traffic (the /admin/profile endpoint; docs/observability.md
        "Runtime traces").

        Runs entirely on the CALLER's thread: jax's profiler session is
        process-global, so bracketing start/stop around the step loop
        from outside traces every device op the loop dispatches — the
        loop itself has NO per-tick check, no extra traced args (zero
        decode recompiles) and zero steady-state overhead when no
        capture is armed. Tick progress is read off ``stats["ticks"]``;
        an idle engine makes no ticks, so the window closes at
        `timeout_s` with whatever it saw (``complete`` says which).
        """
        if not _PROFILE_LOCK.acquire(blocking=False):
            raise RuntimeError(
                "a profiler capture is already in progress (the jax "
                "profiler traces the whole process; retry when it ends)")
        try:
            start_ticks = self.stats["ticks"]
            t0 = time.monotonic()
            capture.start(out_dir)
            try:
                while (self.stats["ticks"] - start_ticks < ticks
                       and time.monotonic() - t0 < timeout_s):
                    time.sleep(0.005)
            finally:
                capture.stop()
        finally:
            _PROFILE_LOCK.release()
        captured = self.stats["ticks"] - start_ticks
        return {"dir": out_dir, "ticks": int(captured),
                "requested_ticks": int(ticks),
                "complete": captured >= ticks,
                "wall_s": round(time.monotonic() - t0, 3)}

    def _track_decode_recompiles(self) -> None:
        """Enforce the zero-recompiles-after-warmup invariant as a live
        counter: the decode step's jit cache may grow by exactly ONE entry
        (warmup); any growth past that means a traced-vs-static leak crept
        in (e.g. a sampling knob going static) and every further tick is
        paying a compile."""
        step = self._spec_step if self.spec is not None else self._decode_step
        try:
            size = int(step._cache_size())
        except Exception:  # noqa: BLE001 - private API; tracking degrades
            return
        if size > self._decode_cache_seen:
            grew = size - self._decode_cache_seen
            if self._decode_cache_seen >= 1:  # beyond the warmup compile
                self.stats["decode_recompiles"] += grew
                self._m_recompiles.inc(grew)
            self._decode_cache_seen = size

    # ----- state migration (fleet/migration.py wire format) ----------------

    @contextlib.contextmanager
    def paused(self, timeout: float = 60.0):
        """Park the step loop BETWEEN ticks, with nothing in flight (the
        loop reads what it has dispatched before it parks), so the caller
        may touch slot state (request export/import). Counting, so nested
        pauses compose; a no-op when no loop thread is running (tests and
        batch drivers call step() themselves, and the export/import entry
        points drain). Raises if the loop does not
        reach a tick boundary within `timeout` — a wedged device step,
        which the caller must not race."""
        with self._cv:
            self._pause_count += 1
            self._cv.notify_all()
        try:
            t = self._thread
            if (t is not None and t.is_alive()
                    and threading.current_thread() is not t):
                if not self._paused_evt.wait(timeout):
                    raise RuntimeError(
                        f"engine step loop did not pause within {timeout}s "
                        "(wedged device step?)")
            yield
        finally:
            with self._cv:
                self._pause_count -= 1
                if self._pause_count == 0:
                    self._paused_evt.clear()
                self._cv.notify_all()

    def _kv_geometry(self) -> dict:
        """The cache facts an importer must match (or fall back on)."""
        cfg = self.cfg
        return {
            "layers": int(cfg.num_layers),
            "kv_heads": int(cfg.n_kv_heads),
            "head_dim": int(cfg.head_dim),
            "dtype": jnp.empty((0,), cfg.dtype).dtype.name,
            "int8": bool(self.kv_cache_int8),
            "sliding_window": cfg.attention_kind.sliding_window_size,
        }

    def _pack_kv_sections(self, leaves: List[np.ndarray], length: int
                          ) -> Tuple[dict, Dict[str, np.ndarray]]:
        """Encode canonical-layout KV leaves (each [L, T, H, D] host
        arrays, T = committed positions) into wire sections + a codec
        descriptor. Three codecs:

          int8-native  the int8 cache's own quantized pages + per-position
                       scales ride verbatim — exact w.r.t. what the source
                       would have decoded from (ops/kv_quant.py recipe)
          raw          float caches ship native bytes — exact
          int8 / fp8   opt-in lossy chunked wire (quant/primitives.py,
                       self.kv_wire) — ~2-4x fewer bytes, exact=False, so
                       a token-identity importer recompute-resumes
        """
        from megatron_tpu.quant import primitives as qp

        geo = self._kv_geometry()
        sections: Dict[str, np.ndarray] = {}
        if self.kv_cache_int8:
            k_q, v_q, k_s, v_s = leaves
            sections.update(kv_k=k_q, kv_v=v_q,
                            kv_k_scale=k_s, kv_v_scale=v_s)
            codec, exact = "int8-native", True
        elif self.kv_wire in ("int8", "fp8"):
            mode = self.kv_wire
            if mode == "fp8" and not qp.fp8_supported():
                mode = "int8"  # same gate as compressed collectives
            chunk = qp.effective_chunk(geo["head_dim"], self.kv_wire_chunk)
            for name, leaf in zip(("kv_k", "kv_v"), leaves):
                q, s = qp.quantize_chunked(jnp.asarray(leaf), chunk, mode)
                sections[name] = np.asarray(q)
                sections[name + "_scale"] = np.asarray(s)
            geo["wire_chunk"] = int(chunk)
            codec, exact = mode, False
        else:
            sections.update(kv_k=np.asarray(leaves[0]),
                            kv_v=np.asarray(leaves[1]))
            codec, exact = "raw", True
        meta = dict(geo, codec=codec, exact=exact, length=int(length))
        return meta, sections

    def _decode_kv_sections(self, kv: dict, sections: Dict[str, np.ndarray]
                            ) -> List[np.ndarray]:
        """Wire sections -> canonical host leaves matching THIS engine's
        cache tuple arity (inverse of _pack_kv_sections)."""
        codec = kv["codec"]
        if codec == "int8-native":
            return [sections[n] for n in
                    ("kv_k", "kv_v", "kv_k_scale", "kv_v_scale")]
        if codec == "raw":
            return [sections["kv_k"], sections["kv_v"]]
        from megatron_tpu.quant import primitives as qp

        dt = jnp.empty((0,), self.cfg.dtype).dtype
        return [np.asarray(qp.dequantize_chunked(
                    jnp.asarray(sections[n]),
                    jnp.asarray(sections[n + "_scale"]), dt))
                for n in ("kv_k", "kv_v")]

    def _export_slot_kv(self, i: int):
        """Gather slot i's pages into the canonical [L, T, H, D] wire
        layout. None when any page of the span is gone (sliding-window
        release parked it on scratch) — there is no exact KV to ship, so
        the importer recompute-resumes from the migrated tokens (exact
        under the deterministic position-based window mask)."""
        length = int(self.lengths[i])
        ps = self.page_size
        if length <= 0:
            return None
        n_pages = -(-length // ps)
        row = self._pending_rows.get(i, self.tables[i])
        pages = [int(p) for p in row[:n_pages]]
        if any(p == SCRATCH_PAGE for p in pages):
            return None
        host = kv_store.export_span(jax.device_get(self.caches), pages,
                                    length)
        return self._pack_kv_sections(host, length)

    def export_request_state(self, req: Request, include_kv: bool = True
                             ) -> Tuple[dict, Dict[str, np.ndarray]]:
        """Snapshot one request's FULL resumable state: tokens (prompt +
        generated), sampling knobs, seed, remaining deadline, PRNG chain
        + absolute position, and (for a decoding slot) its KV pages.
        Token-identity contract: an importer resuming from this snapshot
        emits exactly the tokens this engine would have — greedy AND
        sampled, because the chain keys migrate. Call with the step loop
        paused (self.paused()) or from the driver thread: what is in
        flight is read first (the parked loop has read it already)."""
        if include_kv:
            self._refuse_state_transfer("KV export (migration)")
        self._drain("migration")
        meta: Dict[str, Any] = {
            "kind": "request",
            "prompt": [int(t) for t in np.asarray(req.prompt).tolist()],
            "generated": [int(t) for t in req.generated],
            "logprobs": [float(x) for x in req.logprobs],
            "prompt_logprobs": [float(x) for x in req.prompt_logprobs],
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": float(req.temperature),
            "top_k": int(req.top_k),
            "top_p": float(req.top_p),
            "eod": None if req.eod is None else int(req.eod),
            "seed": int(req.seed),
            "spec": bool(req.spec),
        }
        if req._deadline is not None:
            meta["deadline_remaining_s"] = round(
                max(req._deadline - time.monotonic(), 0.001), 6)
        sections: Dict[str, np.ndarray] = {}
        slot = next((i for i, s in enumerate(self.slots) if s is req), None)
        mid_prefill = (slot is not None
                       and slot in self.prefill_queue.slots)
        if slot is not None and not mid_prefill:
            self._sync_carry("migration")
            sections["resume_key"] = np.asarray(self.keys[slot],
                                                np.uint32).copy()
            meta["position"] = int(self.lengths[slot])
            if include_kv:
                kv = self._export_slot_kv(slot)
                if kv is not None:
                    meta["kv"], kv_sections = kv[0], kv[1]
                    sections.update(kv_sections)
        elif req.resume_key is not None:
            # queued-but-previously-preempted: the chain survives even
            # though no slot state does (chunked prefills never consume
            # PRNG before the final chunk, so this resume stays exact)
            sections["resume_key"] = np.asarray(req.resume_key,
                                                np.uint32).copy()
        self.stats["kv_exports"] += 1
        self._m_kv_exports.inc()
        return meta, sections

    def export_all_requests(self, include_kv: bool = True
                            ) -> List[Tuple[Request, dict,
                                            Dict[str, np.ndarray]]]:
        """Atomically REMOVE every queued and active request and return
        [(live request, meta, sections), ...]. The engine is empty
        afterwards (a drain completes immediately); the caller owns
        completing or failing each returned Request — their waiters are
        still blocked on req.done."""
        out: List[Tuple[Request, dict, Dict[str, np.ndarray]]] = []
        with self.paused():
            self._sync_carry("migration")
            for i in range(self.num_slots):
                req = self.slots[i]
                if req is None or req.done.is_set():
                    continue
                meta, sections = self.export_request_state(
                    req, include_kv=include_kv)
                self._clear_slot(i)
                out.append((req, meta, sections))
            self._m_active.set(self.num_active)
            with self._cv:
                queued = list(self._queue)
                self._queue.clear()
                self._m_queue.set(0)
            for req in queued:
                if req.done.is_set():
                    continue
                meta, sections = self.export_request_state(
                    req, include_kv=False)
                out.append((req, meta, sections))
        return out

    def _kv_import_compatible(self, kv: dict) -> Tuple[bool, str]:
        """Whether a transferred KV state can be installed DIRECTLY into
        this engine's cache (vs recompute-resume). (ok, reason)."""
        if self.mesh is not None:
            return False, "direct KV install on mesh engines is not wired"
        if self._has_draft_model():
            return False, "draft-model cache migration is not wired"
        geo = self._kv_geometry()
        for k in ("layers", "kv_heads", "head_dim"):
            if int(kv.get(k, -1)) != geo[k]:
                return False, f"geometry mismatch on {k}"
        codec = kv.get("codec")
        if codec == "int8-native":
            if not self.kv_cache_int8:
                return False, "int8-native transfer into a float cache"
        elif codec == "raw":
            if self.kv_cache_int8:
                return False, "raw transfer into an int8 cache"
            if kv.get("dtype") != geo["dtype"]:
                return False, "cache dtype mismatch"
        elif codec in ("int8", "fp8"):
            if self.kv_cache_int8:
                return False, "lossy wire into an int8 cache"
        else:
            return False, f"unknown codec {codec!r}"
        if int(kv["length"]) + self._capacity_margin() >= self.max_seq_len:
            return False, "migrated context exceeds this engine's capacity"
        return True, ""

    def _free_slot_for_import(self) -> Optional[int]:
        for i in range(self.num_slots):
            if self.slots[i] is None:
                return i
        return None

    def _kv_install_writer(self):
        """Once-jitted kv_store.install: a canonical [L, T, ...] block
        into the pool at a TRACED page. Static shapes, its own jit —
        repeated imports never grow the decode step's cache (the
        zero-decode-recompiles invariant holds through migration)."""
        if self._kv_writer is None:
            self._kv_writer = jax.jit(
                kv_store.install,
                donate_argnums=(0,) if self._donate() else ())
        return self._kv_writer

    def _install_request_kv(self, req: Request, kv: dict,
                            sections) -> bool:
        """Paged install: allocate the span's pages, write each through
        the once-jitted page writer, publish the table row, and re-enter
        the prompt's full pages into the radix tree — the migrated
        request's prefix lineage survives the hop, so followers sharing
        its prompt hit on THIS replica too."""
        i = self._free_slot_for_import()
        if i is None:
            return False
        length = int(kv["length"])
        ps = self.page_size
        n_pages = -(-length // ps)
        pages = self._alloc_pages(n_pages)
        if pages is None:
            return False
        leaves = self._decode_kv_sections(kv, sections)
        writer = self._kv_install_writer()
        self._sync_carry("migration")
        for j, pg in enumerate(pages):
            self.caches = writer(self.caches,
                                 kv_store.span_block(leaves, j, ps),
                                 jnp.int32(pg))
        row = np.zeros(self.max_pages, np.int32)
        row[:n_pages] = pages
        self.tables[i] = row
        self._table_dirty = True
        self._admit_counter += 1
        self._admit_seq[i] = self._admit_counter
        self._arm_imported_slot(i, req, length)
        p0 = len(req.prompt)
        if p0 >= ps and req.prompt_logprobs:
            # radix-prefix lineage: same full-pages-only rule as
            # _finish_prefill (the tail page is private — decode writes it)
            self.prefix_cache.insert(
                req.prompt, [int(p) for p in row[:p0 // ps]],
                req.prompt_logprobs)
        self._m_pages_free.set(self.pool.free_pages)
        return True

    def _arm_imported_slot(self, i: int, req: Request, length: int) -> None:
        """An installed slot's bookkeeping: the
        migrated request continues decoding at its absolute position with
        its migrated PRNG chain — no prefill, no re-sample."""
        req.submit_time = time.monotonic()
        if req.deadline_s is not None:
            req._deadline = req.submit_time + req.deadline_s
        self.slots[i] = req
        self.lengths[i] = length
        self.last_tok[i] = int(req.generated[-1])
        self.temps[i] = req.temperature
        self.top_ks[i] = req.top_k
        self.top_ps[i] = req.top_p
        self.keys[i] = np.asarray(req.resume_key, np.uint32)
        if self.spec is not None:
            self.spec_on[i] = bool(req.spec)
            self._spec_rows_dev = None
        self.stats["admitted"] += 1
        self._m_admitted.inc()
        self._m_active.set(self.num_active)
        self.last_progress_time = time.monotonic()
        with self._cv:
            self._cv.notify_all()  # wake an idle step loop

    def import_request_state(self, meta: dict,
                             sections: Dict[str, np.ndarray],
                             allow_inexact: bool = False
                             ) -> Tuple[Request, str]:
        """Rebuild a migrated request in THIS engine. Returns (req, path):
        path "kv_import" = the transferred pages were installed and decode
        continues at the migrated position; "recompute" = the request
        re-enters through submit() and teacher-forces prompt + generated
        (recompute-resume — exact, just re-spends prefill FLOPs). Both
        paths are token-identical to the uninterrupted source run unless
        the wire codec was lossy AND allow_inexact let it through. Journals
        a `serve_migrate` stage="import" row naming the path taken."""
        req = Request(
            prompt=np.asarray(meta["prompt"], np.int32),
            max_new_tokens=int(meta["max_new_tokens"]),
            temperature=float(meta.get("temperature", 0.0)),
            top_k=int(meta.get("top_k", 0)),
            top_p=float(meta.get("top_p", 0.0)),
            eod=meta.get("eod"),
            seed=int(meta.get("seed", 0)),
            deadline_s=meta.get("deadline_remaining_s"),
            spec=bool(meta.get("spec", True)))
        req.generated = [int(t) for t in meta.get("generated", [])]
        req.logprobs = [float(x) for x in meta.get("logprobs", [])]
        req.prompt_logprobs = [float(x) for x in
                               meta.get("prompt_logprobs", [])]
        if req.generated and len(req.generated) >= req.max_new_tokens:
            raise ValueError("migrated request is already complete")
        if "resume_key" in sections:
            req.resume_key = np.asarray(sections["resume_key"], np.uint32)
        kv = meta.get("kv")
        if kv is not None:
            self._refuse_state_transfer("KV import (migration)")
        path, reason = "recompute", ""
        if kv is None:
            reason = "no KV in transfer"
        elif not req.generated or req.resume_key is None:
            reason = "no decode state rode along"
        elif int(kv["length"]) != len(req.prompt) + len(req.generated) - 1:
            reason = "inconsistent migrated position"
        elif not (kv.get("exact") or allow_inexact):
            reason = f"lossy wire codec {kv.get('codec')}"
        else:
            ok, reason = self._kv_import_compatible(kv)
            if ok:
                with self.paused():
                    self._drain("migration")
                    if self._install_request_kv(req, kv, sections):
                        path = "kv_import"
                    else:
                        reason = "no free slot/pages for a direct install"
        if path == "recompute":
            # recompute-resume: the preempt-and-resume exactness
            # machinery (resume_key + generated teacher-forcing) is the
            # universal fallback — it only needs tokens and the chain
            self.submit(req)
        self.stats["kv_imports"] += 1
        self._m_kv_imports.inc(path=path)
        j = _journal.get_global_journal()
        if j is not None:
            fields = {"stage": "import", "path": path,
                      "prompt_len": len(req.prompt),
                      "generated": len(req.generated)}
            if kv is not None:
                fields["codec"] = kv.get("codec")
                fields["exact"] = bool(kv.get("exact"))
            if reason:
                fields["fallback_reason"] = reason
            j.emit("serve_migrate", **fields)
        return req, path

    # ----- fleet prefix directory (cross-replica radix sharing) ------------

    def export_prefix_state(self, tokens):
        """Package the radix-cached whole-page prefix of `tokens` for
        replication to a peer: (meta, sections) in the migration wire
        vocabulary (kind="prefix"), or None when nothing is cached."""
        self._refuse_state_transfer("the fleet's prefix directory")
        toks = [int(t) for t in tokens]
        with self.paused():
            self._drain("migration")
            pages, lps = self.prefix_cache.lookup(toks)
            if not pages:
                return None
            ps = self.page_size
            span = len(pages) * ps
            host = kv_store.export_span(jax.device_get(self.caches),
                                        [int(p) for p in pages], span)
            kv_meta, sections = self._pack_kv_sections(host, span)
        meta = {"kind": "prefix", "tokens": toks[:span], "kv": kv_meta}
        # per-node logprob slices concatenate back into the engine's
        # (position-1)-indexed prompt_logprobs layout for tokens[1:span]
        sections["prefix_logprobs"] = (
            np.concatenate([np.asarray(x, np.float32) for x in lps])
            if lps else np.zeros(0, np.float32))
        return meta, sections

    def import_prefix_state(self, meta: dict, sections) -> int:
        """Install replicated prefix pages into this pool + radix tree.
        Returns pages added (0 = incompatible, lossy, or already
        cached). Only EXACT codecs enter the tree — a lossy prefix would
        silently poison every future request that hits it."""
        self._refuse_state_transfer("the fleet's prefix directory")
        kv = meta.get("kv") or {}
        ok, _ = self._kv_import_compatible(kv)
        if not ok or not kv.get("exact"):
            return 0
        toks = [int(t) for t in meta.get("tokens", [])]
        span = int(kv.get("length", 0))
        ps = self.page_size
        if span <= 0 or span % ps != 0 or span > len(toks):
            return 0
        n_pages = span // ps
        with self.paused():
            self._drain("migration")
            have, _ = self.prefix_cache.lookup(toks)
            if len(have) >= n_pages:
                return 0  # the local copy stays authoritative
            pages = self._alloc_pages(n_pages)
            if pages is None:
                return 0
            leaves = self._decode_kv_sections(kv, sections)
            writer = self._kv_install_writer()
            for j, pg in enumerate(pages):
                self.caches = writer(self.caches,
                                     kv_store.span_block(leaves, j, ps),
                                     jnp.int32(pg))
            lp = np.asarray(sections.get("prefix_logprobs",
                                         np.zeros(0)), np.float32)
            added = self.prefix_cache.insert(toks[:span], pages, lp)
            # insert() retained the refs the tree owns; drop the
            # allocation refs so the pages become cache-only (evictable
            # under pressure), and so pages skipped as already-cached
            # free immediately
            self.pool.release(pages)
            self._m_pages_free.set(self.pool.free_pages)
        return added

    # ----- driving ---------------------------------------------------------

    def _mesh_scope(self):
        import contextlib

        return (jax.sharding.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def run_until_idle(self) -> None:
        """Step until the queue and every slot drain (single-thread use:
        tests, benches, batch jobs). Returns with nothing in flight: a
        step that finds nothing to dispatch reads what is, and is not 0
        until that is nothing."""
        self._tick_end = None
        with self._mesh_scope():
            while True:
                self._loop_gap()
                served = self.step()
                with self._cv:
                    if served == 0 and not self._queue:
                        return

    def generate(self, prompts: np.ndarray, lengths: np.ndarray,
                 max_new_tokens: int, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0,
                 eod: Optional[int] = None, seed: int = 0,
                 deadline_s: Optional[float] = None,
                 spec: bool = True,
                 request_id: Optional[str] = None,
                 ) -> GenerationOutput:
        """Batch convenience with generate_tokens' semantics: submit one
        request per row, drain, and repack [B, maxp+max_new] (rows padded
        with eod/0 past their end). The one-shot jitted loop runs EVERY
        row of a ragged batch to maxp + max_new_tokens, so shorter
        prompts get the difference as extra generated tokens — matched
        here so flipping a server between engine and one-shot mode never
        changes a response. `request_id` names the rows' requests in the
        journal (`<id>/<k>` where there are several); the output's
        `engine_s` runs from the first row's submit to the last one's
        end."""
        B, maxp = prompts.shape
        reqs = []
        # the queue-capacity check and the B submits happen under ONE
        # lock acquisition (the Condition lock is an RLock, so submit()
        # re-entering it is fine): a batch that can't fully queue is
        # rejected BEFORE submitting anything — otherwise the admitted
        # rows would decode to completion only to have their output
        # discarded when the rejected row raises below, burning decode
        # capacity exactly when the engine is overloaded. Checking and
        # submitting under separate acquisitions would let two
        # concurrent batches both pass the check and then trip the
        # per-row rejection mid-submission anyway.
        with self._cv:
            if (self.max_queue is not None
                    and len(self._queue) + B > self.max_queue):
                self.stats["rejected"] += B
                self._m_rejected.inc(B)
                raise EngineOverloadedError(
                    f"engine queue cannot take {B} more requests "
                    f"(max_queue={self.max_queue}); retry later")
            for b in range(B):
                p = int(lengths[b])
                reqs.append(self.submit(Request(
                    prompt=np.asarray(prompts[b, :p], np.int32),
                    max_new_tokens=maxp - p + max_new_tokens,
                    temperature=temperature, deadline_s=deadline_s,
                    top_k=top_k, top_p=top_p, eod=eod, seed=seed + b,
                    spec=spec,
                    id=(request_id if B == 1 or request_id is None
                        else f"{request_id}/{b}"))))
        if self._thread is None:
            self.run_until_idle()
        for r in reqs:
            r.done.wait()
        if any(r.overloaded for r in reqs):
            raise EngineOverloadedError(
                next(r.error for r in reqs if r.overloaded))
        if any(r.timed_out for r in reqs):
            raise RequestTimeoutError(
                next(r.error for r in reqs if r.timed_out))
        errs = [r.error for r in reqs if r.error]
        if errs:
            raise ValueError(errs[0])
        total = maxp + max_new_tokens
        pad = 0 if eod is None else eod
        tokens = np.full((B, total), pad, np.int32)
        ends = np.zeros(B, np.int64)
        lp = np.zeros((B, total - 1), np.float32)
        for b, r in enumerate(reqs):
            t = r.tokens
            tokens[b, :len(t)] = t
            ends[b] = len(t)
            # teacher-forced prompt region then generated tokens, matching
            # the one-shot path's row layout (lp[i] scores token i+1)
            lp[b, :len(r.prompt_logprobs)] = r.prompt_logprobs
            gen0 = int(lengths[b]) - 1  # logprob row index of first token
            lp[b, gen0:gen0 + len(r.logprobs)] = r.logprobs
        return GenerationOutput(
            tokens=tokens, lengths=ends, logprobs=lp,
            engine_s=(max(r.finish_time for r in reqs)
                      - min(r.submit_time for r in reqs)))

    # ----- background thread (HTTP serving) --------------------------------

    def start(self) -> None:
        """Spawn the step-loop thread: concurrent submitters share each
        decode tick."""
        if self._thread is not None:
            return
        self._stop = False

        def loop():
            with self._mesh_scope():
                while True:
                    with self._cv:
                        # nothing parks with a tick unread: with one in
                        # flight the loop goes on to read it (below, or
                        # in a step that finds nothing to dispatch)
                        while (not self._stop and not self._inflight
                               and (self._pause_count > 0
                                    or (self.num_active == 0
                                        and not self._queue
                                        and self._pending_params is None))):
                            self._tick_end = None  # a park is no tick's
                            if self._pause_count > 0:
                                # state-migration pause: park between
                                # ticks and tell the pauser slot state is
                                # safe to touch (bounded wait — resume
                                # notifies, the timeout is a backstop)
                                self._paused_evt.set()
                                self._cv.wait(timeout=0.5)
                                continue
                            if self.flight_recorder is not None:
                                # an IDLE engine is healthy, not hung: keep
                                # beating (bounded wait) or the watchdog
                                # dumps a spurious stall bundle — fatally
                                # so under flight_recorder_abort
                                self.flight_recorder.heartbeat("idle")
                                self._cv.wait(timeout=1.0)
                            else:
                                self._cv.wait()
                        self._paused_evt.clear()
                        stop = self._stop
                        park = self._pause_count > 0
                    try:
                        if stop or park:
                            self._drain("stop" if stop else "pause")
                        else:
                            self._loop_gap()
                            self.step()
                    except Exception as e:  # noqa: BLE001 - step() has
                        # already failed the affected requests; the loop
                        # must survive to serve the next ones (a dead
                        # driver thread would hang every future submit)
                        import traceback

                        print(f"inference-engine step error: {e}",
                              file=sys.stderr)
                        traceback.print_exc()
                    if stop:
                        return

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="inference-engine")
        self._thread.start()

    def stop(self) -> None:
        """Stop the step-loop thread and fail whatever it leaves behind:
        waiters on in-flight or still-queued requests block on done.wait()
        with no timeout, so every abandoned request must be signalled or
        its thread hangs forever."""
        if self._thread is None:
            return
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            # a stalled device step still owns the slot state — tearing
            # it down now would race the zombie and let a later start()
            # spawn a second concurrent step loop
            raise RuntimeError(
                "inference-engine step loop did not stop within 30s")
        self._thread = None
        self._gc.watch(False)
        self._drop_inflight()  # read by the loop on its way out; a failure
        # there leaves nothing either
        with self._cv:
            leftovers = list(self._queue)
            self._queue.clear()
        for i in range(self.num_slots):
            req = self.slots[i]
            if req is not None:
                self._clear_slot(i)
                req._finish("engine stopped")
        for req in leftovers:
            req._finish("engine stopped")
        with self._cv:
            if self._pending_params is not None:
                # unblock a reload waiter — the swap will never be applied
                self._pending_params[2].set()
                self._pending_params = None
        self._carry = None
        self._m_active.set(0)
        self._m_queue.set(0)
