"""DecodeEngine — continuous-batching autoregressive decode serving.

The generation counterpart of :class:`~deeplearning4j_tpu.parallel.
inference.ParallelInference`: where that engine batches INDEPENDENT
one-shot forwards, this one multiplexes LONG-LIVED sequences at
different positions into one static-shape KV cache.

Engine loop (one worker thread, the decode analog of the reference's
batching observable):

* **admit** — pending requests (fail-fast admitted through the shared
  :class:`~deeplearning4j_tpu.core.resilience.AdmissionController`; full
  window sheds with ``AdmissionRejectedError`` -> HTTP 503 + Retry-After)
  claim free cache slots. Each prefills at a BUCKETED prompt length
  (``session.bucket_sizes()``, mirroring the server's batch buckets) and
  its 1-row carry is scattered into the slot — arriving requests never
  stall sequences mid-generation for longer than one prefill.
* **step** — ONE ``[B, 1]`` forward advances every active slot (rows at
  completely different positions share the compiled step; idle/finished
  rows are frozen by an active mask), per-row seeded sampling picks each
  next token, and tokens stream to per-request event queues. The loop
  runs ONE STEP AHEAD of the host (ISSUE 30): the tokens a step samples
  stay on the device and feed the next step there, and the host fetches
  and emits step k only after it has dispatched step k+1, so the emit
  loop, the sweep and the uploads run while the device computes. What the
  host knows ahead it uses (a row whose request ends with the token in
  flight sits the next step out); what it cannot know (an eos, a cancel,
  a deadline) costs that row one step whose token is dropped, never
  emitted. Where the next action needs the tokens on the host, the loop
  lands the step in flight first (a speculative turn, a paged preemption,
  going idle, a failure).
* **retire** — eos / ``max_tokens`` / ``max_len`` complete a request;
  an expired :class:`Deadline` terminates it cleanly mid-stream with
  partial output (reason "deadline"); a cancelled handle (client
  disconnect) frees its slot on the next loop turn. Retirement releases
  the admission slot — cache capacity is never leaked to dead clients.

* **speculate** — with ``draft_model=`` (ISSUE 11), each turn runs the
  draft ``k+1`` times at ``[B, 1]``, verifies the ``k`` proposals with
  ONE ``tq=k+1`` target forward, and commits through exact acceptance
  sampling — output law identical to plain decode (greedy streams
  token-for-token), ~accepted+1 tokens per target-model serial round.
  Both caches rewind to the committed frontier inside the fused step;
  rows near ``max_len`` (or with per-request ``speculative_k=0``) take
  the plain path in the same turn. :class:`DecodeAIMD` adapts the
  current ``k`` and the active-slot admission target against a
  per-token p95 budget (``adaptive=True``).

Failures run through a :class:`CircuitBreaker`: a poisoned decode step
fails the affected requests and opens the breaker, so new submits shed
instead of queueing behind a broken jit.

Observability: ``dl4j_tpu_generate_tokens_total``, per-token decode
latency + prefill latency histograms and an in-flight-sequences gauge in
the registry; traced requests get ``engine.queue_wait``,
``engine.prefill`` and ``engine.decode`` child spans in ``/v1/traces``.
Every pass of the loop that has work is itself a ``loop.turn`` trace of
the engine's tracer (children ``loop.admit`` > ``loop.prefill`` >
``loop.prefill.dispatch``, and ``loop.install`` for a handed-over row;
``loop.step`` > ``loop.upload``/``loop.dispatch`` of this turn's step, then
``loop.fetch``/``loop.emit`` of the step before and again of each of this
turn's prefills' first tokens; ``loop.sweep``),
head-sampled like a request and taken whole while a ``jax.profiler``
session collects (README "Tracing"). What a turn costs the host in device
calls it says itself: ``uploads``, ``programs`` and ``fetches`` on the
turn (ISSUE 37: a step is one upload and one program, an admitted prompt
one of each more). And whether the device ran dry under it (ISSUE 38,
:class:`_DryAccount`): some ten times a turn the loop asks the newest
program it enqueued whether it has finished, which brackets each
interval in which the device's queue was empty between two host
timestamps and names the phase the host was in; ``dry_ms``,
``dry_slack_ms`` and ``dry_<phase>_ms`` on the turn, three counters and
``stats()["loop"]`` carry it, traced or not.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import Counter, deque
from functools import partial
from typing import Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.resilience import (
    AdmissionController,
    CircuitBreaker,
    CircuitOpenError,
    CircuitState,
    Deadline,
)
from ..generate.paged import (
    BlockAllocator,
    OutOfBlocksError,
    attach_block_table,
    block_bytes,
    blocks_needed,
    detach_block_table,
    freeze_rows,
    mask_inactive_writes,
    paged_decode_state,
)
from ..generate.sampling import PATHS, sample_tokens, sampler_path
from ..generate.session import (ROW_SPEC_WORDS, GenerationSession,
                                SpeculativeGenerationSession, pack_row_spec)
from ..ops.flash_attention import decode_fetched_entries
from ..ops.paged_attention import pack_row_blocks
from ..obs.metrics import MetricsRegistry, get_registry
from ..obs.compiles import watch_compiles
from ..obs.tracing import (NULL_SPAN, Tracer, current_context, get_tracer,
                           trace_now)

_engine_seq = itertools.count()
# the dry account's clock: a name of the module, so that a test can script it
_now = time.perf_counter

_OUTCOMES = ("completed", "deadline", "cancelled", "shed", "failed",
             "circuit_rejected")
# what the loop asks of the device, by kind of call (the counter's label),
# and what a turn's span calls its count of each
_CALLS = ("upload", "program", "fetch")
_CALL_ATTRS = ("uploads", "programs", "fetches")
_UPLOAD, _PROGRAM, _FETCH = range(3)
# the phases of a turn that the dry account tells apart: the ``loop.<phase>``
# spans (a prefill's and an install's lie in ``admit``), and ``step`` for
# ``loop.step``'s own time; what a turn's span calls each phase's share
_PHASES = ("admit", "select", "upload", "dispatch", "account", "fetch",
           "emit", "sweep", "step")
_DRY_ATTRS = {phase: f"dry_{phase}_ms" for phase in _PHASES}
# rows of the emit loop between two looks at the device's queue
_EMIT_LOOK_ROWS = 32


class GenerationHandle:
    """Per-request streaming handle: the engine pushes ``{"token", "index"}``
    events and one terminal ``{"done": True, "reason", "count"}`` event;
    the consumer iterates :meth:`events` (a server handler streams them as
    chunks) or blocks on :meth:`result`. :meth:`cancel` (e.g. on client
    disconnect) asks the engine to retire the request and free its cache
    slot at the next loop turn."""

    def __init__(self, request_id: str, deadline: Deadline) -> None:
        self.request_id = request_id
        self.deadline = deadline
        self.tokens: List[int] = []
        self.reason: Optional[str] = None
        self._events: "queue.Queue[dict]" = queue.Queue()
        self._done = threading.Event()
        self._cancelled = threading.Event()
        self._cb_lock = threading.Lock()
        self._on_done: List[Callable[["GenerationHandle"], None]] = []

    # ----- engine side -----
    def _emit(self, index: int, token: int) -> None:
        self.tokens.append(int(token))
        self._events.put({"token": int(token), "index": int(index)})

    def _finish(self, reason: str, error: Optional[str] = None) -> None:
        self.reason = reason
        ev = {"done": True, "reason": reason, "count": len(self.tokens)}
        if error:
            ev["error"] = error
        self._events.put(ev)
        with self._cb_lock:
            self._done.set()
            cbs = list(self._on_done)
            self._on_done.clear()
        for cb in cbs:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — a callback never kills the loop
                pass

    def add_done_callback(
            self, fn: Callable[["GenerationHandle"], None]) -> None:
        """Run ``fn(handle)`` when the terminal event lands (immediately
        if it already has) — race-free: registration and the done flag
        share one lock, so the callback fires exactly once. A replica
        pool uses this to release its admission slot."""
        with self._cb_lock:
            if not self._done.is_set():
                self._on_done.append(fn)
                return
        try:
            fn(self)
        except Exception:  # noqa: BLE001
            pass

    # ----- consumer side -----
    def cancel(self) -> None:
        self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def events(self, timeout: Optional[float] = None):
        """Yield events in order until (and including) the terminal one."""
        while True:
            ev = self._events.get(timeout=timeout)
            yield ev
            if ev.get("done"):
                return

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout=timeout):
            raise TimeoutError("generation not finished")
        return list(self.tokens)


class _Request:
    __slots__ = ("prompt", "max_tokens", "eos_id", "handle", "seed",
                 "greedy", "temp", "top_k", "top_p", "spec_k", "trace_ctx",
                 "t_submit", "t_decode_start", "prefilled", "seq")

    def __init__(self, prompt, max_tokens, eos_id, handle, seed, greedy,
                 temp, top_k, top_p, spec_k, trace_ctx,
                 prefilled=None) -> None:
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.eos_id = eos_id
        self.handle = handle
        self.seed = seed
        self.greedy = greedy
        self.temp = temp
        self.top_k = top_k
        self.top_p = top_p
        self.spec_k = spec_k  # None = follow the engine's adaptive k
        self.prefilled = prefilled  # disagg handoff payload (or None)
        self.trace_ctx = trace_ctx
        self.t_submit = trace_now()
        self.t_decode_start = 0.0
        self.seq = -1  # per-engine sequence number, stamped at enqueue


class _InFlight:
    """A decode step dispatched and not yet fetched: its tokens on the
    device, the rows it stepped, the request that held each row when it was
    dispatched (a row whose request has ended since is dropped at the emit),
    the time its upload began, and what the model's layers counted of the
    step (``{leaf: sums}`` on the device, ``{}`` for a model that counts
    nothing)."""

    __slots__ = ("toks", "rows", "reqs", "t0", "counts")

    def __init__(self, toks, rows, reqs, t0, counts) -> None:
        self.toks = toks
        self.rows = rows
        self.reqs = reqs
        self.t0 = t0
        self.counts = counts


class _DryAccount:
    """The loop thread's account of the device's queue, one turn at a time.

    The device's queue is empty when the newest program the loop enqueued
    has finished (``DecodeEngine._device_dry``: ``is_ready()`` of an output
    of it; no wait, 0.3 us alone and 3-11 us inside the running loop on the
    chip's host, PERF.md PR 38). A *look* asks that where it can tell
    something: where the turn starts and ends, before each program the loop
    enqueues, when a fetch has returned, every 32 rows of the emit loop and
    at its end; the other boundaries only *mark* the phase.
    The look that first finds the queue empty, at host time ``t1``, says
    that the device ran dry between the look before it (``t0``, not dry)
    and ``t1``; nothing runs there again before the loop next enqueues a
    program, at ``t2``. ``t2 - t1`` is the interval's **lower bound**,
    split over the phases it spans (``by_phase``, seconds), and ``t1 -
    t0`` its **slack**: the device's true idle time lies between the lower
    bound and the lower bound plus the slack (and a program starts some
    tenths of a millisecond after its dispatch returns, which neither
    holds). Only a turn counts: an engine parked in ``loop.wait`` is idle,
    not starved."""

    __slots__ = ("engine", "newest", "phase", "t_turn", "t_look", "t_dry",
                 "slack", "by_phase")

    def __init__(self, engine: "DecodeEngine", newest) -> None:
        self.engine = engine
        # an output of the newest program enqueued that no later program
        # donates (the token vector, nearly always)
        self.newest = newest
        self.phase = _PHASES[0]
        self.t_turn = self.t_look = 0.0
        # while an interval is open: the time up to which it is counted
        self.t_dry: Optional[float] = None
        self.slack = 0.0
        self.by_phase: dict = {}

    def begin(self) -> None:
        """A turn starts, with its first look: what came before it (a
        ``loop.wait``, the loop's own check) is no part of any interval."""
        self.t_turn = self.t_look = _now()
        self.t_dry, self.slack, self.by_phase = None, 0.0, {}
        self.look(_PHASES[0])

    def _count(self, now: float) -> None:
        if now > self.t_dry:
            self.by_phase[self.phase] = \
                self.by_phase.get(self.phase, 0.0) + now - self.t_dry

    def look(self, phase: Optional[str] = None) -> None:
        """Look at the queue; what follows is ``phase`` (or the phase it
        was)."""
        now = _now()
        if self.t_dry is not None:  # still dry: nothing was enqueued since
            self._count(now)
            self.t_dry = now
        elif self.engine._device_dry():
            self.slack += now - self.t_look
            self.t_dry = now
        self.t_look = now
        if phase is not None:
            self.phase = phase

    def mark(self, phase: str) -> None:
        """What follows is ``phase``. An open interval is counted up to
        here under the phase it was; the device is asked nothing."""
        if self.t_dry is not None:
            now = _now()
            self._count(now)
            self.t_dry = now
        self.phase = phase

    def enqueued(self, out=None) -> None:
        """The loop has enqueued a program, of which ``out`` is an output:
        an open interval ends here. Without ``out``: the next call enqueues
        and fetches by itself (a speculative step), so the interval ends
        before it and the call's end is told with its output."""
        now = _now()
        if self.t_dry is not None:
            self._count(now)
            self.t_dry = None
        self.t_look = now
        if out is not None:
            self.newest = out

    def end(self) -> float:
        """The turn ends, with its last look: an interval still open is
        counted up to here, and the next turn's first look finds it again.
        Returns the turn's wall seconds."""
        self.look()
        return self.t_look - self.t_turn


def live_state_bytes(layers: "Counter", positions: np.ndarray,
                     itemsize: int) -> "Counter":
    """Bytes of the decode state that rows standing at ``positions`` have
    made valid, by kind of entry, summed over ``layers`` (``{layer: how many
    of it}``). Every row at once: a layer's bytes are arithmetic on the
    position, so an array of positions gives an array (or one number that
    is every row's)."""
    live: Counter = Counter()
    for layer, count in layers.items():
        for kind, n in layer.decode_live_bytes(positions, itemsize).items():
            live[kind] += count * int(
                np.broadcast_to(n, positions.shape).sum())
    return live


def install_row(carry, row, i):
    """A one-row carry into row ``i`` of the batch carry: one static
    ``dynamic_update_slice`` a leaf, in place where the carry is donated."""
    def put(c, r):
        z = jnp.zeros((), i.dtype)
        return jax.lax.dynamic_update_slice(
            c, r.astype(c.dtype), (i,) + (z,) * (c.ndim - 1))

    return jax.tree_util.tree_map(put, carry, row)


def paged_install(carry, row, dest, slot, block_size: int):
    """A one-row STATIC carry into the paged batch carry: each cache plane
    packed into block units and scattered at the slot's block ids (``dest``,
    static length max_len/bs: the unallocated tail is id 0, so pad blocks
    land in trash); the position counters at ``slot``."""
    out = {}
    for name, st in carry.items():
        r = row[name]
        new_st = dict(st)
        for key, pool in st.items():
            if key == "pos":
                new_st[key] = jax.lax.dynamic_update_slice(
                    pool, r["pos"].astype(pool.dtype), (slot,))
            else:
                packed = pack_row_blocks(r[key][0], block_size)
                new_st[key] = pool.at[dest].set(packed.astype(pool.dtype))
        out[name] = new_st
    return out


class DecodeEngine:
    def __init__(
        self,
        model,
        *,
        max_len: int = 256,
        slots: int = 8,
        default_timeout: Optional[float] = None,
        default_max_tokens: int = 64,
        admission: Optional[AdmissionController] = None,
        queue_limit: int = 64,
        circuit_breaker: Optional[CircuitBreaker] = None,
        clock: Callable[[], float] = time.monotonic,
        registry: Optional[MetricsRegistry] = None,
        name: Optional[str] = None,
        tracer: Optional[Tracer] = None,
        step_hook: Optional[Callable[[], None]] = None,
        draft_model=None,
        speculative_k: int = 4,
        adaptive: bool = False,
        target_p95_s: float = 0.05,
        adjust_interval: float = 0.5,
        cache_dtype: Optional[str] = None,
        block_size: Optional[int] = None,
        num_kv_blocks: Optional[int] = None,
    ) -> None:
        """``draft_model=`` turns on speculative decoding: the draft
        proposes up to ``speculative_k`` tokens per step, one tq=k+1
        target forward verifies them, and exact acceptance sampling keeps
        the output law (greedy streams token-identical to plain decode).
        ``adaptive=True`` runs the decode-side AIMD controller
        (:class:`DecodeAIMD`): the current ``k`` and the active-slot
        target adapt against ``target_p95_s`` per-token latency, ticked
        every ``adjust_interval`` seconds on the engine loop
        (``adjust_interval=0`` -> manual :meth:`adjust`).
        ``cache_dtype="int8"`` stores the attention KV caches quantized
        (per-slot/per-head scales on the carry; dequant inside the decode
        attention) — the same cache HBM budget holds ~2× the concurrent
        sequences of an fp16 cache, at a bounded logit error that
        ``tests/test_quantize.py`` holds to a greedy token-match rate.
        ``block_size=`` switches the cache to the PAGED layout (ISSUE
        17): fixed-size blocks in a shared per-layer pool of
        ``num_kv_blocks`` (default: the static layout's capacity,
        ``slots * max_len / block_size`` plus the trash block) with
        per-row block tables, allocated at admit, grown as rows advance
        and freed at retire/cancel — a resident sequence costs blocks
        for its USED tokens, not ``max_len``, so short sequences stop
        paying for headroom they never touch. Greedy streams are
        token-identical to the static layout; composes with
        ``cache_dtype="int8"`` (per-block scale planes)."""
        if draft_model is not None:
            self._spec = SpeculativeGenerationSession(
                model, draft_model, max_len=max_len,
                k=max(1, int(speculative_k)), cache_dtype=cache_dtype)
            self.session = self._spec.target
        else:
            self._spec = None
            self.session = GenerationSession(model, max_len=max_len,
                                             cache_dtype=cache_dtype)
        self.cache_dtype = cache_dtype
        self.max_len = int(max_len)
        self.slots = int(slots)
        # paged KV cache config (None = static slot×max_len layout)
        self.block_size = None if block_size is None else int(block_size)
        if self.block_size is not None:
            if self.block_size < 1:
                raise ValueError("block_size must be >= 1")
            if self.max_len % self.block_size:
                raise ValueError(
                    f"max_len {self.max_len} not divisible by block_size "
                    f"{self.block_size}")
            self.num_kv_blocks = (
                self.slots * (self.max_len // self.block_size) + 1
                if num_kv_blocks is None else int(num_kv_blocks))
        else:
            self.num_kv_blocks = None
        self.default_timeout = default_timeout
        self.default_max_tokens = int(default_max_tokens)
        self._clock = clock
        self._tracer = tracer  # None -> process-global at call time
        # test seam: runs after each decode step's tokens were emitted
        self._step_hook = step_hook
        self.name = name or f"decode-{next(_engine_seq)}"
        self._admission = admission or AdmissionController(
            max_pending=queue_limit, clock=clock)
        self._breaker = circuit_breaker or CircuitBreaker(clock=clock)
        # decode-side AIMD knobs: current speculation depth (clamped to
        # the construction-time ceiling) and the active-slot target
        self.max_speculative_k = (max(1, int(speculative_k))
                                  if self._spec is not None else 0)
        self._spec_k = self.max_speculative_k
        self._slot_target = self.slots
        # what the layers say of their decode state beyond its shape: the
        # window after which a mixer folds its entries into summaries, and
        # the layers that count the bytes a row's position has made valid
        # (a layer is its configuration: equal layers are counted once)
        self._window = self.session.window
        self._count_cols = self.session.count_columns()
        self._live_layers = Counter(
            l for l in self.session.model.layers
            if l.decode_live_bytes(0, 1))
        # whether a plain step's attention is the single-query kernel over
        # the static planes (what the two kv_entries counters describe)
        self._static_kv = (self.block_size is None and cache_dtype != "int8"
                           and any(l.pages_decode_planes
                                   for l in self.session.model.layers))
        self._init_metrics(registry if registry is not None else get_registry())

        # device-side batch state: one preallocated carry, per-row specs.
        # The carry is DONATED to every program that takes one and returns
        # one (decode step, both installs), so it is updated in place; the
        # paged layout's block table is held beside it (``_table``), never
        # inside: one buffer under twelve layers cannot be donated twelve
        # times
        self._table, self._table_stale = None, False
        # this turn's device calls by kind (``_CALLS``), counted where they
        # are made
        self._calls = [0, 0, 0]
        if self.block_size is not None:
            self._allocator = BlockAllocator(self.num_kv_blocks)
            # host image of every row's block list (pushed to the device
            # as one shared [slots, max_len/bs] array on change)
            self._block_tables = np.zeros(
                (self.slots, self.max_len // self.block_size), np.int32)
            self._nblocks = np.zeros((self.slots,), np.int32)
            self._block_bytes = block_bytes(self.session, self.block_size)
            self._push_tables()
        else:
            self._allocator = None
        self._carry = self._fresh_carry()
        self._row = None  # a zeroed one-row carry, made when first asked for
        # the draft cache stays static (slot×max_len): proposals run every
        # slot each turn, and the draft rows rewind with the target's
        self._draft_carry = (None if self._spec is None
                             else self._spec.draft.decode_state(self.slots))
        self._draft_row = (None if self._spec is None
                           else self._spec.draft.decode_state(1))
        self._aux_kv_bytes = int(sum(
            l.size * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(self._draft_carry)))
        if self._allocator is None:
            # static layout: resident bytes are the preallocated carry
            self._kv_cache_bytes = self._aux_kv_bytes + int(sum(
                l.size * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(self._carry)))
            self._g_kv_bytes.set(self._kv_cache_bytes)
        else:
            self._update_kv_bytes()
        self._active = np.zeros((self.slots,), bool)
        self._last = np.zeros((self.slots,), np.int32)
        # tokens each row's request has emitted or has in flight (the
        # sampling key's position), advanced when a step is dispatched, and
        # the count at which the request is complete
        self._steps = np.zeros((self.slots,), np.int32)
        self._limit = np.zeros((self.slots,), np.int32)
        # every row's next input token, on the device: what the last step
        # sampled, and the first token of each prefill since, written there
        # beside the install. ``_fresh`` marks the rows whose token the host
        # set instead (``_last``: a speculative turn commits on the host)
        self._toks = jnp.zeros((self.slots,), jnp.int32)
        self._fresh = np.zeros((self.slots,), bool)
        # whether, for how long and under which phase of a turn the device
        # ran dry (``_toks`` is the newest program's output wherever the
        # loop does not say otherwise)
        self._dry = _DryAccount(self, self._toks)
        # the step dispatched and not yet fetched, and the first tokens of
        # the turn's admissions, in their order, dispatched and not yet
        # fetched: (slot, request, token on the device, what the layers
        # counted of the prompt, when its prefill's dispatch began)
        self._flight: Optional[_InFlight] = None
        self._first: List[tuple] = []
        self._seeds = np.zeros((self.slots,), np.uint32)
        self._greedy = np.ones((self.slots,), bool)
        self._temps = np.ones((self.slots,), np.float32)
        self._ks = np.zeros((self.slots,), np.int32)
        self._ps = np.ones((self.slots,), np.float32)
        # cache frontier (next write position) per slot, advanced when a
        # step is dispatched, and the per-request speculation cap (-1 =
        # follow the engine's current k)
        self._pos = np.zeros((self.slots,), np.int64)
        self._spec_caps = np.full((self.slots,), -1, np.int32)
        self._requests: List[Optional[_Request]] = [None] * self.slots
        self.aimd = DecodeAIMD(self, target_p95_s=target_p95_s)
        self._adaptive = bool(adaptive)
        self._adjust_interval = float(adjust_interval)
        self._next_adjust = clock() + self._adjust_interval

        self._pending: "deque[_Request]" = deque()
        # the loop spans' counts: requests enqueued (``req``), passes of
        # the loop that had work (``turn``), requests finished (``retired``)
        self._n_submitted = 0
        self._n_turns = 0
        self._n_finished = 0
        watch_compiles()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._shutdown = False
        self._draining = False
        self._fns = {}
        self._thread = threading.Thread(
            target=self._loop, name=f"{self.name}-loop", daemon=True)
        self._thread.start()

    # ----- metrics ----------------------------------------------------
    def _init_metrics(self, reg: MetricsRegistry) -> None:
        self.registry = reg
        inst = self.name
        req = reg.counter(
            "dl4j_tpu_generate_requests_total",
            "Generation requests by outcome", ("instance", "outcome"))
        self._c = {o: req.labels(inst, o) for o in _OUTCOMES}
        self._c_tokens = reg.counter(
            "dl4j_tpu_generate_tokens_total",
            "Tokens emitted across all generation requests",
            ("instance",)).labels(inst)
        self._g_inflight = reg.gauge(
            "dl4j_tpu_generate_in_flight_sequences",
            "Generation requests admitted and not yet finished",
            ("instance",)).labels(inst)
        self._g_active = reg.gauge(
            "dl4j_tpu_generate_active_slots",
            "Cache slots currently decoding", ("instance",)).labels(inst)
        self._h_decode = reg.histogram(
            "dl4j_tpu_generate_decode_latency_seconds",
            "Per-step decode latency, from the start of the step's upload to "
            "its tokens on the host (one continuous-batched step emits one "
            "token per active sequence; the loop runs one step ahead, so the "
            "emit of the step before lies inside it)",
            ("instance",)).labels(inst)
        self._h_prefill = reg.histogram(
            "dl4j_tpu_generate_prefill_latency_seconds",
            "Prompt prefill latency (bucketed length, batch of one): from "
            "its dispatch to its first token on the host, fetched once the "
            "turn's step is dispatched",
            ("instance",)).labels(inst)
        self._h_token = reg.histogram(
            "dl4j_tpu_generate_token_latency_seconds",
            "Per-emitted-token latency per sequence (a step's upload to "
            "its tokens on the host, divided by the tokens that sequence "
            "committed — the AIMD control signal)",
            ("instance",)).labels(inst)
        self._c_spec_steps = reg.counter(
            "dl4j_tpu_generate_spec_steps_total",
            "Speculative propose/verify steps executed",
            ("instance",)).labels(inst)
        self._c_spec_proposed = reg.counter(
            "dl4j_tpu_generate_spec_proposed_total",
            "Draft tokens proposed for verification",
            ("instance",)).labels(inst)
        self._c_spec_accepted = reg.counter(
            "dl4j_tpu_generate_spec_accepted_total",
            "Draft tokens accepted by the target",
            ("instance",)).labels(inst)
        self._g_spec_k = reg.gauge(
            "dl4j_tpu_generate_speculative_k",
            "Current speculation depth (0 = speculative decoding off)",
            ("instance",)).labels(inst)
        self._g_spec_k.set(self._spec_k)
        self._g_slot_target = reg.gauge(
            "dl4j_tpu_generate_slot_target",
            "AIMD active-slot target (admission fills at most this many "
            "cache slots)", ("instance",)).labels(inst)
        self._g_slot_target.set(self._slot_target)
        self._c_rebuilds = reg.counter(
            "dl4j_tpu_decode_carry_rebuilds_total",
            "Decode carries rebuilt after a donated step or install "
            "raised at run time and took the carry with it (0 in a sound "
            "window)", ("instance",)).labels(inst)
        self._c_ahead = reg.counter(
            "dl4j_tpu_decode_steps_ahead_total",
            "Decode steps dispatched while the step before was still "
            "unfetched (over the decode latency histogram's count: the "
            "share of steps that overlapped the host's work)",
            ("engine",)).labels(inst)
        self._c_dropped = reg.counter(
            "dl4j_tpu_decode_dropped_row_steps_total",
            "Row-steps computed for a request that had ended (eos, cancel, "
            "deadline) by the time the step's tokens were fetched: the "
            "price of running one step ahead; the token is never emitted",
            ("engine",)).labels(inst)
        self._c_kv_attended = reg.counter(
            "dl4j_tpu_decode_kv_entries_attended_total",
            "Cache entries that the rows of the dispatched decode steps "
            "attend, a layer and plane: the sum over a step's rows of "
            "position + 1 (engines whose attention reads the static "
            "[slots, heads, max_len, d] planes; 0 for the others)",
            ("engine",)).labels(inst)
        self._c_kv_fetched = reg.counter(
            "dl4j_tpu_decode_kv_entries_fetched_total",
            "Cache entries that the flash_decode kernel moves out of HBM "
            "for the same rows: whole blocks up to each row's position "
            "(ops.flash_attention.decode_fetched_entries); attended over "
            "fetched is the share of the kernel's bytes that are valid",
            ("engine",)).labels(inst)
        calls = reg.counter(
            "dl4j_tpu_decode_device_calls_total",
            "What the engine's loop asked of the device, by kind of call: "
            "upload (a host array sent), program (a jitted program "
            "dispatched), fetch (an array brought to the host). A decode "
            "step is one upload and one program, an admitted prompt one of "
            "each more, a landed step or first token one fetch (two where "
            "the model's layers count)", ("engine", "kind"))
        self._c_calls = [calls.labels(inst, kind) for kind in _CALLS]
        self._c_loop_s = reg.counter(
            "dl4j_tpu_decode_loop_seconds_total",
            "Wall seconds the engine's loop spent inside turns (passes "
            "with work; a parked engine counts nothing): what the two "
            "device_dry counters are shares of", ("engine",)).labels(inst)
        dry = reg.counter(
            "dl4j_tpu_decode_device_dry_seconds_total",
            "Seconds inside the loop's turns in which the device's queue "
            "was empty, as a lower bound: from the look that first found "
            "the newest program finished to the loop's next enqueue, by "
            "the phase of the turn the host was in (admit, select, upload, "
            "dispatch, account, fetch, emit, sweep; step: loop.step's own "
            "time)", ("engine", "phase"))
        self._c_dry = {phase: dry.labels(inst, phase) for phase in _PHASES}
        self._c_dry_slack = reg.counter(
            "dl4j_tpu_decode_device_dry_slack_seconds_total",
            "Seconds between the look that first found the device's queue "
            "empty and the look before it: the device ran dry somewhere "
            "in there, so its idle time is the lower bound plus at most "
            "this", ("engine",)).labels(inst)
        sampler = reg.counter(
            "dl4j_tpu_decode_sampler_steps_total",
            "Decode steps dispatched, by the work their rows' sampling "
            "specs asked of the sampler (generate.sampling.sampler_path, "
            "the expression the step's program branches on, over the "
            "arrays the step uploads): argmax (every active row greedy: "
            "no sort, no noise), sample (a temperature draw, no sort), "
            "sort (a sampling row has top-k or top-p: one sort over the "
            "vocabulary)", ("engine", "path"))
        self._c_sampler = [sampler.labels(inst, path) for path in PATHS]
        self._c_windows = reg.counter(
            "dl4j_tpu_decode_windows_closed_total",
            "Windows that decoding rows closed (a row's position reached a "
            "multiple of its mixer's window: the window's chunks became "
            "summaries that later positions attend); prompts' windows are "
            "not counted (the loop.prefill span has them)",
            ("engine",)).labels(inst)
        self._c_moe_choices = reg.counter(
            "dl4j_tpu_moe_choices_total",
            "Expert choices of the tokens a served model's expert layers "
            "routed (decode steps and prefills, every layer), by where "
            "they went: held (a routed expert this engine holds: the "
            "token-expert pairs computed here), absent (a routed expert "
            "of another chip's share: nothing is computed for it here), "
            "zero (a zero-compute expert)", ("engine", "kind"))
        self._c_moe_expert = reg.counter(
            "dl4j_tpu_moe_expert_tokens_total",
            "Token-expert pairs each held expert computed, summed over "
            "the layers (the shape of the load)", ("engine", "expert"))
        self._g_state_bytes = reg.gauge(
            "dl4j_tpu_decode_state_bytes",
            "Bytes of the decode state that the active rows' positions have "
            "made valid, by kind of entry as the layers name them (a "
            "windowed mixer: window, summary; latent attention: latent; "
            "grouped-query attention: kv; a short convolution's rolling "
            "columns: conv, the same at every position); host arithmetic, "
            "once a loop turn", ("engine", "kind"))
        self._g_kv_bytes = reg.gauge(
            "dl4j_tpu_generate_kv_cache_bytes",
            "Live resident bytes of the decode KV cache: the full "
            "preallocated carry for the static layout, allocated blocks "
            "x block bytes (+ the static draft cache) for the paged one "
            "— updated on admit/grow/retire, so the gauge tracks what "
            "resident sequences actually hold", ("instance",)).labels(inst)

    @property
    def tracer(self) -> Tracer:
        return self._tracer if self._tracer is not None else get_tracer()

    # ----- paged block accounting (engine-loop thread only) ------------
    def _update_kv_bytes(self) -> None:
        used = self._allocator.total_blocks - self._allocator.free_blocks
        self._kv_cache_bytes = used * self._block_bytes + self._aux_kv_bytes
        self._g_kv_bytes.set(self._kv_cache_bytes)

    def _update_state_bytes(self) -> None:
        """The live share of the decode state from the rows' positions."""
        size = jnp.dtype(self.session.model.dtype).itemsize
        for kind, n in live_state_bytes(
                self._live_layers, self._pos[self._active], size).items():
            self._g_state_bytes.labels(self.name, kind).set(n)

    def _push_tables(self) -> None:
        """Mirror the host block tables onto the device as ONE shared
        ``[slots, max_len/bs]`` array (same shape/dtype every push — no
        recompiles), which the step attaches under every paged layer. Of a
        copy: the host image is written again while a step that was given
        the table may still be in flight."""
        self._table = jnp.asarray(self._block_tables.copy())
        self._table_stale = False
        self._calls[_UPLOAD] += 1

    @property
    def _table_width(self) -> int:
        """Block ids a row's list holds (0 for the static layout)."""
        return 0 if self._allocator is None else self._block_tables.shape[1]

    def _device_table(self):
        """The block table for a program that takes it: pushed here if a
        row's block list changed since the last push, so once a turn at
        most, however many rows grew, were admitted or retired."""
        if self._table_stale:
            self._push_tables()
        return self._table

    def _fresh_carry(self):
        """A zeroed batch carry: the static per-layer caches, or the
        paged pools without their block tables (``_table`` holds the one
        shared table)."""
        if self._allocator is None:
            return self.session.decode_state(self.slots)
        return detach_block_table(paged_decode_state(
            self.session, self.slots, block_size=self.block_size,
            num_blocks=self.num_kv_blocks))

    def _ensure_blocks(self, slot: int, upto: int) -> None:
        """Grow ``slot``'s block list to cover positions ``[0, upto)``.
        All-or-nothing: raises :class:`OutOfBlocksError` without touching
        any state when the pool cannot satisfy it."""
        need = blocks_needed(upto, self.block_size)
        held = int(self._nblocks[slot])
        if need <= held:
            return
        ids = self._allocator.alloc(need - held)
        self._block_tables[slot, held:need] = ids
        self._nblocks[slot] = need
        self._table_stale = True
        self._update_kv_bytes()

    def _release_blocks(self, slot: int) -> None:
        if self._allocator is None:
            return
        held = int(self._nblocks[slot])
        if held:
            self._allocator.free(self._block_tables[slot, :held].tolist())
            self._block_tables[slot, :held] = 0
            self._nblocks[slot] = 0
            self._table_stale = True
            self._update_kv_bytes()

    def _preempt_row(self, slot: int, why: str) -> None:
        """A mid-stream allocation failed and nothing retires this turn:
        fail the row cleanly (partial tokens already streamed) and return
        its blocks to the pool."""
        req = self._requests[slot]
        self._requests[slot] = None
        self._active[slot] = False
        self._release_blocks(slot)
        self._g_active.set(int(self._active.sum()))
        if req is not None:
            self._finish(req, "failed", error=why)

    def _reserve_rows(self, rows: np.ndarray, ahead: int,
                      parent=NULL_SPAN) -> np.ndarray:
        """Reserve ``ahead`` positions past each row's frontier before a
        fused step. Rows the pool cannot back are preempted (their freed
        blocks may rescue later rows in the same sweep), once the step in
        flight has landed: the rows it ends free their blocks, and the row
        to preempt has its last token first. Returns the surviving row
        mask."""
        rows = rows.copy()
        for slot in np.nonzero(rows)[0]:
            if not rows[slot]:
                continue  # ended when the step in flight landed
            try:
                try:
                    self._ensure_blocks(int(slot),
                                        int(self._pos[slot]) + ahead)
                except OutOfBlocksError:
                    if self._flight is None and not self._first:
                        raise
                    self._drain(parent)
                    rows &= self._active
                    if rows[slot]:
                        self._ensure_blocks(int(slot),
                                            int(self._pos[slot]) + ahead)
            except OutOfBlocksError as e:
                rows[slot] = False
                self._preempt_row(int(slot),
                                  f"kv block pool exhausted: {e}")
        return rows

    @property
    def _row_template(self):
        """A zeroed one-row carry: the shapes a handoff is held to, and what
        a test installs. The prefill makes its own inside its program, so an
        engine that only serves never holds one (a bounded state of windows
        and summaries is 67 MB a layer at 32,768 positions)."""
        if self._row is None:
            self._row = self.session.decode_state(1)
        return self._row

    # ----- jitted steps -----------------------------------------------
    def _prefill_fn(self, tb: int):
        """jit: an admitted prompt, whole (ISSUE 37). ``adm`` is the
        admission's one upload (``_prefill_args``). The row and its first
        token are ``GenerationSession.prefill_row``'s, installed HERE: the
        row into the donated batch carry at its slot, the token into the
        token vector (not donated: the step in flight still holds it for
        its fetch). No buffer the size of a slot outlives the program, so
        a turn may dispatch every admission it has before it fetches
        anything."""
        key = ("prefill", tb)
        if key not in self._fns:
            sess = self.session
            bs = self.block_size
            ids_at = ROW_SPEC_WORDS + self._table_width

            def fn(params, state, carry, toks, adm):
                spec = adm[:ROW_SPEC_WORDS]
                row, tok, counts = sess.prefill_row(
                    params, state, adm[None, ids_at:], spec)
                slot = spec[1]
                if bs is None:
                    carry = install_row(carry, row, slot)
                else:
                    carry = paged_install(carry, row,
                                          adm[ROW_SPEC_WORDS:ids_at], slot, bs)
                # what the layers counted of the prompt comes home with
                # its first token ({} for a model that counts nothing)
                return (carry, toks.at[slot].set(tok.astype(toks.dtype)),
                        tok, counts)

            # the profiler's "XLA Modules" line shows jit_<name>
            fn.__name__ = f"prefill_{tb}"
            self._fns[key] = jax.jit(fn, donate_argnums=2)
        return self._fns[key]

    def _prefill_args(self, tb: int, slot: int, req: "_Request") -> tuple:
        """``_prefill_fn(tb)``'s operands after the carry, on the device:
        the token vector, then the admission as ONE array: the row's spec
        (its length, its slot, its sampling law), the slot's block ids for
        a paged carry (the unallocated tail 0), the prompt right-padded to
        its bucket."""
        nb = self._table_width
        adm = np.zeros((ROW_SPEC_WORDS + nb + tb,), np.int32)
        adm[:ROW_SPEC_WORDS] = pack_row_spec(
            len(req.prompt), slot, req.seed, req.greedy, req.temp,
            req.top_k, req.top_p)
        if nb:
            held = int(self._nblocks[slot])
            adm[ROW_SPEC_WORDS:][:held] = self._block_tables[slot, :held]
        adm[ROW_SPEC_WORDS + nb:][:len(req.prompt)] = req.prompt
        return self._toks, jnp.asarray(adm)

    def _draft_prefill_fn(self, tb: int):
        """jit: 1-row draft prefill (cache build only — the draft's
        prompt logits are never sampled; proposals start from the first
        committed token)."""
        key = ("draft_prefill", tb)
        if key not in self._fns:
            sess = self._spec.draft
            model = sess.model

            def fn(params, state, row_carry, ids, lengths):
                mask = (jnp.arange(tb, dtype=jnp.int32)[None, :]
                        < lengths[:, None]).astype(model.dtype)
                _, _, new_rnn = model.forward_pure(
                    params, state, sess._prep(ids), train=False, rng=None,
                    mask=mask, rnn_state=row_carry)
                return new_rnn

            fn.__name__ = f"draft_prefill_{tb}"
            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def _decode_step_fn(self):
        if "decode" not in self._fns:
            sess = self.session
            model = sess.model

            def decode_step(params, state, carry, toks, image, table=None):
                # the host's image of the rows, one array (``_step_args``)
                bits = jax.lax.bitcast_convert_type
                last, steps, ks = image[0], image[4], image[7]
                fresh, active, gmask = (image[i] != 0 for i in (1, 2, 5))
                seeds = bits(image[3], jnp.uint32)
                temps, ps = (bits(image[i], jnp.float32) for i in (6, 8))
                # a row steps from the token the step before sampled, or its
                # prefill, which never left the device; a row whose last
                # token was committed on the host (``fresh``) from that
                tokens = jnp.where(fresh, last, toks)
                # the fused step writes every row: an inactive row of a
                # paged carry writes the trash block, not its own live
                # blocks, and one of a static carry writes nothing
                fwd = mask_inactive_writes(
                    attach_block_table(carry, table), active, sess.planes)
                with jax.named_scope("forward"):
                    out, new_rnn = sess._forward(
                        params, state, sess._prep(tokens[:, None]), None, fwd)
                with jax.named_scope("logits"):
                    logits = sess._logits(out, params)[:, :, 0]
                # a slot keeps its last request's spec after it ends: an
                # inactive row is greedy to the sampler (its token is
                # zeroed below), so a sampled request that finished holds
                # no later batch on the sort path
                with jax.named_scope("sample"):
                    toks = sample_tokens(logits, seeds, steps,
                                         gmask | ~active, temps, ks, ps)
                # idle/finished slots must not advance their pos or (h, c);
                # their cache planes the masked write left as they were
                with jax.named_scope("freeze_rows"):
                    counts = sess.summed_counts(new_rnn, active)
                    new_rnn = detach_block_table(
                        freeze_rows(new_rnn, fwd, active, sess.planes))
                # the counts come home with the step's tokens ({} for a
                # model that counts nothing)
                return (new_rnn, jnp.where(active, toks, 0).astype(jnp.int32),
                        counts)

            self._fns["decode"] = jax.jit(decode_step, donate_argnums=2)
        return self._fns["decode"]

    def _write_row_fn(self):
        """jit: a row that no prefill of this engine made (a handed-over
        row, the draft model's) into a batch carry."""
        if "write" not in self._fns:
            self._fns["write"] = jax.jit(install_row, donate_argnums=0)
        return self._fns["write"]

    def _set_token_fn(self):
        """jit: a handed-over row's first token into the device's token
        vector, at its row: dispatched beside the install, so the row's
        first step needs nothing of the host."""
        if "set_token" not in self._fns:
            def set_token(toks, i, tok):
                return toks.at[i].set(tok.astype(toks.dtype))

            self._fns["set_token"] = jax.jit(set_token)
        return self._fns["set_token"]

    def _paged_install_fn(self):
        """jit: a handed-over 1-row STATIC carry into the paged batch carry
        (``paged_install``). One compiled program, whatever the prompt's
        length."""
        if "paged_install" not in self._fns:
            self._fns["paged_install"] = jax.jit(
                partial(paged_install, block_size=self.block_size),
                donate_argnums=0)
        return self._fns["paged_install"]

    def _install_row(self, slot: int, row, tok) -> None:
        """A handed-over row into the batch carry (the static
        dynamic-update-slice, or the paged block scatter) and its first
        token into the token vector: the one admission that has no prefill
        to ride in."""
        at = jnp.asarray(slot, jnp.int32)
        self._dry.look()
        if self._allocator is None:
            self._carry = self._write_row_fn()(self._carry, row, at)
            self._calls[_UPLOAD] += 1
        else:
            dest = np.zeros((self._table_width,), np.int32)
            held = int(self._nblocks[slot])
            dest[:held] = self._block_tables[slot, :held]
            self._carry = self._paged_install_fn()(
                self._carry, row, jnp.asarray(dest), at)
            self._calls[_UPLOAD] += 2
        self._toks = self._set_token_fn()(self._toks, at, tok)
        self._dry.enqueued(self._toks)
        self._calls[_PROGRAM] += 2

    # ----- client side ------------------------------------------------
    def submit(
        self,
        prompt: Sequence[int],
        *,
        max_tokens: Optional[int] = None,
        greedy: bool = True,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        timeout: Optional[float] = None,
        deadline: Optional[Deadline] = None,
        request_id: Optional[str] = None,
        priority: Optional[str] = None,
        speculative_k: Optional[int] = None,
    ) -> GenerationHandle:
        """Fail-fast enqueue (the ``output_async`` analog): raises
        :class:`AdmissionRejectedError` when the pending window is full and
        :class:`CircuitOpenError` while the decode step is known-poisoned.
        Returns immediately; tokens stream through the handle.
        ``priority`` names an admission priority class (``X-Priority``) —
        under overload, lower classes shed first. ``speculative_k`` caps
        this request's speculation window (0 = plain decode for this
        request; None = follow the engine's adaptive k); exact acceptance
        sampling means the choice changes latency, never the output law."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_len {self.max_len} — "
                "no room to generate")
        if speculative_k is not None and int(speculative_k) < 0:
            raise ValueError("speculative_k must be >= 0")
        if deadline is None:
            deadline = Deadline.after(
                timeout if timeout is not None else self.default_timeout,
                clock=self._clock)
        mt = self.default_max_tokens if max_tokens is None else int(max_tokens)
        mt = max(1, min(mt, self.max_len - len(prompt)))
        handle = GenerationHandle(request_id or f"{self.name}-req", deadline)
        tracer = self.tracer
        ctx = current_context() if tracer.enabled else None
        req = _Request(prompt, mt, eos_id, handle, int(seed) & 0xFFFFFFFF,
                       bool(greedy), float(temperature), int(top_k),
                       float(top_p),
                       None if speculative_k is None else int(speculative_k),
                       ctx)
        with self._lock:
            if self._shutdown or self._draining:
                raise RuntimeError("DecodeEngine is shut down" if
                                   self._shutdown else
                                   "DecodeEngine is draining")
            if self._breaker.state is CircuitState.OPEN:
                self._c["circuit_rejected"].inc()
                raise CircuitOpenError(retry_after=self._breaker.retry_after())
            try:
                self._admission.admit(priority)
            except Exception:
                self._c["shed"].inc()
                raise
            self._g_inflight.inc()
            req.seq = self._n_submitted
            self._n_submitted += 1
            self._pending.append(req)
        self._wake.set()
        return handle

    def generate(self, prompt: Sequence[int], **kw) -> List[int]:
        """Blocking convenience: submit + wait for the full token list."""
        return self.submit(prompt, **kw).result()

    def submit_prefilled(
        self,
        handoff: dict,
        *,
        timeout: Optional[float] = None,
        deadline: Optional[Deadline] = None,
        request_id: Optional[str] = None,
        priority: Optional[str] = None,
    ) -> GenerationHandle:
        """Admit a request whose prefill already ran on another host (the
        disaggregated-serving resume path). ``handoff`` is the dict built
        by :class:`~deeplearning4j_tpu.serving.disagg.PrefillEngine` —
        prompt, sampled first token, per-layer cache slices and the
        sampling law. The decode stream continues token-identically to a
        local :meth:`submit` of the same prompt/sampling."""
        prompt = [int(t) for t in handoff.get("prompt", ())]
        if not prompt:
            raise ValueError("empty prompt in handoff")
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"handoff prompt length {len(prompt)} >= max_len "
                f"{self.max_len} — no room to generate")
        hd = handoff.get("cache_dtype")
        if hd != self.cache_dtype:
            raise ValueError(
                f"handoff cache_dtype {hd!r} != engine cache_dtype "
                f"{self.cache_dtype!r}")
        if int(handoff.get("pos", -1)) != len(prompt):
            raise ValueError("handoff pos != prompt length")
        s = dict(handoff.get("sampling", {}))
        spec_k = s.get("speculative_k")
        if spec_k is not None and int(spec_k) < 0:
            raise ValueError("speculative_k must be >= 0")
        if deadline is None:
            deadline = Deadline.after(
                timeout if timeout is not None else self.default_timeout,
                clock=self._clock)
        mt = int(s.get("max_tokens") or self.default_max_tokens)
        mt = max(1, min(mt, self.max_len - len(prompt)))
        handle = GenerationHandle(request_id or f"{self.name}-req", deadline)
        tracer = self.tracer
        ctx = current_context() if tracer.enabled else None
        eos = s.get("eos_id")
        req = _Request(prompt, mt, None if eos is None else int(eos), handle,
                       int(s.get("seed", 0)) & 0xFFFFFFFF,
                       bool(s.get("greedy", True)),
                       float(s.get("temperature", 1.0)),
                       int(s.get("top_k", 0)), float(s.get("top_p", 1.0)),
                       None if spec_k is None else int(spec_k), ctx,
                       prefilled=handoff)
        with self._lock:
            if self._shutdown or self._draining:
                raise RuntimeError("DecodeEngine is shut down" if
                                   self._shutdown else
                                   "DecodeEngine is draining")
            if self._breaker.state is CircuitState.OPEN:
                self._c["circuit_rejected"].inc()
                raise CircuitOpenError(retry_after=self._breaker.retry_after())
            try:
                self._admission.admit(priority)
            except Exception:
                self._c["shed"].inc()
                raise
            self._g_inflight.inc()
            req.seq = self._n_submitted
            self._n_submitted += 1
            self._pending.append(req)
        self._wake.set()
        return handle

    # ----- engine loop ------------------------------------------------
    def _device_dry(self) -> bool:
        """Whether the device's queue is empty: the newest program the loop
        enqueued has finished. Asked without a wait; whatever cannot say
        (a step that died, tokens that are no array) answers no and raises
        nothing: a dead step surfaces where it is fetched."""
        try:
            return self._dry.newest.is_ready()
        except Exception:  # noqa: BLE001 — the fetch reports it
            return False

    def _finish(self, req: _Request, reason: str,
                error: Optional[str] = None) -> None:
        req.handle._finish(reason, error)
        self._n_finished += 1
        outcome = reason if reason in _OUTCOMES else "completed"
        self._c[outcome].inc()
        self._admission.release()
        self._g_inflight.dec()
        if req.trace_ctx is not None and req.t_decode_start:
            rec = self.tracer.make_record(
                "engine.decode", req.trace_ctx, req.t_decode_start,
                trace_now(),
                attrs={"engine": self.name, "tokens": len(req.handle.tokens),
                       "reason": reason}, error=reason == "failed")
            self.tracer.record_spans([rec])

    def _free_slot(self) -> Optional[int]:
        for i in range(self.slots):
            if self._requests[i] is None:
                return i
        return None

    def _admit(self, parent=NULL_SPAN) -> int:
        """Prefill pending requests into free slots; returns how many were
        admitted. ``parent`` is the turn's ``loop.admit`` span: every span
        under the loop takes its parent explicitly, because an unsampled
        root is ``NULL_SPAN``, which never becomes the current span."""
        admitted = 0
        while True:
            # AIMD admission pacing: fill at most slot_target cache slots
            # even when more are free (the controller shrinks the target
            # when per-token p95 breaches the budget)
            if int(self._active.sum()) >= self._slot_target:
                return admitted
            slot = self._free_slot()
            with self._lock:
                if not self._pending:
                    return admitted
                if slot is None:
                    return admitted
                req = self._pending.popleft()
            if req.handle.cancelled:
                self._finish(req, "cancelled")
                continue
            if req.handle.deadline.expired():
                self._finish(req, "deadline")
                continue
            t_pop = trace_now()
            tracer = self.tracer
            if req.trace_ctx is not None:
                tracer.record_span(
                    "engine.queue_wait", parent=req.trace_ctx,
                    start_time=req.t_submit, end_time=t_pop,
                    attrs={"engine": self.name})
            try:
                with tracer.span("loop.prefill", parent=parent, attrs={
                        "req": req.seq, "slot": slot,
                        "queue_wait_ms": (t_pop - req.t_submit) * 1e3,
                        "prompt_len": len(req.prompt)}) as span:
                    self._prefill_into(slot, req, span)
                admitted += 1
            except OutOfBlocksError as e:
                # transient when rows are mid-flight (their blocks free at
                # retire): requeue and retry next wake. Terminal when the
                # batch is idle — the prompt can never fit this pool.
                if self._active.any():
                    with self._lock:
                        self._pending.appendleft(req)
                    return admitted
                self._finish(req, "failed", error=str(e))
            except Exception as e:  # noqa: BLE001 — fail the request, not the loop
                if self._carry_lost():
                    # a donated install died at run time: every row's
                    # cache went with it
                    self._fail_active(e)
                else:
                    self._breaker.record_failure()
                self._finish(req, "failed", error=str(e))

    def _prefill_into(self, slot: int, req: _Request,
                      parent=NULL_SPAN) -> None:
        sess = self.session
        if self._allocator is not None:
            # reserve blocks for the committed prompt BEFORE any compute:
            # OutOfBlocksError here is cheap and leaves nothing to undo
            self._ensure_blocks(slot, len(req.prompt))
        try:
            self._prefill_into_reserved(slot, req, parent)
        except Exception:
            self._release_blocks(slot)
            raise

    def _prefill_into_reserved(self, slot: int, req: _Request,
                               parent=NULL_SPAN) -> None:
        sess = self.session
        span = self.tracer.span
        tb = min(
            next(s for s in sess.bucket_sizes() if s >= len(req.prompt)),
            self.max_len)
        parent.set_attribute("bucket", tb)
        parent.set_attribute("pad_share",
                             100.0 * (1.0 - len(req.prompt) / tb))
        if self._window:
            parent.set_attribute("windows", len(req.prompt) // self._window)
        with span("loop.prefill.dispatch", parent=parent):
            t0 = time.perf_counter()
            tt0 = trace_now() if req.trace_ctx is not None else 0.0
            if req.prefilled is not None:
                # disaggregated handoff: the prefill tier already ran the
                # bucketed prefill and sampled the first token — install
                # its shipped cache slice instead of recomputing
                (row, tok), counts = self._handoff_row(req.prefilled), {}
            else:
                # one upload, one program: the row and its first token are
                # installed where they are computed, and nothing is fetched
                # before the turn's step is dispatched
                self._dry.look()
                self._carry, self._toks, tok, counts = self._prefill_fn(tb)(
                    sess.model.params, sess.model.state, self._carry,
                    *self._prefill_args(tb, slot, req))
                self._dry.enqueued(self._toks)
                self._calls[_UPLOAD] += 1
                self._calls[_PROGRAM] += 1
        if req.prefilled is not None:
            with span("loop.install", parent=parent):
                self._install_row(slot, row, tok)
        cap = -1 if req.spec_k is None else min(req.spec_k,
                                                self.max_speculative_k)
        if self._spec is not None and cap != 0:
            # paired draft cache: same prompt, same slot — proposals must
            # condition on the same committed prefix the target verifies.
            # For handoffs this re-runs the (cheap) draft prefill locally:
            # the draft cache never crosses the wire.
            ids = np.zeros((1, tb), np.int32)
            ids[0, : len(req.prompt)] = req.prompt
            drow = self._draft_prefill_fn(tb)(
                self._spec.draft.model.params, self._spec.draft.model.state,
                self._draft_row, jnp.asarray(ids),
                jnp.asarray([len(req.prompt)], jnp.int32))
            self._draft_carry = self._write_row_fn()(
                self._draft_carry, drow, jnp.asarray(slot, jnp.int32))
            # the newest program is the draft's now, not the token vector's
            self._dry.enqueued(
                jax.tree_util.tree_leaves(self._draft_carry)[0])
            self._calls[_UPLOAD] += 3
            self._calls[_PROGRAM] += 2
        self._breaker.record_success()
        if req.trace_ctx is not None:
            rec = self.tracer.make_record(
                "engine.prefill", req.trace_ctx, tt0, trace_now(),
                attrs={"engine": self.name, "slot": slot,
                       "prompt_len": len(req.prompt), "bucket": tb})
            self.tracer.record_spans([rec])
            req.t_decode_start = trace_now()
        # install the slot; the first token is fetched and emitted once
        # the turn's step is dispatched (``_land_firsts``)
        self._requests[slot] = req
        self._active[slot] = True
        self._fresh[slot] = False
        self._steps[slot] = 1  # next sample is decode step 1
        self._limit[slot] = min(req.max_tokens,
                                self.max_len - len(req.prompt))
        self._seeds[slot] = req.seed
        self._greedy[slot] = req.greedy
        self._temps[slot] = req.temp
        self._ks[slot] = req.top_k
        self._ps[slot] = req.top_p
        self._pos[slot] = len(req.prompt)  # committed cache frontier
        self._spec_caps[slot] = cap
        self._g_active.set(int(self._active.sum()))
        self._first.append((slot, req, tok, counts, t0))

    def _handoff_row(self, h: dict):
        """Rebuild a 1-row target carry from a serialized prefill handoff
        (shape/dtype-checked against this engine's row template). Cache
        planes arrive trimmed to the used positions ``[0, pos)``; the tail
        is zero-filled exactly like a fresh bucketed prefill leaves it."""
        pos = int(h["pos"])
        layers = h.get("layers", {})
        row = {}
        for name, st in self._row_template.items():
            layer = layers.get(name)
            if layer is None and set(st.keys()) - {"pos"}:
                # pos-only carries (position counters) ship nothing; a
                # layer WITH cache planes must be on the wire
                raise ValueError(f"handoff missing cache for layer {name!r}")
            new_st = {}
            for key, t in st.items():
                if key == "pos":
                    new_st[key] = jnp.asarray([pos], t.dtype)
                    continue
                arr = layer.get(key)
                if arr is None:
                    raise ValueError(
                        f"handoff layer {name!r} missing {key!r} — "
                        "prefill/decode cache_dtype mismatch?")
                want = t.shape[:2] + (pos,) + t.shape[3:]
                if tuple(arr.shape) != want:
                    raise ValueError(
                        f"handoff {name}.{key} shape {tuple(arr.shape)} != "
                        f"expected {want}")
                full = np.zeros(t.shape, t.dtype)
                full[:, :, :pos] = arr
                new_st[key] = jnp.asarray(full, t.dtype)
            row[name] = new_st
        self._calls[_UPLOAD] += 1 + len(jax.tree_util.tree_leaves(row))
        return row, jnp.asarray(int(h["first_token"]), jnp.int32)

    def _retire_if_done(self, slot: int, last_token: int, emitted: int) -> None:
        req = self._requests[slot]
        if req is None:
            return
        reason = None
        if req.handle.cancelled:
            reason = "cancelled"
        elif req.eos_id is not None and last_token == req.eos_id:
            reason = "completed"
        elif emitted >= req.max_tokens:
            reason = "completed"
        elif len(req.prompt) + emitted >= self.max_len:
            reason = "completed"
        elif req.handle.deadline.expired():
            reason = "deadline"
        if reason is not None:
            self._requests[slot] = None
            self._active[slot] = False
            self._release_blocks(slot)
            self._g_active.set(int(self._active.sum()))
            self._finish(req, reason)

    def _fail_active(self, e: Exception, lost: bool = False) -> None:
        """Poisoned device step: fail every active request, open-circuit
        accounting, clear the batch — and leave the engine with a carry
        the next admitted request can run on. A step in flight lands first
        (its tokens are its requests'), unless the failure is its own or
        that of the step before it (``lost``: the tokens never came, so
        whatever was computed from that carry is lost with it)."""
        if not lost and not self._drain():
            return  # the landing failed, and has failed them all
        self._flight, self._first = None, []
        self._toks = jnp.zeros((self.slots,), jnp.int32)
        self._dry.enqueued(self._toks)
        self._breaker.record_failure()
        # before any caller hears of the failure
        self._rebuild_lost_carry(force=lost)
        for slot in range(self.slots):
            req = self._requests[slot]
            if req is not None:
                self._requests[slot] = None
                self._active[slot] = False
                self._release_blocks(slot)
                self._finish(req, "failed", error=str(e))
        self._g_active.set(0)

    def _carry_lost(self) -> bool:
        """A donated program that raised at run time took its carry with
        it; one that failed while tracing consumed nothing."""
        return any(l.is_deleted() for l in jax.tree_util.tree_leaves(
            (self._carry, self._draft_carry)))

    def _rebuild_lost_carry(self, force: bool = False) -> None:
        """Replace a carry that a failed donated call consumed by a zeroed
        one (no row outlives it: the caller fails them all). ``force``: the
        step that returned it died after its dispatch, so what the arrays
        hold is not a carry whatever they say."""
        if not (force or self._carry_lost()):
            return
        self._carry = None  # never two carries at once
        self._carry = self._fresh_carry()
        if self._spec is not None:
            self._draft_carry = self._spec.draft.decode_state(self.slots)
        self._c_rebuilds.inc()

    def _step(self, rows: Optional[np.ndarray] = None,
              parent=NULL_SPAN) -> None:
        """Dispatch one plain [B, 1] decode step over ``rows`` (default:
        every active slot — the non-speculative path, and the boundary
        fallback for rows whose remaining cache room cannot hold a k+1
        window), THEN fetch and emit the step before it and the first
        tokens of this turn's prefills, in their order: the host's work on
        step n-1 lies under the device's work on step n. A row whose request is
        complete with the token in flight sits this step out; with no row
        left to step, the turn only lands what is in flight. ``parent`` is
        the turn's ``loop.step`` span."""
        with self.tracer.span("loop.select", parent=parent):
            self._dry.mark("select")
            rows = (self._active if rows is None else rows) \
                & (self._steps < self._limit)
            if self._allocator is not None:
                rows = self._reserve_rows(rows, 1, parent)
        prev = self._flight
        if rows.any():
            try:
                step = self._dispatch(rows, parent)
            except Exception as e:  # noqa: BLE001 — poisoned step: fail active requests
                self._fail_active(e)
                return
            if prev is not None:
                self._c_ahead.inc()
                parent.set_attribute("ahead", 1)
            self._flight = step
        else:
            self._flight = None
        if prev is None or self._land(prev, parent):
            self._land_firsts(parent)

    def _dispatch(self, rows: np.ndarray, parent=NULL_SPAN) -> _InFlight:
        """Upload the rows' image and enqueue their step, which takes its
        tokens where the step before and the prefills left them, on the
        device. The rows' sampling position and cache frontier advance
        here: the host's image of a row is one token ahead of what its
        request has been handed."""
        sess = self.session
        span = self.tracer.span
        dry = self._dry
        t0 = time.perf_counter()
        with span("loop.upload", parent=parent):
            dry.mark("upload")
            args = self._step_args(rows)
        with span("loop.dispatch", parent=parent):
            dry.look("dispatch")
            self._carry, self._toks, counts = self._decode_step_fn()(
                sess.model.params, sess.model.state, self._carry, *args,
                self._device_table())
            dry.enqueued(self._toks)
        with span("loop.account", parent=parent):
            dry.phase = "account"
            self._calls[_UPLOAD] += 1
            self._calls[_PROGRAM] += 1
            self._c_sampler[int(sampler_path(
                self._greedy | ~rows, self._ks, self._ps))].inc()
            if self._static_kv:
                lengths = self._pos[rows] + 1
                self._c_kv_attended.inc(int(lengths.sum()))
                self._c_kv_fetched.inc(int(decode_fetched_entries(
                    lengths, self.max_len).sum()))
            self._fresh[rows] = False
            self._steps[rows] += 1
            self._pos[rows] += 1
            return _InFlight(self._toks, rows, list(self._requests), t0,
                             counts)

    def _step_args(self, rows: np.ndarray) -> tuple:
        """The decode step's operands after the carry, on the device: the
        token vector that never left it, then the host's image of the
        rows, ONE array (ISSUE 37). A transfer reads the host's array
        after the call that asked for it returns, and the host writes its
        arrays again (the next dispatch, the next admission) while the
        step is still in flight: the image is built anew every time and
        never written again, so it is the copy that makes that safe."""
        arrays = (self._last, self._fresh, rows, self._seeds, self._steps,
                  self._greedy, self._temps, self._ks, self._ps)
        image = np.empty((len(arrays), self.slots), np.int32)
        # every entry is 4 bytes or fewer: the seeds and the two float
        # rows ride as their bits, and ``decode_step`` takes them apart
        for i, a in enumerate(arrays):
            image[i] = a.view(np.int32) if a.itemsize == 4 else a
        return self._toks, jnp.asarray(image)

    def _land(self, step: _InFlight, parent=NULL_SPAN) -> bool:
        """Fetch a dispatched step's tokens and hand each to its request.
        A row whose request ended after the dispatch (the slot is free, or
        another request's) is dropped. False when the step died at run
        time, which surfaces here: every active request has then failed."""
        span = self.tracer.span
        dry = self._dry
        try:
            with span("loop.fetch", parent=parent):
                dry.mark("fetch")
                toks_h = np.asarray(step.toks)
                self._calls[_FETCH] += 1
                if step.counts:  # the same program's: they are there
                    self._count(jax.device_get(step.counts))
                    self._calls[_FETCH] += 1
        except Exception as e:  # noqa: BLE001 — poisoned step: fail active requests
            self._fail_active(e, lost=True)
            return False
        dt = time.perf_counter() - step.t0
        self._h_decode.observe(dt)
        self._breaker.record_success()
        # a sampled turn also says what a row costs, in its three parts: no
        # clock is read for a turn that is not
        timed = parent is not NULL_SPAN
        clock = time.perf_counter_ns
        put = count = retire = t0 = t1 = t2 = 0
        with span("loop.emit", parent=parent):
            dry.look("emit")
            slots = np.nonzero(step.rows)[0]
            for n, slot in enumerate(slots):
                if n % _EMIT_LOOK_ROWS == 0 and n:
                    dry.look()
                req = step.reqs[slot]
                if req is not self._requests[slot]:
                    self._c_dropped.inc()
                    continue
                tok = int(toks_h[slot])
                emitted = len(req.handle.tokens)
                if timed:
                    t0 = clock()
                req.handle._emit(emitted, tok)
                if timed:
                    t1 = clock()
                self._last[slot] = tok
                # the step wrote position len(prompt) + emitted - 1
                if self._window and \
                        (len(req.prompt) + emitted) % self._window == 0:
                    self._c_windows.inc()
                self._c_tokens.inc()
                self._h_token.observe(dt)
                if timed:
                    t2 = clock()
                self._retire_if_done(slot, tok, emitted + 1)
                if timed:
                    put += t1 - t0
                    count += t2 - t1
                    retire += clock() - t2
            if timed:
                attrs = parent.attributes
                for key, n in (("emit_rows", len(slots)),
                               ("emit_put_ms", put * 1e-6),
                               ("emit_count_ms", count * 1e-6),
                               ("emit_retire_ms", retire * 1e-6)):
                    parent.set_attribute(key, attrs.get(key, 0) + n)
            dry.look("step")
        if self._step_hook is not None:
            self._step_hook()
        return True

    def _count(self, counts: dict) -> dict:
        """What the model's layers counted of one step or one prefill, on
        the host (``{leaf: sums}`` by the columns the layers declare,
        ``GenerationSession.count_columns``), into the registry. An expert
        layer's ``moe_choices`` are its tokens' choices by where they went:
        ``expert:<id>`` a held expert, ``absent``, ``zero``. Returns what a
        span says of them."""
        cols = self._count_cols.get("moe_choices")
        if cols is None:
            return {}
        held = 0
        for col, n in zip(cols, np.asarray(counts["moe_choices"]).tolist()):
            kind, _, expert = col.partition(":")
            if kind == "expert":
                held += n
                self._c_moe_expert.labels(self.name, expert).inc(n)
            else:
                self._c_moe_choices.labels(self.name, kind).inc(n)
        self._c_moe_choices.labels(self.name, "held").inc(held)
        return {"moe_held_pairs": held}

    def _land_firsts(self, parent=NULL_SPAN) -> bool:
        """Fetch and emit, each as index 0 and in the order of their
        admission, the first tokens of the prefills in flight. False when
        one died at run time: it sat between two steps in the device's
        queue and took the carry with it, so every active request has then
        failed."""
        firsts, self._first = self._first, []
        return all(self._land_first(first, parent) for first in firsts)

    def _land_first(self, first: tuple, parent=NULL_SPAN) -> bool:
        slot, req, tok, counts, t0 = first
        span = self.tracer.span
        dry = self._dry
        try:
            with span("loop.fetch", parent=parent) as fetch:
                dry.mark("fetch")
                tok = int(tok)
                self._calls[_FETCH] += 1
                if counts:
                    self._calls[_FETCH] += 1
                    # the prompt's counts: the prefill's own span closed at
                    # its dispatch, so the span that lands them says them
                    fetch.set_attribute("req", req.seq)
                    for name, n in self._count(
                            jax.device_get(counts)).items():
                        fetch.set_attribute(name, n)
        except Exception as e:  # noqa: BLE001 — poisoned prefill
            self._fail_active(e, lost=True)
            return False
        self._h_prefill.observe(time.perf_counter() - t0)
        with span("loop.emit", parent=parent):
            dry.mark("emit")
            if req is self._requests[slot]:
                self._last[slot] = tok
                self._c_tokens.inc()
                req.handle._emit(0, tok)
                self._retire_if_done(slot, tok, emitted=1)
            dry.look("step")
        return True

    def _drain(self, parent=NULL_SPAN) -> bool:
        """Land the step in flight and the first tokens in flight, if any:
        afterwards the host's image of every row (``_last``, the handles)
        is current."""
        step, self._flight = self._flight, None
        return (step is None or self._land(step, parent)) \
            and self._land_firsts(parent)

    def _spec_step(self, parent=NULL_SPAN) -> None:
        """One speculative engine turn: propose/verify/accept for every
        row with cache room for the full k+1 window, then a plain [B, 1]
        step for the remainder (rows near ``max_len``, and requests with
        ``speculative_k=0``). Both caches are rewound to the committed
        frontier inside :meth:`SpeculativeGenerationSession.step`, so a
        cancelled or expired request never leaves speculative writes
        behind when its slot is reused."""
        # the session takes every row's last token from the host
        # (``_last``): this turn's prefills' first
        if not self._drain(parent):
            return
        k = max(1, self._spec_k)
        caps = np.where(self._spec_caps < 0, k,
                        np.minimum(self._spec_caps, k)).astype(np.int32)
        spec_rows = (self._active & (caps > 0)
                     & (self._pos + k + 1 <= self.max_len))
        if self._allocator is not None and spec_rows.any():
            # a speculative window may write up to k+1 positions past the
            # frontier; rows that can't reserve that many blocks degrade
            # to the plain path (which reserves just 1, preempting only
            # when even that fails)
            spec_rows = spec_rows.copy()
            for slot in np.nonzero(spec_rows)[0]:
                try:
                    self._ensure_blocks(slot, int(self._pos[slot]) + k + 1)
                except OutOfBlocksError:
                    spec_rows[slot] = False
        plain_rows = self._active & ~spec_rows
        span = self.tracer.span
        dry = self._dry
        if spec_rows.any():
            t0 = time.perf_counter()
            try:
                # propose, verify and accept are dispatched and fetched
                # inside the session's step: one span round all of it. An
                # open dry interval ends before it, and once its tokens
                # are home the device is dry again until the loop's next
                # dispatch
                with span("loop.fetch", parent=parent):
                    dry.look("fetch")
                    dry.enqueued()
                    # the session's step takes (and returns) the tables
                    # inside the carry, and donates nothing
                    (carry, self._draft_carry, toks, n_acc,
                     n_emit) = self._spec.step(
                        attach_block_table(self._carry,
                                           self._device_table()),
                        self._draft_carry, self._last,
                        self._steps, spec_rows, jnp.asarray(self._seeds),
                        jnp.asarray(self._greedy), jnp.asarray(self._temps),
                        jnp.asarray(self._ks), jnp.asarray(self._ps),
                        np.where(spec_rows, caps, 0), k=k)
                    self._carry = detach_block_table(carry)
                    toks_h = np.asarray(toks)
                    acc_h = np.asarray(n_acc)
                    ne_h = np.asarray(n_emit)
                    # the session uploads its nine operands a turn
                    self._calls[_UPLOAD] += 9
                    self._calls[_PROGRAM] += 1
                    self._calls[_FETCH] += 3
                    dry.enqueued(toks)
            except Exception as e:  # noqa: BLE001
                self._fail_active(e)
                return
            dt = time.perf_counter() - t0
            self._h_decode.observe(dt)
            self._breaker.record_success()
            self._c_spec_steps.inc()
            with span("loop.emit", parent=parent):
                dry.look("emit")
                for slot in np.nonzero(spec_rows)[0]:
                    req = self._requests[slot]
                    if req is None:
                        continue
                    self._c_spec_proposed.inc(int(caps[slot]))
                    self._c_spec_accepted.inc(int(acc_h[slot]))
                    committed = 0
                    for j in range(int(ne_h[slot])):
                        tok = int(toks_h[slot, j])
                        emitted = len(req.handle.tokens)
                        req.handle._emit(emitted, tok)
                        self._last[slot] = tok
                        self._fresh[slot] = True
                        self._steps[slot] += 1
                        self._pos[slot] += 1
                        self._c_tokens.inc()
                        committed += 1
                        self._retire_if_done(slot, tok, emitted + 1)
                        if self._requests[slot] is None:
                            break  # retired mid-window: drop the tail
                    self._h_token.observe(dt / max(1, committed))
                dry.look("step")
            if self._step_hook is not None:
                self._step_hook()
        if plain_rows.any():
            # and their tokens next turn: no step stays in flight here
            self._step(plain_rows, parent)
            self._drain(parent)

    def _sweep_pending(self) -> None:
        """Fail pending requests that died in the queue (cancel/expiry)
        WITHOUT waiting for a cache slot: a burst of doomed requests must
        release its admission window even while every slot is busy."""
        with self._lock:
            dead = [r for r in self._pending
                    if r.handle.cancelled or r.handle.deadline.expired()]
            for r in dead:
                self._pending.remove(r)
        for r in dead:
            self._finish(r, "cancelled" if r.handle.cancelled else "deadline")

    def _loop(self) -> None:
        while True:
            # a step in flight is work: its tokens are still to deliver
            if not self._active.any() and not self._pending \
                    and self._flight is None and not self._wait_for_work():
                return
            self._turn()

    def _wait_for_work(self) -> bool:
        """Park until a request is pending; False once the engine is shut
        down. One ``loop.wait`` root per idle period, however many times
        the wait polls."""
        with self.tracer.span("loop.wait", parent=None,
                              attrs={"engine": self.name}):
            while True:
                with self._lock:
                    if self._pending:
                        return True
                if self._shutdown:
                    return False
                self._wake.wait(timeout=0.05)
                self._wake.clear()

    def _turn(self) -> None:
        """One pass of the loop that has work: admit, dispatch this turn's
        step, land the step before, sweep. The pass is a ``loop.turn`` trace
        of the engine's tracer (head-sampled at its rate; every pass while a
        profiler session collects), whose children say where the pass's
        time went on the host."""
        span = self.tracer.span
        dry = self._dry
        self._n_turns += 1
        finished = self._n_finished
        self._calls = [0, 0, 0]
        with span("loop.turn", parent=None, attrs={
                "engine": self.name, "turn": self._n_turns,
                "pending": len(self._pending)}) as turn:
            dry.begin()
            with span("loop.admit", parent=turn) as admit:
                turn.set_attribute("admitted", self._admit(admit))
            turn.set_attribute("rows", int(self._active.sum()))
            if self._active.any() or self._flight is not None:
                spec = self._spec is not None
                dry.mark("step")
                with span("loop.step", parent=turn,
                          attrs={"spec": spec, "ahead": 0}) as step:
                    if spec:
                        self._spec_step(step)
                    else:
                        self._step(parent=step)
            with span("loop.sweep", parent=turn):
                dry.mark("sweep")
                # also sweep cancelled requests on slots that produced
                # nothing
                for slot in range(self.slots):
                    req = self._requests[slot]
                    if req is not None and (req.handle.cancelled or
                                            req.handle.deadline.expired()):
                        self._retire_if_done(slot, -1,
                                             len(req.handle.tokens))
                self._sweep_pending()
                if self._live_layers:
                    self._update_state_bytes()
                if (self._adaptive and self._adjust_interval > 0
                        and self._clock() >= self._next_adjust):
                    self.adjust()
                    self._next_adjust = self._clock() + self._adjust_interval
            turn.set_attribute("retired", self._n_finished - finished)
            for attr, child, n in zip(_CALL_ATTRS, self._c_calls,
                                      self._calls):
                turn.set_attribute(attr, n)
                child.inc(n)
            # the turn's account of the device's queue, in seconds to the
            # counters and in ms on the span
            self._c_loop_s.inc(dry.end())
            self._c_dry_slack.inc(dry.slack)
            for phase, s in dry.by_phase.items():
                self._c_dry[phase].inc(s)
                turn.set_attribute(_DRY_ATTRS[phase], s * 1e3)
            turn.set_attribute("dry_ms", sum(dry.by_phase.values()) * 1e3)
            turn.set_attribute("dry_slack_ms", dry.slack * 1e3)

    # ----- decode-side AIMD control -----------------------------------
    @property
    def speculative_k(self) -> int:
        """Current speculation depth (0 when no draft model)."""
        return self._spec_k if self._spec is not None else 0

    @property
    def slot_target(self) -> int:
        return self._slot_target

    def set_decode_control(self, speculative_k: Optional[int] = None,
                           slot_target: Optional[int] = None):
        """Write the AIMD-controlled knobs (clamped: ``k`` to
        ``[1, max_speculative_k]`` when a draft is attached, the slot
        target to ``[1, slots]``). Returns the effective pair."""
        if speculative_k is not None and self._spec is not None:
            self._spec_k = max(1, min(int(speculative_k),
                                      self.max_speculative_k))
            self._g_spec_k.set(self._spec_k)
        if slot_target is not None:
            self._slot_target = max(1, min(int(slot_target), self.slots))
            self._g_slot_target.set(self._slot_target)
        return self.speculative_k, self._slot_target

    def adjust(self) -> Optional[dict]:
        """Tick the AIMD controller once (the engine loop does this every
        ``adjust_interval`` seconds when ``adaptive=True``); returns the
        observation/action, or None when no tokens were emitted since the
        last tick."""
        return self.aimd.tick()

    def token_p95(self) -> Optional[float]:
        """Lifetime per-token p95 from the latency histogram (bucket
        upper bound; None before any traffic — PR-7 zero-guard)."""
        count = self._h_token.count
        if count <= 0:
            return None
        threshold = 0.95 * count
        for le, c in self._h_token.buckets():
            if c >= threshold:
                return le
        return float("inf")

    # ----- lifecycle / introspection ----------------------------------
    def bucket_sizes(self) -> List[int]:
        return self.session.bucket_sizes()

    @property
    def circuit_state(self) -> CircuitState:
        return self._breaker.state

    def load_score(self) -> float:
        """Dispatch load score for a replica pool: admitted-but-unfinished
        sequences plus the fraction of cache slots busy (a replica with
        free slots is cheaper than one continuously batching at
        capacity)."""
        return (float(self._admission.pending)
                + float(self._active.sum()) / max(1, self.slots))

    def stats(self) -> dict:
        counts = {k: int(c.value) for k, c in self._c.items()}
        proposed = int(self._c_spec_proposed.value)
        accepted = int(self._c_spec_accepted.value)
        spec_steps = int(self._c_spec_steps.value)
        kv_fetched = self._c_kv_fetched.value
        sampler_steps = [c.value for c in self._c_sampler]
        counts.update({
            "in_flight": self._admission.pending,
            # the engine-list aggregation key health()/pools sum over
            "queue_depth": self._admission.pending,
            "active_slots": int(self._active.sum()),
            "slots": self.slots,
            "slot_target": self._slot_target,
            "tokens": int(self._c_tokens.value),
            "max_len": self.max_len,
            "cache_dtype": self.cache_dtype or str(self.session.model.dtype),
            "kv_cache_bytes": self._kv_cache_bytes,
            "kv_block_size": self.block_size,
            "kv_blocks_total": (None if self._allocator is None
                                else self._allocator.total_blocks),
            "kv_blocks_free": (None if self._allocator is None
                               else self._allocator.free_blocks),
            "circuit_state": self._breaker.state.value,
            "carry_rebuilds": int(self._c_rebuilds.value),
            # the loop runs one step ahead: steps fetched, those of them
            # dispatched under the step before, row-steps thrown away
            "decode_steps": int(self._h_decode.count),
            "steps_ahead": int(self._c_ahead.value),
            "dropped_row_steps": int(self._c_dropped.value),
            # what the loop's turns asked of the device, by kind of call
            "device_calls": {kind: int(c.value) for kind, c in
                             zip(_CALLS, self._c_calls)},
            # the loop's account of the device's queue over all its turns:
            # the share of their wall time in which the device was dry (a
            # lower bound), what the looks' spacing leaves open above it,
            # and the lower bound by the phase of the turn
            "loop": self._loop_stats(),
            # of the entries the decode kernel moved, the share it attended
            "kv_fetch_valid_share": (
                self._c_kv_attended.value / kv_fetched if kv_fetched
                else None),
            # of the plain steps dispatched, the share whose sampler sorted
            "sampler_sort_share": (
                sampler_steps[PATHS.index("sort")] / sum(sampler_steps)
                if any(sampler_steps) else None),
            "draining": self._draining,
            # zero-guarded (PR-7 convention): derived ratios are None, not
            # 0.0, before any speculative traffic
            "per_token_p95_s": self.token_p95(),
            "speculative": {
                "enabled": self._spec is not None,
                "current_k": self.speculative_k,
                "max_k": self.max_speculative_k,
                "steps": spec_steps,
                "proposed": proposed,
                "accepted": accepted,
                "acceptance_rate": (accepted / proposed) if proposed
                else None,
                "accepted_tokens_per_step":
                    ((accepted + spec_steps) / spec_steps) if spec_steps
                    else None,
            },
        })
        return counts

    def _loop_stats(self) -> dict:
        wall = self._c_loop_s.value
        by_phase = {phase: c.value for phase, c in self._c_dry.items()
                    if c.value}
        return {
            "turn_seconds": wall,
            "dry_share": sum(by_phase.values()) / wall if wall else None,
            "dry_slack_share": (self._c_dry_slack.value / wall if wall
                                else None),
            "dry_by_phase": {phase: s / wall for phase, s in
                             by_phase.items()},
        }

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop admitting, wait for in-flight generations to finish."""
        with self._lock:
            self._draining = True
        end = None if timeout is None else time.monotonic() + timeout
        while self._admission.pending > 0:
            if end is not None and time.monotonic() > end:
                return False
            time.sleep(0.01)
        return True

    def shutdown(self, *, drain: bool = True,
                 drain_timeout: Optional[float] = 30.0) -> None:
        if drain and not self._shutdown:
            self.drain(timeout=drain_timeout)
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
            pending = list(self._pending)
            self._pending.clear()
        for req in pending:
            self._finish(req, "cancelled")
        for slot in range(self.slots):
            req = self._requests[slot]
            if req is not None:
                req.handle.cancel()
        self._wake.set()
        self._thread.join(timeout=10)


class DecodeAIMD:
    """AIMD controller for the decode engine's latency/throughput knobs —
    the decode-side mirror of :class:`~deeplearning4j_tpu.parallel.pool.
    AdaptiveBatcher`.

    Each :meth:`tick` estimates the per-token p95 from the delta of the
    engine's token-latency histogram since the previous tick, then:

    * **p95 over target** → multiplicative decrease: the active-slot
      target AND the speculation depth both shrink by ``shrink_factor``
      (fewer sequences sharing the step, shallower windows — per-token
      latency is the hard constraint, back off fast).
    * **p95 under target, pending queue non-empty and slots headroom** →
      additive increase of the slot target (demand exists; batch wider).
    * **p95 under target otherwise** → additive increase of the
      speculation depth toward ``max_speculative_k`` (spend the latency
      headroom on deeper windows: more accepted tokens per fixed-cost
      target forward).

    No tokens since the last tick leaves everything untouched. Writes go
    through :meth:`DecodeEngine.set_decode_control` (clamped there)."""

    def __init__(self, engine: DecodeEngine, *, target_p95_s: float = 0.05,
                 grow_step: int = 1, shrink_factor: float = 0.5,
                 min_k: int = 1, min_slots: int = 1) -> None:
        if not 0.0 < shrink_factor < 1.0:
            raise ValueError("shrink_factor must be in (0, 1)")
        self.engine = engine
        self.target_p95_s = float(target_p95_s)
        self.grow_step = int(grow_step)
        self.shrink_factor = float(shrink_factor)
        self.min_k = int(min_k)
        self.min_slots = int(min_slots)
        self._last_buckets = [c for _, c in engine._h_token.buckets()]
        self._last_count = engine._h_token.count

    def _p95_delta(self) -> Optional[float]:
        hist = self.engine._h_token
        pairs = hist.buckets()  # cumulative (le, count)
        count = hist.count
        cums = [c for _, c in pairs]
        deltas = [c - p for c, p in zip(cums, self._last_buckets)]
        dcount = count - self._last_count
        self._last_buckets = cums
        self._last_count = count
        if dcount <= 0:
            return None
        threshold = 0.95 * dcount
        for (le, _), d in zip(pairs, deltas):
            if d >= threshold:
                return le if le != float("inf") else float("inf")
        return float("inf")

    def tick(self) -> Optional[dict]:
        """One control step; returns the observation/action taken, or
        None when no tokens were emitted since the last tick."""
        p95 = self._p95_delta()
        if p95 is None:
            return None
        eng = self.engine
        k, st = eng.speculative_k, eng.slot_target
        queue_depth = max(0, eng._admission.pending
                          - int(eng._active.sum()))
        if p95 > self.target_p95_s:
            new_k = max(self.min_k, int(k * self.shrink_factor)) if k else 0
            new_st = max(self.min_slots, int(st * self.shrink_factor))
            action = "shrink"
        elif queue_depth > 0 and st < eng.slots:
            new_k, new_st = k, st + self.grow_step
            action = "grow_slots"
        elif k and k < eng.max_speculative_k:
            new_k, new_st = k + self.grow_step, st
            action = "grow_k"
        else:
            new_k, new_st = k, st
            action = "hold"
        new_k, new_st = eng.set_decode_control(
            new_k if new_k else None, new_st)
        return {"p95_s": p95, "queue_depth": queue_depth, "action": action,
                "speculative_k": new_k, "slot_target": new_st}
