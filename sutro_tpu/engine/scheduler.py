"""Continuous-batching scheduler.

Host-side control plane of the engine — the component the reference's
remote service keeps behind ``POST /batch-inference`` (SURVEY §2.3 row 1,
§7.3 "continuous batching under XLA static shapes"). Design:

- A fixed array of ``decode_batch_size`` slots; every decode step runs the
  whole array through one compiled step regardless of occupancy (static
  shapes — no recompiles as rows enter/leave).
- Rows are admitted whenever a slot is free and the page allocator can
  reserve the row's worst-case page count up front (prompt + max_new
  capped to context) — reservation up front makes mid-flight OOM
  impossible and keeps the loop deadlock-free.
- Prefill is BATCHED, shortest-prompt-first: up to ``prefill_batch_size``
  reserved rows share one device dispatch padded to a power-of-two
  (batch x length) bucket (compile-count bounded); each row's
  last-position logits seed its slot's first sampled token. Prompts
  longer than ``prefill_chunk`` prefill alone via the chunked path.
  Admission waits for the device ONCE an iteration: prefills and their
  first-token samples are dispatched back to back, the first tokens of
  the whole wave fetched together (``_resolve_wave``), and where the
  rows can enter the next fused window by the tokens on the device that
  wait lies behind the window's dispatch (``_hold_wave``), so a wave
  does not drain the device's queue.
- Order-preserving results: completions are emitted keyed by ``row_id`` and
  re-assembled in input order by the jobstore, while execution order is
  whatever batching dictates (reference contract: README.md:221).
- Constrained decoding: slots carrying a token-FSM contribute a per-slot
  vocab mask assembled host-side each step (SURVEY §7.3 "vectorized
  constrained decoding"); unconstrained slots get all-True rows.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import logging
import time
from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Protocol, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from . import faults
from .kvcache import ONE_POOL, PageAllocator, RowPools, pages_needed
from .runner import ModelRunner
from .. import telemetry
from ..ops.sampling import (
    cumulative_logprob, sample as device_sample, unpack_mask,
)

# StepTimer phase -> telemetry stage (OBSERVABILITY.md span schema):
# the timer wraps DEVICE dispatches, so its phases map onto the
# device-side stages of the flight-recorder timeline
_TEL_STAGE = {
    "prefill": "prefill",
    "decode": "decode_window",
    "admit_sample": "admit",
}
# the scheduler's own host phases (the timer's cursor; OBSERVABILITY.md
# "Scheduler phases"): histogram + flight recorder only — no exemplar
# and no fan-out into per-request traces, which nobody would read
_QUIET_STAGES = frozenset((
    "sched_poll", "job_start", "admit_host", "constraint_compile",
    "fsm_mask", "fsm_plan", "batch_build", "emit", "sched_idle",
    "sched_other",
))


@functools.partial(jax.jit, static_argnames=("split",))
def _admit_sample_jit(
    logits, key, temperature, top_p, top_k, allowed, row_seeds,
    first=None, slots=None, split=False,
):
    """First-token sampling + logprob for admission, under ONE jit.

    Calling ``sample`` eagerly here cost ~450 ms of host time per
    prefill group (profiled round 5, CPU host): the top-p path's
    ``lax.cond`` re-traces its branches on EVERY eager call. Jitted,
    repeat groups of the same shape hit the pjit cache and the whole
    sample+logprob pair runs as one compiled program. ``allowed``
    arrives bit-packed ([B, ceil(V / 8)] uint8), like the masked decode
    step's. ``first`` is the decode batch's first tokens by SLOT, on the
    device: the tokens of this dispatch's rows are written into it at
    ``slots`` (a padding row's slot is out of range and dropped) and it
    is returned third, for a window that goes out before the host has
    read them (``ModelRunner.merge_first``). ``split``: ``key`` is the
    session's key, split HERE (the values ``jax.random.split`` gives
    when called on its own, which costs a row two dispatches more) and
    handed back fourth; else it is the key to sample with."""
    if allowed is not None:
        allowed = unpack_mask(allowed, logits.shape[-1])
    if split:
        key, sub = jax.random.split(key)
    else:
        key, sub = None, key
    tok = device_sample(
        logits, sub,
        temperature=temperature, top_p=top_p, top_k=top_k,
        allowed=allowed, row_seeds=row_seeds,
    )
    if first is not None:
        first = first.at[slots].set(tok.astype(first.dtype), mode="drop")
    return tok, cumulative_logprob(logits, tok), first, key


def _step_seed(row_seed: int, step: int) -> int:
    """Deterministic (row, step) -> int32 seed mix."""
    return ((row_seed * 1_000_003) ^ (step * 2_654_435_761)) & 0x7FFFFFFF


class TokenConstraint(Protocol):
    """Token-level FSM driving schema-constrained decoding
    (engine/constrain/). ``remaining`` (tokens of budget left for the
    row, when known) lets the FSM force closure so schema rows emit
    complete JSON even at the length cap."""

    def allowed_tokens(
        self, remaining: "int | None" = None
    ) -> np.ndarray:  # [V] bool
        ...

    def advance(self, token_id: int) -> None:
        ...

    def is_complete(self) -> bool:
        ...

    # OPTIONAL fast path: implementations may additionally provide
    # ``token_allowed(token_id, remaining=None) -> bool`` (O(1) validity
    # of one token) — the speculative fused-window verifier uses it when
    # present and falls back to ``allowed_tokens`` otherwise.
    #
    # OPTIONAL: ``allowed_packed(remaining=None, shared=None) ->
    # (uint8 [ceil(V / 8)], filtered)``: the bits of
    # ``allowed_tokens(remaining)`` as ``np.packbits`` gives them, and
    # whether a budget filtered them (False: a kept array, no pass over
    # the vocabulary). ``shared`` is a dict local to one assembly of a
    # batch's masks. The mask assembly copies such a row as it is and
    # packs the bool mask of an implementation without the method.


# per-method cache: does this allowed_tokens accept ``remaining``? Keyed
# by the unbound class function (bounded: one entry per implementing
# class); the value keeps a strong ref so the id can't be reused.
# Instance-attribute callables (no __func__) are probed per object and
# memoized on the instance itself, so the cache cannot grow unboundedly
# in a long-lived daemon.
_TAKES_BUDGET: Dict[int, Tuple[Any, bool]] = {}


def _probe_takes_budget(fn: Any) -> bool:
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        # inspect.signature's documented failure modes for builtins /
        # C callables: no signature means no ``remaining`` kwarg
        return False
    kw_ok = (
        inspect.Parameter.POSITIONAL_OR_KEYWORD,
        inspect.Parameter.KEYWORD_ONLY,
    )
    return any(
        (p.name == "remaining" and p.kind in kw_ok)
        or p.kind == inspect.Parameter.VAR_KEYWORD
        for p in sig.parameters.values()
    )


def _method_takes_budget(obj: Any, bound: Any, attr_cache: str) -> bool:
    """Cached signature probe for a bound constraint method: one probe
    per implementing class (``_TAKES_BUDGET``) or, for instance-attribute
    callables, memoized on the instance. Called on per-token hot paths —
    must not hit ``inspect.signature`` repeatedly."""
    fn = getattr(bound, "__func__", None)
    if fn is not None:
        key = id(fn)
        cached = _TAKES_BUDGET.get(key)
        if cached is not None:
            return cached[1]
        takes = _probe_takes_budget(fn)
        _TAKES_BUDGET[key] = (fn, takes)
        return takes
    takes = getattr(obj, attr_cache, None)
    if takes is None:
        takes = _probe_takes_budget(bound)
        try:
            setattr(obj, attr_cache, takes)
        except (AttributeError, TypeError):
            pass  # __slots__ / frozen instances: re-probe next call
    return takes


@dataclasses.dataclass
class GenRequest:
    row_id: int
    prompt_ids: np.ndarray
    max_new_tokens: int = 256
    temperature: float = 0.7
    top_p: float = 0.95
    top_k: int = 0
    constraint: Optional[TokenConstraint] = None
    # Lazy constraint (double-buffered admission): when set (and
    # ``constraint`` is None), the row's FSM is built at ADMISSION time
    # — by the batcher's background prep thread while the device runs
    # the previous window, falling back to an inline build. A 20k-row
    # job stops instantiating 20k FSMs up front, and steady-state
    # admission host time hides behind device windows.
    constraint_factory: Optional[Callable[[], TokenConstraint]] = None
    # written ONLY by the prep thread, consumed once by the scheduler
    # thread at admission (single-assignment handoff; the scheduler
    # never blocks on it)
    prepped_constraint: Optional[TokenConstraint] = None
    prep_queued: bool = False
    # Reference `truncate_rows` semantics (sdk.py:457,480): True => over-long
    # prompts are truncated to fit the context; False => the row fails.
    allow_truncate: bool = True
    # Per-row sampling seed (`random_seed_per_input`): when set, this row's
    # tokens are drawn from keys folded from (row_seed, step) — reproducible
    # regardless of batch composition.
    row_seed: Optional[int] = None
    # Stop SEQUENCES (byte strings): generation ends once any appears in
    # the decoded output (detection here via a rolling byte tail; exact
    # text truncation happens at the result-rendering layer, which has
    # the full decoded string). Requires the batcher's ``token_bytes``.
    stop_seqs: Optional[List[bytes]] = None
    # vLLM-style sampling penalties over GENERATED tokens (defaults
    # disable). Rows using them decode single-step (the host threads
    # token counts between steps).
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    # Generation by blocks (a model with ``block_length`` > 1; None / 0:
    # the model's own): the denoising forwards a block takes, the rule
    # that picks the positions a forward fills (``configs.REMASKING``)
    # and the dynamic rule's threshold
    denoising_steps: int = 0
    remasking: Optional[str] = None
    confidence_threshold: Optional[float] = None

    def has_penalties(self) -> bool:
        return (
            self.presence_penalty != 0.0
            or self.frequency_penalty != 0.0
            or self.repetition_penalty != 1.0
        )



@dataclasses.dataclass
class GenResult:
    row_id: int
    token_ids: List[int]
    cumulative_logprob: float
    # "stop" | "length" | "schema_complete" | "cancelled" |
    # "error" | "error_too_long" | "error_capacity"
    finish_reason: str
    input_tokens: int
    # quarantine message for error_* rows (row-level failure domain):
    # the jobstore lands it in the results ``error`` column; None for
    # clean rows
    error: Optional[str] = None


@dataclasses.dataclass
class _SharedPrefix:
    """A job-wide common token prefix prefilled ONCE into shared pages.
    Every slot's page table starts with these pages (read-only — decode
    and suffix prefill write only at positions >= ``tokens``, which land
    in the slot's own pages); the allocator frees them at end of run,
    not per slot. Templates guarantee the headline workload has one
    (reference templates/classification.py builds a single prompt shell
    for all rows)."""

    tokens: int              # shared length, a multiple of kv_page_size
    pages: List[int]
    # cross-job radix store (engine/prefixstore.py): ``handle`` pins the
    # store-owned head of ``pages``; ``own_pages`` is the session-owned
    # tail to free at release (None = the whole list, the storeless
    # per-job path). Release via _release_prefix, never raw frees.
    handle: Optional[Any] = None
    own_pages: Optional[List[int]] = None

    @property
    def n_pages(self) -> int:
        return len(self.pages)


@dataclasses.dataclass
class JobCtx:
    """One job's slice of a (possibly multi-job) batcher session.

    Cross-job co-batching (VERDICT r3 next-step 3): the reference's
    fleet implicitly multiplexes many users' jobs over shared capacity
    (/root/reference/sutro/sdk.py:202-216 — jobs are independent
    submissions against one service); here same-model jobs share the
    decode batch. Admission pulls rows across jobs in (priority, seq)
    order, every slot carries its job, and results/progress/accounting
    stream through the job's own callbacks — a p0 3-row job admitted
    mid-flight of a p1 20k-row job rides free slots to completion
    without preempting p1's active rows."""

    job_id: str
    pending: List[GenRequest]
    on_result: Callable[["GenResult"], None]
    priority: int = 0
    seq: int = 0             # FIFO tiebreak within a priority
    on_progress: Optional[Callable[[Dict[str, Any]], None]] = None
    should_cancel: Optional[Callable[[], bool]] = None
    progress_every: float = 1.0
    # Row-level failure domain: a row whose decode/constrain raises is
    # re-admitted as a FRESH request up to ``row_retries`` times, then
    # quarantined as an error result (the job still completes).
    # ``on_row_event`` is the failure_log sink — every retry/quarantine
    # event streams through it (engine wires it to the jobstore).
    row_retries: int = 0
    on_row_event: Optional[Callable[[Dict[str, Any]], None]] = None
    row_attempts: Dict[int, int] = dataclasses.field(default_factory=dict)
    # Interactive serving tier (serving/gateway.py): ``on_token`` streams
    # every accepted token to the request's channel the moment the single
    # commit point (_accept_token) records it — all decode paths (single
    # step, fused windows, speculative window, fast-forward) converge
    # there, so per-token streaming needs exactly one hook. ``interactive``
    # marks the ctx as a latency-priority request that may preempt batch
    # rows inside the EngineConfig.interactive_slots budget.
    on_token: Optional[Callable[[int, int, float], None]] = None
    interactive: bool = False
    # Session KV checkpointing (serving/gateway.py chat sessions): when
    # True — set by the gateway only while the tiered pool is on — a
    # finished row's page-aligned KV transfers into the radix prefix
    # store at release instead of being freed, so the session's NEXT
    # turn resumes by prefix hit (and tier promotion once the pages
    # demote) instead of re-prefilling the whole conversation.
    kv_checkpoint: bool = False
    # Stage-graph streaming handoff (engine/stagegraph.py): a downstream
    # stage's ctx starts with an EMPTY pending list and is fed rows as
    # upstream chunks finalize. ``hold_open() -> True`` keeps _sweep_done
    # from declaring the ctx complete while its feeders still run; the
    # executor flips it False once every upstream stage has drained.
    hold_open: Optional[Callable[[], bool]] = None
    # -- internal session state --
    prefix: Optional[_SharedPrefix] = None
    prefix_ready: bool = False  # _setup_prefix attempted (lazily, at
    #                             first admission opportunity — eager
    #                             setup would pin prefix pages for jobs
    #                             whose rows wait behind a full batch)
    # honest roofline attribution (telemetry/doctor.py): prefix tokens
    # this job got warm from the radix store vs prefix tokens it paid
    # to prefill itself — without the split, the first job eats the
    # whole shell cost in its spans and later jobs look faster than
    # the hardware
    prefix_saved: int = 0
    prefix_paid: int = 0
    stats: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {
            "in": 0, "out": 0, "rows": 0,
            # the job's rows' part of the decode dispatches: row-steps,
            # and beside it ``lost_<reason>`` keys as they occur
            # (_lose; engine/api.py writes perf["decode_yield"])
            "row_steps": 0,
        }
    )
    # forensics trace (telemetry/traces.py): the propagated per-request
    # (gateway-assigned) or per-job (scheduler-assigned) trace id;
    # ``trace_enq_mono`` is the submit/park time the queue_wait span
    # measures from; ``trace_preempted`` holds row ids suspended by a
    # preemption so re-admission emits the matching resume event
    trace_id: Optional[str] = None
    trace_enq_mono: float = 0.0
    trace_preempted: set = dataclasses.field(default_factory=set)
    n_slots: int = 0         # live slots carrying this job
    done: bool = False
    started: float = 0.0
    t_last: float = 0.0


@dataclasses.dataclass
class _Slot:
    req: GenRequest
    pages: List[int]         # FULL table pages (shared prefix + own)
    pos: int                 # tokens currently in cache
    last_token: int
    job: Optional[JobCtx] = None
    shared_n: int = 0        # leading entries of ``pages`` owned by the
    #                          job's _SharedPrefix (not freed per slot)
    # Sarathi-style piggybacked prefill (long prompts): the slot is
    # reserved but advances one prefill chunk per scheduler iteration
    # (_prefill_tick) while OTHER slots keep decoding; it joins the
    # decode batch only once the whole prompt is in pages and its first
    # token is sampled. While prefilling, the slot's dense decode state
    # routes to the garbage page (its table row stays zero) so the
    # discarded decode writes can never clobber prefilled positions.
    prefilling: bool = False
    prefill_pos: int = 0     # next global position to prefill
    ptable: Optional[np.ndarray] = None  # real [MP] table for chunks
    out_ids: List[int] = dataclasses.field(default_factory=list)
    logprob_sum: float = 0.0
    # rolling decoded-byte tail for stop-sequence detection (window =
    # longest stop seq + the current token's bytes)
    tail: bytes = b""
    hit_stop_seq: bool = False
    stop_longest: int = 0  # cached max stop-seq length (set on arm)
    # generated-token counts for sampling penalties (only maintained
    # when the request uses them), plus the packed seen-bitmap for the
    # repetition scope (prompt + generated, vLLM/HF semantics) — built
    # incrementally so the per-step assembly is a memcpy, not an
    # O(vocab) packbits
    counts: Dict[int, int] = dataclasses.field(default_factory=dict)
    seen_bits: Optional[np.ndarray] = None  # uint8 [ceil(V/8)]
    # a block model's row: the prompt tokens past its last whole block,
    # not in the cache yet; they start the row's first generated block
    # and are cleared once that block's window has been accepted
    given: int = 0
    # seated in the decode batch, its first token still on the device:
    # ``last_token`` says nothing until the wave is resolved (``_arm``)
    first_pending: bool = False
    seated: bool = False  # ``_seat`` ran (it runs once a row)


@dataclasses.dataclass
class _Hib:
    """A preempted slot's full host state, parked while its page-aligned
    KV sits in the tiered pool under ``key`` (engine/kvtier.py). Resume
    re-reserves pages, uploads the payload, re-prefills ONLY the
    sub-page tail (pos % page_size tokens), and arms the slot exactly
    where it stopped — the request's live constraint object continues
    in place, so nothing rewinds. A tier miss at resume falls back to
    the pre-tier path: the row regenerates from scratch (its constraint
    rebuilt from the factory, which victim selection guarantees
    exists)."""

    key: bytes               # tier-pool entry key (opaque, unique)
    pos: int                 # tokens whose KV was resident at suspend
    last_token: int
    out_ids: List[int]
    logprob_sum: float
    tail: bytes
    hit_stop_seq: bool
    stop_longest: int
    counts: Dict[int, int]
    seen_bits: Optional[np.ndarray]
    shared_tokens: int       # job shared-prefix coverage at suspend —
    #                          resume requires the SAME coverage (the
    #                          stored payload holds own pages only)
    n_pages: int             # own aligned pages stored under ``key``


class _DecodeFacts(NamedTuple):
    """What the rows of one decode batch need from the step, seen once
    where ``batch_build`` walks them. ``_choose_path`` reads these and
    nothing else."""
    has_constraint: bool      # a row decodes under an FSM
    has_row_seed: bool        # a row's stream must reproduce by itself
    has_penalty: bool         # a row's logits depend on its own counts
    all_greedy: bool          # every row at temperature <= 0
    constrained_greedy: bool  # every constrained row at temperature <= 0
    flagged: bool             # a slot owes its FSM-masked step (_needs_mask)
    room: int                 # least reserved page room of a row, in steps
    wrapped: bool             # the runner has a ring or pipeline wrapper
    #                           (sp or pp > 1): no verify forward there
    constrained: float = 1.0  # the share of the rows that decode under
    #                           an FSM (a window keeps a plain row's every
    #                           token)
    unmasked_ok: float = 1.0  # the share of the constrained greedy rows'
    #                           UNMASKED tokens their FSMs accept, as the
    #                           last few dispatches saw it (_observe)
    stepping: bool = False    # those rows' last dispatch was a masked step


# The constrained greedy batch, window or masked step (_choose_path). A
# window of K steps commits a constrained row E = (1 - p^K) / (1 - p)
# tokens (its masked first step, then unmasked tokens until the FSM refuses
# one, each accepted with probability p) and a plain row K, for K steps of
# the device and one round of host work; a masked step commits every row 1
# for one step and one round. In units of a window's device step, a
# masked step costs _STEP_COST, device and host together, and a window
# K + _WINDOW_HOST: the window's tokens a second over the step's are
# E * _STEP_COST / (K + _WINDOW_HOST). The two constants are the classify
# cell's, read again once a batch of greedy rows was sampled by its argmax
# alone (PERF.md section 6, PR 53, part_table.py and phase_table.py of the
# cell on one v5e, the profiler on: a masked step 22.3 ms of device, which
# writes K/V a step and takes the argmax under the mask, 29.6 while it
# paid for the sampler's exact head, and 9.5 ms of host round it, an
# iteration 31.8 ms; the same cell held on windows, a window of 8
# 162.7 ms of device = 20.3 ms a step, 20.8 with the approximate head a
# greedy window no longer sorts, and 11.6 ms round it, the 2.1 ms of its
# commit among them; the fast-forward probe, which runs ahead of either,
# in neither): with every row constrained and K = 8 the line lies at
# E ~ 5.5, p ~ 0.89 (E ~ 4.4, p ~ 0.82 at PR 48's 1.97 and 0.68). Only a
# batch near the line feels them: at p = 0 a window is worth a fifth of
# the steps it displaces, at p = 1 one and a half times.
_STEP_COST = 1.57
_WINDOW_HOST = 0.57
# a batch near the line stays where it is: the other path has to be worth
# a tenth more (p under ~0.86 leaves the windows, over ~0.92 returns)
_SWITCH_GAIN = 1.1
# The most of a batch's rows that may END in the windows in flight for one
# more window to go out ahead while rows wait for slots
# (``_pipe_capacity_ok``). Held back, the window costs the device the host's
# round between a fetch and the next dispatch; sent ahead, it costs the
# ending rows' share of its row-steps (``stale``), which rows that wait
# would have used a window sooner. Where the two meet was measured, a cell
# a side (PERF.md section 6, PR 58, my chip runs): a sixteenth of the batch
# ending at a time (the long-output cells: +8 to +12 %) and an eighth
# (Mellum 2: +6.2 %) are worth sending; a quarter is not (LFM2's short
# jobs: -2.8 % with 3.3 % of the row-steps stale, granite's: inside its
# spread with or without the rule); at a half the stage graph's rows, a
# window long, lost their overlap (``tests/test_stagegraph.py``). The line
# is put between the eighth and the quarter.
_AHEAD_ENDING = 3 / 16


def _window_gain(p: float, K: int, constrained: float = 1.0) -> float:
    """A speculative window's tokens a second over a masked step's, for
    a batch of which the share ``constrained`` decodes under FSMs that
    accept an unmasked token with probability ``p``."""
    E = float(K) if p >= 1.0 else (1.0 - p ** K) / (1.0 - p)
    E = constrained * E + (1.0 - constrained) * K
    return E * _STEP_COST / (K + _WINDOW_HOST)


class _DecodeBatch(NamedTuple):
    """One iteration's decode batch: the active slots, the dense [B]
    operands every runner entry point takes, and the facts that choose
    the path."""
    active: List[int]
    last: np.ndarray          # [B] int32
    past_len: np.ndarray      # [B] int32
    table: np.ndarray         # [B, MP] int32
    temp: np.ndarray          # [B] float32
    top_p: np.ndarray         # [B] float32
    top_k: np.ndarray         # [B] int32
    row_seeds: np.ndarray     # [B] int32
    facts: _DecodeFacts


class _WaveEntry(NamedTuple):
    """One prefill dispatch of the admission wave."""

    # (slot index, slot) pairs its first tokens arm; none for a chunk
    # that is not its row's last and only has routing counts to hand in
    rows: Any
    tok: Any    # [B] first tokens, on the device (None without rows)
    logp: Any   # [B] their log-probabilities
    route: Any  # the dispatch's routing counts, or None
    tokens: int  # prompt tokens it prefilled


def _lose(
    lost: Dict[str, int], ctx: Optional[JobCtx], reason: str, n: int
) -> None:
    """``n`` row-steps of one row of a decode dispatch committed
    nothing: into the dispatch's tally ``lost`` and the record of the
    row's job. Called once a row and reason, never a token."""
    if n:
        lost[reason] = lost.get(reason, 0) + n
        if ctx is not None:
            key = "lost_" + reason
            ctx.stats[key] = ctx.stats.get(key, 0) + n


class ContinuousBatcher:
    # what a row needs of each pool and what the pages support: the
    # runner's (set in __init__). THE default, for a runner that offers
    # no description (a test's stub): a single K/V pool that supports
    # everything, behind verbs that return at once
    _pools = RowPools(ONE_POOL)
    _layout = ONE_POOL
    _tier_refused = False
    # positions a block of the runner's model holds (1: a causal model)
    _block = 1

    def __init__(
        self,
        runner: ModelRunner,
        stop_ids: List[int],
        *,
        seed: int = 0,
        token_bytes=None,  # tokenizer token_bytes(id) -> bytes; enables
        #                    GenRequest.stop_seqs detection
        prefix_store=None,  # engine-lifetime radix prefix store
        #                     (engine/prefixstore.py); None = today's
        #                     per-job prefix path, bit-identical
        kv_tier=None,  # tiered paged-KV pool (engine/kvtier.py):
        #                HBM -> host -> disk page migration + session
        #                hibernation; None = untiered, bit-identical
    ):
        self.runner = runner
        self.ecfg = runner.ecfg
        self.vocab = runner.mcfg.vocab_size
        # an unconstrained row's bit-packed mask: every id allowed, the
        # last byte's bits past the vocabulary zero
        self._ones_row = np.packbits(np.ones((self.vocab,), bool))
        self.stop_ids = set(int(s) for s in stop_ids)
        self.token_bytes = token_bytes
        self.B = self.ecfg.decode_batch_size
        self.MP = self.ecfg.max_pages_per_seq
        # the runner has a ring (sp) or pipeline (pp) wrapper: the paged
        # prefill from start > 0 and the verify forward have none
        self._wrapped = (
            getattr(runner, "sp", 1) != 1 or getattr(runner, "pp", 1) != 1
        )
        # hot-loop caches: max_context() and the stop-id membership are
        # consulted per accepted token (O(B*K) per window)
        self._max_ctx = self.ecfg.max_context()
        self._stop_arr = np.array(sorted(self.stop_ids), np.int64)
        # Native host runtime (native/runtime.cpp): page allocator +
        # admission + dense step-state arrays as zero-copy views. Falls
        # back to the pure-Python allocator when the toolchain is absent
        # or SUTRO_NATIVE_RUNTIME=0.
        from .native_runtime import maybe_native_runtime

        # what a row needs of each pool beside the page free list, and
        # what the pages support: the runner's description of its pools
        # (kvcache.RowPools, CacheLayout). The scheduler asks the
        # description, never which family it serves
        self._pools = getattr(runner, "pools", self._pools)
        layout = self._layout = self._pools.layout
        alloc_pages = runner.num_pages
        # A model that generates by BLOCKS (``ModelConfig.block_length``):
        # a row's ``pos`` stays a multiple of the block, a window yields
        # whole blocks a row and admission samples no first token
        self._block = layout.block_length
        self._bd_forwards = 0.0  # forwards the last fetched window ran
        self.native = (
            None if layout.refuses("native") else maybe_native_runtime(
                alloc_pages, self.B, self.MP, self.ecfg.kv_page_size,
                self.ecfg.max_batch_tokens, self.ecfg.max_context(),
            )
        )
        self.allocator = (
            None if self.native is not None
            else PageAllocator(alloc_pages)
        )
        # Where the pages cannot move to the tiers or be shared between
        # rows (``CacheLayout.refuses`` says why, family by family) those
        # paths fall back to prefilling again, and say so
        # (sutro_state_fallback_prefill_tokens_total). A new session's
        # pages are all free, so whatever else a row holds is too.
        self._tier_refused = (
            layout.refuses("tiers") is not None and kv_tier is not None
        )
        if layout.refuses("tiers"):
            kv_tier = None
        if layout.refuses("share"):
            prefix_store = None
        self._pools.reset()
        # Cross-job radix prefix store: its pages live in THIS runner's
        # KV pool but the store outlives the session, so the fresh free
        # list above must give them up before any admission. A store
        # whose pages cannot be re-reserved (pool geometry changed, or
        # a mismatched page size) resets to empty instead of poisoning
        # the run — the ids are already free here, so forgetting the
        # tree is the only consistent move.
        self._prefix_store = None
        if (
            prefix_store is not None
            and prefix_store.page_size == self.ecfg.kv_page_size
        ):
            owned = prefix_store.owned_pages()
            ok = all(0 < p < alloc_pages for p in owned)
            if ok and owned:
                if self.native is not None:
                    ok = self.native.reserve_pages(owned)
                else:
                    try:
                        # the store keeps ownership of these pages (it
                        # frees them via reset()/eviction); reserve only
                        # marks them busy in the allocator's free list
                        self.allocator.reserve(owned)  # graftlint: disable=resource-leak
                    except KeyError:
                        ok = False
            if ok:
                self._prefix_store = prefix_store
            else:
                prefix_store.reset()
                self._prefix_store = prefix_store
        # Tiered paged-KV pool (engine/kvtier.py): page payloads below
        # HBM. Cold prefix-store leaves DEMOTE into it instead of
        # evicting, suspended rows HIBERNATE their pages there and
        # resume by page-upload, and completed session turns checkpoint
        # through the prefix store into it. A geometry mismatch
        # disables tiering for this session (the payloads would not be
        # page-compatible). Hibernation captures the partial tail page
        # too (ceil(pos/PS) own pages), so resume is a PURE page-upload
        # — no suffix prefill — which is why multi-page-group (sp/pp)
        # rows hibernate as well: read_pages/write_pages are
        # sharding-agnostic host copies, unlike runner.prefill(start>0).
        self._kv_tier = None
        if kv_tier is not None and kv_tier.page_size == self.ecfg.kv_page_size:
            self._kv_tier = kv_tier
        self._can_hibernate = self._kv_tier is not None
        # hibernated rows: (id(ctx), row_id) -> _Hib. Entries live only
        # while their ctx is live in THIS session (purged at job finish
        # / session suspend / run_multi exit), so id() reuse is safe.
        self._hibernated: Dict[Tuple[int, int], "_Hib"] = {}
        self._hib_seq = 0
        # session-level tier op counters (api.py stamps them into the
        # job's flight-recorder attrs for the doctor's kv_pressure /
        # resume_bound verdicts)
        self.tier_demotes = 0
        self.tier_promotes = 0
        self.slots: List[Optional[_Slot]] = [None] * self.B
        # per-slot generation counter: bumped on release so a pipelined
        # window dispatched against a slot's OLD occupant fails the
        # (slot, gen) check at processing time after the slot is reused
        self._gen = [0] * self.B
        # resident where the runner can make it so: the session key
        # comes back from the admission sample's program (a committed
        # result), and a program compiles once more for a committed
        # argument where it had an upload
        resident = getattr(runner, "resident", None) or (lambda x: x)
        self._resident = resident
        self._key = resident(jax.random.PRNGKey(seed))
        self._fixed_key = jax.random.PRNGKey(seed)
        self._step = 0
        # slot indices whose speculative window rejected a token: each
        # takes its FSM-masked step inside the NEXT window (allowed0),
        # so one adversarial row doesn't degrade the batch to masked
        # single-steps; only non-greedy constrained batches still fall
        # back to the masked single-step path
        self._needs_mask: set = set()
        # what _choose_path's window-or-step rule reads: the running
        # share of the constrained greedy rows' unmasked tokens that
        # their FSMs accepted (_observe; before any observation 1.0,
        # which says "window"), this dispatch's (accepted, asked) on the
        # way into it, and whether those rows' last dispatch was a
        # masked step
        self._unmasked_ok = 1.0
        self._asked = [0, 0]
        self._stepping = False
        # penalty id-buffer growth events already logged (power-of-two K)
        self._pk_grown: set = set()
        # FSM fast-forward ("jump decoding"): forced scaffold tokens
        # committed through parallel verify forwards instead of
        # step-by-step windows. The probe backoff bounds the O(B x V)
        # singleton scan on batches sitting in free-text regions; it
        # counts DISPATCHES (_ff_wait: those still to go by without a
        # probe), windows and masked steps alike
        self.ff_forced = 0
        self._ff_wait = 0
        self._ff_backoff = 0
        # shared-prefix KV reuse (one per run; see _setup_prefix)
        self._prefix: Optional[_SharedPrefix] = None
        # preemptive priority ladder (engine/control.py): installed by
        # the engine when the control plane is on; None = the batch
        # path is bit-identical to a ladder-less build
        self.ladder = None
        # tokens actually sent through a prefill program this run —
        # the instrument proving the prefix cache's N-fold prefill
        # saving (input_tokens in progress streams stays the per-row
        # FULL prompt count: user-facing accounting is unchanged)
        self.prefill_tokens = 0
        # Double-buffered admission prep: a background thread builds
        # the NEXT admission group's lazy constraints while the device
        # runs the current window, so FSM instantiation leaves the
        # critical path. prep_overlap_s / prep_inline_s split the prep
        # cost into hidden-behind-device vs paid-inline for the host
        # overhead profile.
        self._prep_thread: Optional[Any] = None
        self._prep_q: Optional[Any] = None
        self.prep_overlap_s = 0.0
        self.prep_inline_s = 0.0
        self.prep_rows_overlapped = 0
        import threading as _threading

        # guards the overlap counters: _prep_stop joins with a timeout,
        # so a wedged worker can briefly coexist with its replacement —
        # two threads may then bump these counters concurrently
        self._prep_lock = _threading.Lock()
        from .profiling import StepTimer

        # telemetry latch (one decision per batcher, zero per-step cost
        # when off): the timer sink feeds every device-dispatch phase
        # into the stage histogram + flight recorder; _tel_jobs carries
        # the live co-batched job ids so batch-wide spans are
        # attributable per job
        self._tel_on = telemetry.enabled()
        self._tel_jobs: Tuple[str, ...] = ()
        # live co-batched trace ids (subset of _tel_jobs' ctxs that
        # carry one): batch-wide spans fan into each request's forensic
        # timeline (telemetry/traces.py)
        self._tel_traces: Tuple[str, ...] = ()
        # per-window device-time attribution (doctor roofline grades):
        # the decode/prefill loops stash {stage: {batch, steps, ...}}
        # here right before dispatch; the sink folds it into the span
        self._tel_attrs: Dict[str, Dict[str, Any]] = {}
        # the last routing counts fetched, by stage: a span dispatched
        # before its own counts are back carries the previous ones
        self._route_attrs: Dict[str, Dict[str, Any]] = {}
        # with telemetry on the timer is also the scheduler's phase
        # cursor: every instant of run_multi belongs to one named phase
        self.timer = StepTimer(
            sink=self._tel_sink if self._tel_on else None,
            cursor=self._tel_on,
            stage_names=_TEL_STAGE,
            opened=self._tel_opened if self._tel_on else None,
        )
        # the admission wave: prefills dispatched whose first tokens are
        # still on the device, in dispatch order; _resolve_wave fetches
        # the lot in one host sync. When its first dispatch went out,
        # and how many waves came before it (span attrs)
        self._wave: List[_WaveEntry] = []
        self._wave_t0 = 0.0
        self._wave_seq = 0
        # the slots' first tokens where admission's sample writes them,
        # [B] on the device (made by the first sample): a window that
        # goes out before its wave is resolved takes them from there
        # (runner.merge_first; a stand-in runner has none, and its
        # waves are resolved before the build). A block model samples
        # no first token and needs none
        self._first_dev: Any = None
        # a row is waiting for a slot (run_multi, once an iteration): a
        # slot freed a window sooner has a taker
        self._rows_waiting = False
        self._joins_on_device = self._block > 1 or callable(
            getattr(runner, "merge_first", None)
        )
        # tokens committed by the accept loops (the accept span's attr)
        self._n_accepted = 0
        # what the dispatch just accepted yielded: (row-steps, tokens
        # committed, {reason: row-steps lost}), left by _close_accept
        # for _after_step, which counts it under the iteration's path
        # (OBSERVABILITY.md "What a decode dispatch yields")
        self._yield: Tuple[int, int, Dict[str, int]] = (0, 0, {})

    def _close_accept(
        self, n0: int, row_steps: int, lost: Dict[str, int]
    ) -> None:
        """The end of a dispatch's accept loop. Its yield (row-steps,
        the tokens committed since ``n0``, the row-steps ``_lose``
        counted by reason) goes onto the ``accept`` span, and waits for
        ``_after_step`` to count it under the iteration's path.
        Row-steps = tokens + the lost, on every path."""
        n = self._n_accepted - n0
        self._yield = (row_steps, n, lost)
        ok, asked = self._asked
        if asked:
            # a memory of a few dispatches: half of it the newest one
            self._unmasked_ok = 0.5 * (self._unmasked_ok + ok / asked)
            self._asked = [0, 0]
        if lost:
            self.timer.enter(
                "accept", tokens=n, row_steps=row_steps, lost=lost
            )
        else:
            self.timer.enter("accept", tokens=n, row_steps=row_steps)

    def _observe(self, ctx: Optional[JobCtx], ok: int, asked: int) -> None:
        """``asked`` unmasked greedy tokens of one constrained row of
        this dispatch were held against its FSM (a window's speculative
        positions up to its first refusal; a masked step's unmasked
        argmax, which the step's program looked up in the mask it
        held; a verify forward's plain argmax at each planned position
        it reached, against the position's candidates), and ``ok`` of
        them were valid: into this dispatch's observation, which
        ``_close_accept`` folds into the estimate, and into the record
        of the row's job."""
        if asked:
            self._asked[0] += ok
            self._asked[1] += asked
            if ctx is not None:
                st = ctx.stats
                st["unmasked_ok"] = st.get("unmasked_ok", 0) + ok
                st["unmasked_asked"] = st.get("unmasked_asked", 0) + asked

    def _tel_opened(self, phase: Optional[str], t0: float) -> None:
        """The phase this thread is in NOW, for whoever snapshots the
        flight recorder before it ends (a plan walk can run for
        seconds)."""
        telemetry.RECORDER.mark_open(
            phase and _TEL_STAGE.get(phase, phase), t0,
            {"jobs": self._tel_jobs} if self._tel_jobs else None,
        )

    def _tel_sink(
        self, phase: str, t0: float, dt: float, cpu_s: float,
        attrs: Optional[Dict[str, Any]],
    ) -> None:
        stage = _TEL_STAGE.get(phase, phase)
        quiet = stage in _QUIET_STAGES
        # stage exemplar: point the aggregate histogram at one live
        # request's trace so a slow-bucket sample is resolvable
        telemetry.stage_observe(
            stage, dt,
            exemplar=(
                self._tel_traces[0]
                if self._tel_traces and not quiet else None
            ),
        )
        extra = self._tel_attrs.get(stage)
        if attrs:
            extra = {**extra, **attrs} if extra else attrs
        if stage == "decode_window":
            # what the dispatches made under this span fetched of the
            # pool, and what their rows' tokens fill (runner)
            take = getattr(self.runner, "take_kv_pages", None)
            pages = take() if take is not None else None
            if pages is not None:
                extra = {
                    **(extra or {}),
                    "kv_pages_fetched": round(pages[0], 1),
                    "kv_pages_needed": round(pages[1], 1),
                }
        rec = dict(extra) if extra else {}
        # wall minus CPU in a pure-Python phase is time the scheduler
        # thread waited for the GIL (prep thread, tokenizer, streams)
        rec["cpu_s"] = round(cpu_s, 6)
        if self._tel_jobs:
            rec["jobs"] = self._tel_jobs
        telemetry.RECORDER.record(stage, None, t0, dt, rec)
        if not quiet:
            for tid in self._tel_traces:
                telemetry.TRACES.add(tid, stage, t0, dt, extra)

    # ------------------------------------------------------------------

    @property
    def free_page_count(self) -> int:
        if self.native is not None:
            return self.native.free_count
        return self.allocator.free_count

    def _max_total(self, req: GenRequest) -> int:
        return min(
            len(req.prompt_ids) + req.max_new_tokens,
            self.ecfg.max_context(),
        )

    def _inflight_tokens(self) -> int:
        return sum(
            self._max_total(s.req) for s in self.slots if s is not None
        )

    def _setup_prefix(self, ctx: JobCtx) -> None:
        """Detect the job's longest common PAGE-ALIGNED token prefix and
        prefill it once into shared pages (VERDICT r3 missing #5: the
        single largest chip-independent win for templated jobs — the
        reference's classify template sends one prompt shell for every
        row). Capped at min(len)-1 so every row still prefills >= 1 own
        token (its last-position logits seed the first sample). Skipped
        when: disabled, < 2 rows (1 with the radix store: a lone
        interactive request can hit — and seed — a cross-job shell),
        prefix < 1 page, the pages would starve admission, or under
        sp/pp (suffix prefill rides the chunked paged path, which
        neither wraps).

        With the engine-lifetime radix store attached this becomes
        LOOKUP → EXTEND → INSERT: the warm head of the shell pins
        store pages (prefilled by an EARLIER job — only the novel tail
        is prefilled here, at its offset), and the freshly prefilled
        tail transfers into the tree for the next job. A store crash
        during lookup (fault site ``prefixstore.lookup``) degrades to
        a plain miss — the job pays full prefill but never fails.
        Without a store: per-JOB pages, exactly the pre-store path.
        A job whose rows share no page of prefix, or whose model keeps
        none, returns before anything is touched, and the admission wave
        in front of it stays as it is; where a prefix is set up (pages
        taken, perhaps evicted, a prefill waited for) the wave is
        resolved first."""
        ctx.prefix = None
        pending = ctx.pending
        ecfg = self.ecfg
        store = self._prefix_store
        if not getattr(ecfg, "prefix_cache", True):
            return
        if len(pending) < (1 if store is not None else 2):
            return
        if self._wrapped:
            return
        PS = ecfg.kv_page_size
        first = pending[0].prompt_ids
        lcp = min(len(r.prompt_ids) for r in pending) - 1
        for r in pending[1:]:
            if lcp <= 0:
                return
            neq = np.nonzero(
                first[:lcp] != r.prompt_ids[:lcp]
            )[0]
            if len(neq):
                lcp = int(neq[0])
        shared = (lcp // PS) * PS
        if shared < PS:
            return
        why = self._layout.refuses("share")
        if why is not None:
            # the pages cannot be shared: every row prefills its own
            self._count_state_fallback(shared * (len(pending) - 1), why)
            if why == "prefix_on_block_model" and self._tel_on:
                telemetry.BLOCK_REFUSALS_TOTAL.inc(1.0, "shared_prefix")
            return
        self._resolve_wave()
        n_pages = shared // PS
        # warm head from the radix store (pins the matched path);
        # any store raise is a plain miss — never a job failure
        handle = None
        if store is not None:
            try:
                if faults.ACTIVE is not None:
                    faults.inject("prefixstore.lookup", job=ctx.job_id)
                handle = store.lookup_pin(first[:shared])
                if not handle.nodes:
                    handle = None
            except Exception:
                logger.debug(
                    "prefix-store lookup failed; treating as miss",
                    exc_info=True,
                )
                handle = None
        try:
            if (
                store is not None
                and self._kv_tier is not None
                and (len(handle.nodes) if handle else 0) < n_pages
            ):
                # tier promotion: pages past the store hit may be warm
                # in the host/disk tiers (demoted earlier under
                # pressure, or an idle session's checkpoint) — upload
                # them into fresh pages instead of re-prefilling. Net
                # zero page pressure: each promoted page replaces a
                # tail page the prefill below would have allocated.
                handle = self._promote_prefix(ctx, first, n_pages, handle)
            hit_pages = list(handle.pages) if handle is not None else []
            hit = len(hit_pages) * PS
            tail_n = n_pages - len(hit_pages)
            # don't let the prefix starve admission: after taking its
            # NEW pages the WIDEST pending row must still fit. Under
            # pressure, unpinned LRU store pages are evicted back into
            # the free list first — live jobs always win over cached
            # shells.
            worst_own = max(
                pages_needed(self._max_total(r), PS) for r in pending
            ) - n_pages
            need_free = tail_n + max(worst_own, 1)
            if self.free_page_count < need_free:
                self._evict_store_pages(need_free - self.free_page_count)
            if self.free_page_count < need_free:
                if handle is not None:
                    store.release(handle)
                return
            if tail_n == 0:
                # full warm hit: nothing to prefill, nothing to insert
                ctx.prefix = _SharedPrefix(
                    tokens=shared, pages=hit_pages, handle=handle,
                    own_pages=[],
                )
                ctx.prefix_saved += shared
                if self._tel_on and ctx.trace_id is not None:
                    telemetry.TRACES.event(
                        ctx.trace_id, "prefix_hit",
                        {"saved_tokens": int(shared),
                         "paid_tokens": 0},
                    )
                return
            if self.native is not None:
                pages = self.native.alloc_pages(tail_n)
                if pages is None:
                    if handle is not None:
                        store.release(handle)
                    return
            else:
                pages = self.allocator.alloc(tail_n)
        except Exception:
            # eviction/allocation raising must not strand the pin — a
            # handle that never unpins blocks those pages from eviction
            # for the life of the store
            if handle is not None:
                store.release(handle)
            raise
        paid = shared - hit
        try:
            table = np.zeros((self.MP,), np.int32)
            table[: len(hit_pages)] = hit_pages
            table[len(hit_pages) : n_pages] = pages
            if self._tel_on:
                attrs = {
                    "tokens": int(paid), **self._prefill_attrs((paid,)),
                }
                if store is not None:
                    attrs["prefix_saved"] = int(hit)
                    attrs["prefix_paid"] = int(paid)
                self._tel_attrs["prefill"] = attrs
            with self.timer.time("prefill"):
                # last-position logits are discarded: each row derives
                # its first sample from its OWN suffix prefill. Only
                # the novel tail runs, at its global offset — the warm
                # head is already resident in the store's pages.
                self.runner.prefill(
                    np.asarray(first[hit:shared], np.int32), table,
                    start=hit,
                )
        except Exception:
            # pin first (a cheap decref that cannot fail), pages second
            # — if the page free itself raises, the pin must already be
            # returned
            if handle is not None:
                store.release(handle)
            self._free_prefix_pages(pages)
            raise
        self.prefill_tokens += paid
        ctx.prefix_saved += hit
        ctx.prefix_paid += paid
        try:
            own = list(pages)
            if store is not None:
                h = (
                    handle if handle is not None else store.empty_handle()
                )
                if store.extend(h, first[hit:shared], list(pages)):
                    handle, own = h, []  # tail ownership moved to the
                    #                      store
                # extend declined (closed store): the tail stays
                # session-owned; a non-empty original handle still pins
                # the head
        except Exception:
            # a store raise mid-extend must not strand the pin (or the
            # freshly prefilled tail pages, which the store declined);
            # pin first — it cannot fail
            if handle is not None:
                store.release(handle)
            self._free_prefix_pages(pages)
            raise
        if handle is not None and not handle.nodes:
            handle = None
        ctx.prefix = _SharedPrefix(
            tokens=shared, pages=hit_pages + list(pages),
            handle=handle, own_pages=own,
        )
        if self._tel_on and ctx.trace_id is not None:
            if hit:
                telemetry.TRACES.event(
                    ctx.trace_id, "prefix_hit",
                    {"saved_tokens": int(hit),
                     "paid_tokens": int(paid)},
                )
            if store is not None and not own:
                # freshly prefilled tail transferred into the radix
                # tree — the next job's warm head
                telemetry.TRACES.event(
                    ctx.trace_id, "prefix_extend",
                    {"tokens": int(paid)},
                )

    def _free_prefix_pages(self, pages: List[int]) -> None:
        if self.native is not None:
            self.native.free_pages(pages)
        else:
            self.allocator.free(pages)

    def _release_prefix(self, pfx: _SharedPrefix) -> None:
        """The ONLY way a _SharedPrefix goes away: unpin the store-owned
        head (the pages STAY resident — and out of the allocator — for
        the next job; that's the cache) and free the session-owned
        remainder to the pool."""
        if pfx.handle is not None and self._prefix_store is not None:
            self._prefix_store.release(pfx.handle)
        own = pfx.pages if pfx.own_pages is None else pfx.own_pages
        if own:
            self._free_prefix_pages(own)

    def _evict_store_pages(self, n_pages: int) -> int:
        """Allocation-pressure hook: pull up to ``n_pages`` unpinned LRU
        pages out of the radix store and hand them back to THIS
        session's allocator (they were reserved at construction).
        Returns the number actually freed. With the tiered pool on,
        victims DEMOTE — their payloads migrate to host RAM keyed by
        full token prefix — instead of being dropped, so a later job's
        lookup can promote them back instead of re-prefilling."""
        if n_pages <= 0 or self._prefix_store is None:
            return 0
        if self._kv_tier is not None:
            return self._demote_store_pages(n_pages)
        freed = self._prefix_store.evict(n_pages)
        if freed:
            self._free_prefix_pages(freed)
        return len(freed)

    def _demote_store_pages(self, n_pages: int) -> int:
        """Tiered eviction: pull unpinned LRU leaves out of the radix
        store, read their payloads off the device (one batched
        synchronous fetch — the ids go back to the allocator the moment
        it returns), and stage them into the tier pool asynchronously.
        A read/stage failure degrades that page to a plain eviction;
        the freed count is what matters to the caller either way."""
        pairs = self._prefix_store.demote(n_pages)
        if not pairs:
            return 0
        ids = [p for _, p in pairs]
        with self.timer.time("kv_demote"):
            try:
                raw = self.runner.read_pages(ids)
                for j, (path_bytes, _) in enumerate(pairs):
                    per = {
                        k: np.ascontiguousarray(v[:, j : j + 1])
                        for k, v in raw.items()
                    }
                    self._kv_tier.put_page(path_bytes, per)
                self.tier_demotes += len(pairs)
            except Exception:  # noqa: BLE001 — a failed read degrades
                # to a plain eviction; the pages still free below
                logger.warning(
                    "kv tier demotion read failed; evicting plainly",
                    exc_info=True,
                )
        self._free_prefix_pages(ids)
        return len(pairs)

    def _promote_prefix(self, ctx: JobCtx, first, n_pages: int, handle):
        """Probe the tier pool for consecutive prefix pages past the
        radix-store hit, upload them into freshly allocated pages, and
        graft them onto ``handle`` (``store.promote``). Returns the
        possibly-extended handle; every failure path returns the
        original handle and the caller pays plain tail prefill — a
        tier problem never fails a job."""
        store = self._prefix_store
        tier = self._kv_tier
        PS = self.ecfg.kv_page_size
        k = len(handle.nodes) if handle is not None else 0
        hits: List[Tuple[bytes, dict]] = []
        while k + len(hits) < n_pages:
            key = tier.prefix_key(first[: (k + len(hits) + 1) * PS])
            p = tier.get_page(key)
            if p is None:
                break  # consecutive run only: page i is useless
                #        without page i-1 (causal attention)
            hits.append((key, p))
        if not hits:
            return handle
        if self._layout.has_state and any(
            "c" not in p for _, p in hits
        ):
            # pages without their conv state cannot restore the state
            # at the position the tail would resume from: refuse the
            # hit, pay the plain tail prefill, and count it
            if self._tel_on:
                telemetry.STATE_FALLBACK_PREFILL_TOKENS_TOTAL.inc(
                    float(len(hits) * PS), "tier_payload_without_state"
                )
            return handle
        n = len(hits)
        if self.native is not None:
            pages = self.native.alloc_pages(n)
            if pages is None:
                return handle
            pages = list(pages)
        else:
            if n > self.allocator.free_count:
                return handle
            pages = self.allocator.alloc(n)
        try:
            payload = {
                pk: np.concatenate([p[pk] for _, p in hits], axis=1)
                for pk in hits[0][1]
            }
            with self.timer.time("kv_promote"):
                self.runner.write_pages(pages, payload)
        except Exception:  # noqa: BLE001 — degrade to re-prefill
            self._free_prefix_pages(pages)
            logger.warning(
                "kv tier promotion upload failed; re-prefilling",
                exc_info=True,
            )
            return handle
        h = handle if handle is not None else store.empty_handle()
        if not store.promote(h, first[k * PS : (k + n) * PS], pages):
            # racer re-inserted the run / store closed: keep the tier
            # copy, return our upload, pay the plain tail prefill
            self._free_prefix_pages(pages)
            return handle
        tier.discard([key for key, _ in hits])
        self.tier_promotes += n
        if self._tel_on and ctx.trace_id is not None:
            telemetry.TRACES.event(
                ctx.trace_id, "kv_promote", {"pages": n}
            )
        return h

    def _count_state_fallback(self, tokens: int, reason: str) -> None:
        if self._tel_on and tokens > 0:
            telemetry.STATE_FALLBACK_PREFILL_TOKENS_TOTAL.inc(
                float(tokens), reason
            )

    def _reserve(
        self, req: GenRequest, ctx: JobCtx, reserved: int = 0,
        exclude=frozenset(),
    ):
        """Reserve a slot + worst-case pages for ``req``. Returns
        ``(slot_idx, own_pages, table)`` or None. No device work happens
        here — prefill/sampling run in ``_admit_batch`` so several
        reserved rows can share one dispatch. Slots are only *armed*
        there, so same-batch state lives in the arguments: ``reserved``
        carries the worst-case tokens of rows reserved but not yet
        armed, ``exclude`` their slot indices (the native runtime tracks
        both internally — its slots go active at try_admit). With the
        job's shared prefix active, the table head carries the prefix
        pages and only the remainder is allocated per slot."""
        n = len(req.prompt_ids)
        pfx = ctx.prefix
        # admission waits for what the row needs of the other pools as
        # it waits for pages
        room = self._pools.room(
            self._max_total(req), n > self.ecfg.prefill_chunk,
            (lambda: any(
                s is None and i not in exclude
                for i, s in enumerate(self.slots)
            )) if self._tel_on else None,
        )
        if room is None:
            return None

        def _admit_native():
            if pfx is not None:
                return self.native.try_admit_pfx(
                    n, req.max_new_tokens, pfx.pages
                )
            return self.native.try_admit(n, req.max_new_tokens)

        if self.native is not None:
            free_idx = _admit_native()
            if free_idx < 0 and self._prefix_store is not None:
                # allocation pressure: a page shortage may be cached
                # shells, not live rows — evict unpinned LRU store
                # pages into the free list and retry once
                need = pages_needed(
                    self._max_total(req), self.ecfg.kv_page_size
                )
                short = need - self.native.free_count
                if short > 0 and self._evict_store_pages(short):
                    free_idx = _admit_native()
            if free_idx < 0:
                return None
            assert self.slots[free_idx] is None
            table = self.native.table[free_idx]
            pages = self.native.slot_pages(free_idx)  # own pages only
        else:
            free_idx = next(
                (
                    i
                    for i, s in enumerate(self.slots)
                    if s is None and i not in exclude
                ),
                None,
            )
            if free_idx is None:
                return None
            total = self._max_total(req)
            need = pages_needed(total, self.ecfg.kv_page_size)
            if need > self.MP:
                return None
            if self._block > 1:
                # a window writes whole blocks past a cap that falls
                # inside one, and the windows in flight behind it write
                # theirs: room for them where the table has it, so that
                # a row's last windows are as long as its first
                need = min(self.MP, pages_needed(
                    total + self._window_tokens()
                    * (max(self.ecfg.decode_lookahead, 1) + 1),
                    self.ecfg.kv_page_size,
                ))
            npfx = pfx.n_pages if pfx is not None else 0
            # native-clamp parity (rt_try_admit_pfx): a prefix covering
            # the whole need still allocates 1 own page (every row
            # prefills >= 1 own token) and admits while the table row
            # has room — the old `own < 1 -> reject` starved rows whose
            # shared prefix was bigger than their worst case
            own = max(need - npfx, 1)
            if npfx + own > self.MP:
                return None
            if own > self.allocator.free_count:
                # allocation pressure: evict unpinned LRU store pages
                # back into the free list before refusing the row
                self._evict_store_pages(
                    own - self.allocator.free_count
                )
                if own > self.allocator.free_count:
                    return None
            inflight = self._inflight_tokens() + reserved
            if (
                inflight > 0
                and inflight + total > self.ecfg.max_batch_tokens
            ):
                return None
            pages = self.allocator.alloc(own)
            table = np.zeros((self.MP,), np.int32)
            if pfx is not None:
                table[: pfx.n_pages] = pfx.pages
                table[pfx.n_pages : pfx.n_pages + own] = pages
            else:
                table[: len(pages)] = pages
        self._pools.bind(table, pages, room)
        return free_idx, pages, table

    # -- double-buffered admission prep --------------------------------

    def _materialize_constraint(self, req: GenRequest) -> None:
        """Resolve a lazy constraint at admission: take the prep
        thread's handoff when ready, else build inline. Runs on the
        scheduler thread only; after this, ``req.constraint`` never
        changes again (slots rely on it)."""
        if req.constraint is not None or req.constraint_factory is None:
            return
        if faults.ACTIVE is not None:
            faults.inject("constrain.compile", row=req.row_id)
        c = req.prepped_constraint
        if c is not None:
            req.constraint = c
            req.prepped_constraint = None
            return
        t0 = time.monotonic()
        try:
            # scheduler-thread builds only: the prep thread's are
            # ``constraint_prep``
            with self.timer.host("constraint_compile", row=req.row_id):
                req.constraint = req.constraint_factory()
        finally:
            self.prep_inline_s += time.monotonic() - t0

    def _prep_worker(self, q) -> None:
        while True:
            req = q.get()
            if req is None:
                return
            t0 = time.monotonic()
            try:
                if (
                    req.constraint is None
                    and req.prepped_constraint is None
                    and req.constraint_factory is not None
                ):
                    # single-assignment handoff: only this thread
                    # writes prepped_constraint, only the scheduler
                    # consumes it (worst race: the scheduler admitted
                    # the row mid-build and this FSM is dropped)
                    req.prepped_constraint = req.constraint_factory()
                    with self._prep_lock:
                        self.prep_rows_overlapped += 1
            except Exception:
                logger.exception("admission prep failed; admission "
                                 "will rebuild inline")
            dt = time.monotonic() - t0
            with self._prep_lock:
                self.prep_overlap_s += dt
            if self._tel_on:
                # overlapped builds hide behind device windows but are
                # still real work on the timeline — and they hold the
                # GIL against the scheduler thread, whose phases show
                # it as wall over cpu_s
                telemetry.stage_observe("constraint_prep", dt)
                telemetry.RECORDER.record(
                    "constraint_prep", None, t0, dt,
                    {"row": req.row_id, "thread": "prep"},
                )

    def _prep_pump(self, order: List["JobCtx"]) -> None:
        """Queue the NEXT admission group's lazy constraints for the
        background prep thread. Called once per scheduler iteration —
        the builds overlap the device window dispatched below. Admission
        pops from the TAIL of ``ctx.pending``, so the tail is what gets
        prepped; the budget covers two groups (double buffering)."""
        budget = 2 * self.ecfg.prefill_batch_size
        want: List[GenRequest] = []
        for ctx in order:
            if ctx.done:
                continue
            for req in reversed(ctx.pending):
                if budget == 0:
                    break
                budget -= 1
                if (
                    req.constraint is None
                    and req.constraint_factory is not None
                    and not req.prep_queued
                ):
                    want.append(req)
            if budget == 0:
                break
        if not want:
            return
        if self._prep_thread is None or not self._prep_thread.is_alive():
            import queue as _queue
            import threading as _threading

            self._prep_q = _queue.SimpleQueue()
            self._prep_thread = _threading.Thread(
                target=self._prep_worker, args=(self._prep_q,),
                daemon=True, name="sutro-admit-prep",
            )
            self._prep_thread.start()
        for req in want:
            req.prep_queued = True
            self._prep_q.put(req)

    def _prep_stop(self) -> None:
        """End-of-session shutdown: a long-lived engine runs one
        session per job — leaking one thread per job would accumulate."""
        t, self._prep_thread = self._prep_thread, None
        if t is not None and t.is_alive():
            self._prep_q.put(None)
            t.join(timeout=30)
        self._prep_q = None

    def _unreserve(self, slot_idx: int, pages) -> None:
        """Roll back a reservation whose prefill never armed a slot (a
        raised prefill would otherwise leak the slot's pages forever in a
        long-lived daemon)."""
        self._pools.release(pages)
        if self.native is not None:
            self.native.release(slot_idx)
        else:
            self.allocator.free(pages)

    def _admit_batch(self, batch) -> None:
        """``batch`` is a list of ``(req, ctx, slot_idx, pages, table)``
        reservations — possibly spanning JOBS (co-batched admission).
        Dispatches ONE batched prefill and ONE batched first-token
        sample over its logits where they lie, on the device, installs
        the rows' slots and appends the batch to the admission wave:
        nothing is waited for here. ``_resolve_wave`` fetches the first
        tokens and arms the slots, a wave at a time, behind the
        iteration's window where the rows can enter it by the tokens on
        the device (``_hold_wave``); a row whose job streams its tokens
        is resolved at once, so a chat's first token waits for no row
        behind it. Each row prefills its own suffix at its job's
        shared-prefix offset."""
        reqs = [b[0] for b in batch]
        starts = [
            b[1].prefix.tokens if b[1].prefix is not None else 0
            for b in batch
        ]
        tokens = int(
            sum(self._prefill_len(r) - s for r, s in zip(reqs, starts))
        )
        self.timer.count("rows", len(batch))
        try:
            if self._tel_on:
                self._tel_attrs["prefill"] = {
                    "tokens": tokens,
                    "batch": len(batch),
                    "wave": self._wave_seq,
                    # a block model's prompts end in tokens that no
                    # prefill takes (they head the first block)
                    **({"tail_tokens": int(sum(
                        len(r.prompt_ids) - self._prefill_len(r)
                        for r in reqs
                    ))} if self._block > 1 else {}),
                    **self._state_attrs(len(batch)),
                    **self._kv_attrs(len(r.prompt_ids) for r in reqs),
                    **self._stream_attrs("prefill", tokens),
                    **self._prefill_attrs(
                        self._prefill_len(r) - s_ for r, s_ in zip(reqs, starts)
                    ),
                }
            with self.timer.time("prefill"):
                if len(batch) == 1:
                    logits, route = self.runner.prefill(
                        reqs[0].prompt_ids[starts[0] :].astype(np.int32),
                        batch[0][4], start=starts[0], on_device=True,
                    )
                elif any(starts):
                    logits, route = self.runner.prefill_batch_at(
                        [
                            r.prompt_ids[s:].astype(np.int32)
                            for r, s in zip(reqs, starts)
                        ],
                        np.stack([b[4] for b in batch]),
                        starts, on_device=True,
                    )
                else:
                    logits, route = self.runner.prefill_batch(
                        [r.prompt_ids.astype(np.int32) for r in reqs],
                        np.stack([b[4] for b in batch]),
                        on_device=True,
                    )
            self.prefill_tokens += tokens
            # a block model samples nothing from a prefill: its first
            # block starts as the prompt's leftover tokens and masks
            tok, logp = (None, None) if self._block > 1 else (
                self._sample_first(logits, reqs, [b[2] for b in batch])
            )
        except Exception:
            for _, _, slot_idx, pages, _ in batch:
                self._unreserve(slot_idx, pages)
            raise
        rows = []
        for req, ctx, slot_idx, pages, _ in batch:
            pfx = ctx.prefix
            # the slot is taken from here on (_reserve skips it); its
            # first token, and with it a place in a decode batch, comes
            # with the wave
            slot = _Slot(
                req=req,
                pages=(list(pfx.pages) + list(pages)) if pfx else pages,
                pos=self._prefill_len(req),
                last_token=0,
                job=ctx,
                shared_n=pfx.n_pages if pfx else 0,
            )
            ctx.n_slots += 1
            self.slots[slot_idx] = slot
            rows.append((slot_idx, slot))
        self._to_wave(rows, tok, logp, route, tokens)
        if any(b[1].on_token is not None for b in batch):
            self._resolve_wave()

    def _wave_rows(self):
        """The (slot index, slot) pairs the unresolved wave will arm."""
        return [r for e in self._wave for r in e.rows]

    def _to_wave(self, rows, tok, logp, route, tokens: int) -> None:
        if not self._wave:
            self._wave_t0 = time.monotonic()
        self._wave.append(_WaveEntry(rows, tok, logp, route, tokens))

    def _hold_wave(self) -> bool:
        """The end of an iteration's admission. Where every row of the
        wave can enter a fused window by its first token ON THE DEVICE,
        the rows take their places in the batch now (``_seat``) and the
        wave stays unresolved: True, and the caller resolves it right
        after the iteration's decode dispatch, so that the wait for the
        wave's prefills is a wait with the next window queued behind
        them. Else the wave is resolved here, in front of the build:
        a row that decodes under an FSM, a seed of its own or penalties
        needs its first token on the host before its next step (these
        are the facts that keep ``_choose_path`` off the pipelined
        windows), as does a row that can only end on it; a job that
        streams was resolved at its own dispatch (``_admit_batch``)."""
        rows = self._wave_rows()
        if (
            rows
            and self._joins_on_device
            and all(self._enters_unread(s) for _, s in rows)
        ):
            for i, s in rows:
                self._seat(i, s)
                # (a block model's row has no first token to wait for)
                s.first_pending = self._block == 1
            return True
        self._resolve_wave()
        return False

    def _enters_unread(self, s: _Slot) -> bool:
        """Nothing the host does with slot ``s`` before its first
        window's tokens are back needs the row's first token."""
        r = s.req
        return (
            r.constraint is None
            and r.row_seed is None
            and not r.has_penalties()
            and (s.job is None or s.job.on_token is None)
            # a row prefilled in chunks gets its table row back when it
            # is seated, and a window dispatched while the row was still
            # prefilling must have run by then (its writes for the slot
            # go to the garbage page only while the row reads zero):
            # the resolve in front of the build waits for it
            and not s.prefilling
            # a first token that is certain to end the row would cost a
            # window's row-steps for nothing
            and (
                self._block > 1
                or self._remaining(r, 0, len(r.prompt_ids)) > 1
            )
        )

    def _resolve_wave(self, in_window=()) -> None:
        """The admission wave's ONE host sync: fetch the first tokens
        (and routing counts) of every prefill dispatched since the last
        one, then arm the rows in dispatch order. A slot is pending from
        its prefill's dispatch to the resolve of its iteration, and
        across nothing else: that resolve is ``_hold_wave``'s, in front
        of the batch's build, or where the wave was held the one right
        behind the iteration's decode dispatch (``_pipelined_step``;
        ``in_window``: the slots that dispatch took, whose rows entered
        it by the tokens on the device). Also called right after the
        dispatch of a row that streams, and before anything that may
        release or move a slot (an eviction, a resume, a suspend, a
        cancel, the setup of a shared prefix) or raise out of admission
        (``run_multi``'s ``finally`` resolves what a raise left).
        Should the fetch or a row's arming raise, the rows not armed
        yet are given up, as a failed dispatch gives up its own: a
        window that already holds them finds their generation moved
        and drops their tokens as ``stale``."""
        rows = self._wave_rows()
        if not rows:
            return  # routing counts alone wait for a wave with rows
        wave, self._wave = self._wave, []
        n, armed = len(rows), 0
        on_device = sum(1 for i, _ in rows if i in in_window)
        try:
            if self._tel_on:
                self._tel_attrs["prefill"] = {
                    "tokens": 0, "wave": self._wave_seq, "wave_rows": n,
                    "wave_tokens": sum(e.tokens for e in wave),
                    "joined_device": on_device,
                }
            with self.timer.time("prefill"):
                got = jax.device_get(
                    [(e.tok, e.logp, e.route) for e in wave]
                )
                routes = [g[2] for g in got if g[2] is not None]
                if routes:
                    self._note_route("prefill", np.stack(routes))
                if self._tel_on:
                    # dispatch spans time the host alone: the wave's
                    # prompt tokens took this long to come back
                    self._tel_attrs["prefill"]["wave_s"] = round(
                        time.monotonic() - self._wave_t0, 6
                    )
            self._wave_seq += 1
            if self._tel_on:
                telemetry.ADMIT_WAVES_TOTAL.inc(1.0)
                telemetry.ADMIT_WAVE_ROWS_TOTAL.inc(float(n))
                for how, k in (("device", on_device), ("host", n - on_device)):
                    if k:
                        telemetry.ADMIT_WAVE_JOINED_ROWS_TOTAL.inc(
                            float(k), how
                        )
            for e, (toks, logps, _) in zip(wave, got):
                for k, (i, s) in enumerate(e.rows):
                    if toks is None:  # a block model: no first token
                        self._seat(i, s)
                    else:
                        self._arm(i, s, int(toks[k]), float(logps[k]))
                    armed += 1
        except BaseException:
            for i, s in rows[armed:]:
                if self.slots[i] is s:
                    self._drop_slot(i)
            raise

    def _seat(self, i: int, s: _Slot) -> None:
        """Slot ``i``'s prompt is in its pages (or will be when the
        prefills in front of the next decode dispatch have run): the row
        takes its place in the decode batch, with everything of it that
        the host knows without the row's first token. A block model's
        row is then armed: the prompt's leftover tokens head its first
        block and no token is sampled. Any other row is armed by its
        first token (``_arm``). Once a row."""
        if s.seated:
            return
        s.seated = True
        req = s.req
        if self._block > 1:
            s.pos = self._prefill_len(req)
            s.given = len(req.prompt_ids) - s.pos
        else:
            if self.native is not None:
                if s.prefilling:
                    row = self.native.table[i]
                    row[:] = 0
                    row[: len(s.pages)] = s.pages
                self.native.arm_slot(
                    i, len(req.prompt_ids), 0,
                    req.temperature, req.top_p, req.top_k,
                )
            s.pos = len(req.prompt_ids)
            self._seed_penalty_bits(s, req)
        s.prefilling = False
        s.ptable = None
        if s.job is not None:
            s.job.stats["in"] += len(req.prompt_ids)

    def _arm(self, i: int, s: _Slot, first: int, logp: float) -> None:
        """Slot ``i`` is seated and its first token is here."""
        self._seat(i, s)
        s.first_pending = False
        if self.native is not None:
            self.native.last[i] = first
        s.last_token = first
        if s.job is not None:
            s.job.stats["out"] += 1  # the prefill-sampled first token
        self._record_token(s, first, logp)
        self._deliver_token(s, first, logp)

    def _prefill_len(self, req: GenRequest) -> int:
        """The prompt tokens a prefill takes: all of them, or a block
        model's whole blocks (``ModelRunner.whole_blocks``)."""
        n = len(req.prompt_ids)
        return n if self._block == 1 else self.runner.whole_blocks(n)

    def _window_tokens(self) -> int:
        """Positions a row that a fused window yields: THE place that
        says it. ``decode_multi_step`` steps of one token, or for a
        block model that many rounded down to whole blocks (at least
        one)."""
        KS = self.ecfg.decode_multi_step
        if self._block == 1:
            return KS
        return max(KS // self._block, 1) * self._block

    def _drop_slot(self, i: int) -> None:
        """Give slot ``i`` up with no result: its pages and state go
        back, its job counts one slot less, and windows in flight find
        the generation changed."""
        s = self.slots[i]
        self._unreserve(i, s.pages[s.shared_n :])
        if s.job is not None:
            s.job.n_slots -= 1
        self.slots[i] = None
        self._gen[i] += 1
        self._needs_mask.discard(i)

    def _seed_penalty_bits(self, slot: _Slot, req: GenRequest) -> None:
        if req.has_penalties():
            # repetition scope includes the PROMPT (vLLM/HF)
            bits = np.zeros((self.vocab + 7) // 8, np.uint8)
            ids = np.unique(np.asarray(req.prompt_ids, np.int64))
            ids = ids[(ids >= 0) & (ids < self.vocab)]
            np.bitwise_or.at(
                bits, ids // 8, (0x80 >> (ids % 8)).astype(np.uint8)
            )
            slot.seen_bits = bits

    def _admit_prefilling(
        self, req: GenRequest, ctx: JobCtx, slot_idx: int, pages, table
    ) -> None:
        """Arm a PREFILLING slot: pages reserved, no device work yet.
        ``_prefill_tick`` advances it one chunk per scheduler iteration;
        the decode batch keeps running in between (the Sarathi
        observation: a long admit must degrade active rows' cadence by
        a bounded fraction, not pause them for the whole prefill)."""
        pfx = ctx.prefix
        shared = pfx.tokens if pfx is not None else 0
        full = np.array(table, np.int32, copy=True)
        slot = _Slot(
            req=req,
            pages=(list(pfx.pages) + list(pages)) if pfx else pages,
            pos=shared,
            last_token=0,
            job=ctx,
            shared_n=pfx.n_pages if pfx else 0,
            prefilling=True,
            prefill_pos=shared,
            ptable=full,
        )
        self.slots[slot_idx] = slot
        ctx.n_slots += 1
        if self.native is not None:
            # while prefilling, the slot's DENSE table row routes the
            # (discarded) decode writes to the garbage page — they must
            # never clobber already-prefilled positions. The real table
            # lives on slot.ptable for the chunk dispatches and is
            # restored at activation.
            self.native.table[slot_idx, :] = 0

    def _prefill_tick(self) -> None:
        """Advance the lowest-index prefilling slot by ONE chunk; on the
        final chunk, sample its first token and join the admission
        wave (_resolve_wave). Dispatch only: no chunk is waited for."""
        i = next(
            (
                j
                for j, s in enumerate(self.slots)
                if s is not None and s.prefilling
            ),
            None,
        )
        if i is None:
            return
        s = self.slots[i]
        req = s.req
        C = self.ecfg.prefill_chunk
        P = self._prefill_len(req)
        seg = req.prompt_ids[s.prefill_pos : min(s.prefill_pos + C, P)]
        if self._tel_on:
            self._tel_attrs["prefill"] = {
                "tokens": int(len(seg)), "wave": self._wave_seq,
                **self._state_attrs(1),
                **self._kv_attrs((s.prefill_pos + len(seg),)),
                **self._stream_attrs("prefill", len(seg)),
                **self._prefill_attrs((len(seg),)),
            }
        with self.timer.time("prefill"):
            logits, route = self.runner.prefill_batch_at(
                [np.asarray(seg, np.int32)],
                s.ptable[None, :],
                [s.prefill_pos], on_device=True,
            )
        self.prefill_tokens += len(seg)
        s.prefill_pos += len(seg)
        if s.prefill_pos < P:
            if route is not None:
                self._to_wave((), None, None, route, len(seg))
            return
        # last chunk: sample the first token (a block model samples
        # none); the wave arms the slot
        tok, logp = (None, None) if self._block > 1 else (
            self._sample_first(logits, [req], [i])
        )
        self._to_wave([(i, s)], tok, logp, route, len(seg))

    def _fastforward_step(self, b: _DecodeBatch) -> bool:
        """FSM fast-forward ("jump decoding") via masked-candidate
        verification: each constrained row PLANS a jump along its
        forced byte path (fsm.plan_fastforward — purely functional, no
        FSM mutation): draft tokens plus the SMALL candidate mask at
        every token boundary. One parallel forward then yields each
        planned position's argmax over its candidates — the EXACT
        masked-path token — so a whole scaffold commits per dispatch
        and every planned position lands a valid token (no rejections;
        a flagged row with a plan gets its masked step as the plan's
        first position). Under byte tokenization candidates are
        singletons; under BPE vocabs they are the path's prefix
        tokenizations, still small. Unplanned rows ride as plain
        greedy steps (constrained ones verified by ``token_allowed``,
        the speculative window's rule).

        The forward also returns each position's PLAIN argmax: along
        the accepted draft that is the row's unmasked token, and the
        position's candidates are its mask, so a verify forward says
        for free what a window's verify would have found there
        (``_observe``): a job's opening scaffold tells the scheduler
        whether windows will pay before it has spent one.

        Exact vs the every-step-masked path: each accepted token is the
        argmax over the same budget-filtered mask, conditioned on the
        same accepted prefix; acceptance stops at the first draft
        divergence AFTER taking that position's masked token, and
        logprobs come from the candidate-set softmax (== the masked
        distribution). Plans never mutate FSMs, so returning False
        leaves no trace."""
        FF = getattr(self.ecfg, "constrain_fastforward", 0)
        if FF <= 0:
            return False
        if self._ff_wait:
            self._ff_wait -= 1
            return False
        active, last, past_len, table = b.active, b.last, b.past_len, b.table
        tm = self.timer
        # the plan walk, failed probes included, up to the dispatch
        tm.enter("fsm_plan", rows=len(active), planned=0, engaged=False)
        PS = self.ecfg.kv_page_size
        MAXC = 32
        flagged = self._needs_mask & set(active)
        plans = {}
        total = 0
        for i in active:
            tm.tick()
            s = self.slots[i]
            c = s.req.constraint
            plan_fn = getattr(c, "plan_fastforward", None)
            p = None
            if plan_fn is not None:
                rem = self._remaining(s.req, len(s.out_ids), s.pos)
                cap = min(FF, len(s.pages) * PS - s.pos - 1, rem)
                if cap >= 1:
                    p = plan_fn(rem, cap, MAXC)
            if p is None:
                if i in flagged:
                    # ANY flagged row this dispatch cannot plan for
                    # (no plan_fastforward, no capacity, or no
                    # plannable masked step) must get the window's
                    # allowed0 recovery — riding as an unmasked
                    # greedy step would re-flag it forever
                    self._ff_fail_backoff()
                    return False
                continue
            plans[i] = p
            total += len(p[1])
        tm.note(planned=len(plans))
        if total < 2 * len(active):
            self._ff_fail_backoff()
            return False
        tm.note(engaged=True)
        # a flagged row WITH a plan takes its masked step as the plan's
        # first position
        self._needs_mask -= set(plans)
        self._ff_backoff = 0
        # static shapes: pad to the configured width regardless of this
        # step's plans — a data-dependent K would retrace the verify
        # program per distinct length
        K = FF
        C = K + 1
        drafts = np.zeros((self.B, K), np.int32)
        dlens = np.zeros((self.B,), np.int32)
        cand = np.zeros((self.B, C, MAXC), np.int32)
        cand_n = np.zeros((self.B, C), np.int32)
        for i, (draft, cands) in plans.items():
            dlens[i] = len(draft)
            if draft:
                drafts[i, : len(draft)] = draft
            for j, cs in enumerate(cands):
                cand[i, j, : len(cs)] = cs
                cand_n[i, j] = len(cs)
        self._note_window(b, self.ecfg.decode_multi_step)
        with self.timer.time("decode"):
            ct, cl, pt, pl = self.runner.verify_candidates(
                np.asarray(last, np.int32), drafts, dlens,
                cand, cand_n, np.asarray(past_len, np.int32), table,
            )
        self._step += 1
        tm.enter("accept")
        n0 = self._n_accepted
        gens = list(self._gen)
        # every row was dispatched the forward's static width C
        lost: Dict[str, int] = {}
        for i in active:
            s = self.slots[i]
            ctx = s.job
            if ctx is not None:
                ctx.stats["row_steps"] += C
            if i in plans:
                draft, cands = plans[i]
                P = len(cands)
                _lose(lost, ctx, "plan_short", C - P)
                jumped = 0  # draft-matching accepts only: the final
                #             free-choice/diverged token is an ordinary
                #             masked step, not a jump — counting it
                #             would overstate ff_forced
                # the forward's plain argmax at a position reached
                # along the draft is the row's UNMASKED token there,
                # and the position's candidates are its mask: what a
                # window's verify would have found, for free
                plain_ok = asked = 0
                for j in range(P):
                    tok = int(ct[i, j])
                    asked += 1
                    plain_ok += int(pt[i, j]) in cands[j]
                    matched = j < len(draft) and tok == draft[j]
                    if matched:
                        jumped += 1
                    rc = self._accept_token(i, tok, float(cl[i, j]))
                    if rc == 2:
                        _lose(lost, ctx, "failed", P - j)
                        break
                    if rc:
                        _lose(lost, ctx, "finished", P - 1 - j)
                        break
                    if not matched:
                        # diverged from the draft (or the plan's final
                        # free position): later positions are
                        # conditioned on the draft, not on this token
                        _lose(lost, ctx, "diverged", P - 1 - j)
                        break
                self._observe(ctx, plain_ok, asked)
                self.ff_forced += jumped
                if ctx is not None and jumped:
                    ctx.stats["ff_forced"] = (
                        ctx.stats.get("ff_forced", 0) + jumped
                    )
                continue
            # unplanned rider: plain greedy step at position 0
            _lose(lost, ctx, "no_plan", C - 1)
            tok = int(pt[i, 0])
            c = s.req.constraint
            if c is not None:
                rem = self._remaining(s.req, len(s.out_ids), s.pos)
                tok_ok = self._token_ok(c, tok, rem)
                self._observe(ctx, int(tok_ok), 1)
                if not tok_ok:
                    # its next dispatch opens with this row's
                    # FSM-masked step (allowed0 recovery, or the
                    # masked single step)
                    self._needs_mask.add(i)
                    _lose(lost, ctx, "rejected", 1)
                    continue
            if self._accept_token(i, tok, float(pl[i, 0])) == 2:
                _lose(lost, ctx, "failed", 1)
        self._commit_verified(active, past_len, gens)
        self._close_accept(n0, C * len(active), lost)
        return True

    def _ff_fail_backoff(self) -> None:
        """Exponential re-probe backoff after a disengaged fast-forward
        scan: the next probe 2, then 4, ... 32 dispatches on. Free-text
        regions (non-singleton masks) would otherwise pay the
        O(rows x V) mask scan before every dispatch. In dispatches, not
        device steps: a batch on masked single steps (one step a
        dispatch) finds its rows' closing scaffold as soon as a batch
        on windows of K does."""
        self._ff_backoff = min(max(self._ff_backoff * 2, 2), 32)
        self._ff_wait = self._ff_backoff - 1

    def _split_pfx(self, active):
        """Operands for Hydragen-style split decode (Pallas path,
        EngineConfig.prefix_split): a tuple of ``(pfx_pages [Pp_g]
        int32, pfx_len [B] int32)`` groups, one per distinct
        shared-prefix PAGE RUN among the active rows (co-batched
        templated jobs each get their own group UNLESS the prefix
        store gave them the very same pages, in which case they merge
        into one group and the shared pages are read once; member row
        sets are disjoint, so the carries combine exactly —
        ops/attention.py). ``None`` when disabled, on the fallback
        path, or when no active row belongs to a prefix."""
        if not getattr(self.ecfg, "prefix_split", False):
            return None
        if not getattr(self.runner, "use_pallas", False):
            return None
        groups = []
        by_pages = {}  # page-run tuple -> index into groups
        seen = set()
        for i in active:
            ctx = self.slots[i].job
            if ctx is None or ctx.prefix is None or id(ctx) in seen:
                continue
            seen.add(id(ctx))
            pages = ctx.prefix.pages
            key = tuple(pages)
            gi = by_pages.get(key)
            if gi is None:
                by_pages[key] = len(groups)
                # pad the page list to a power-of-two bucket so
                # distinct template lengths don't each retrace the
                # fused decode programs (the pad pages are the garbage
                # page 0, fully masked by pfx_len in the carry; the
                # kernel skips only the REAL pfx_len // PS pages)
                cap = 1
                while cap < len(pages):
                    cap *= 2
                padded = np.zeros((cap,), np.int32)
                padded[: len(pages)] = pages
                groups.append((padded, np.zeros((self.B,), np.int32)))
                gi = len(groups) - 1
            pfx_len = groups[gi][1]
            for j in active:
                if self.slots[j].job is ctx:
                    pfx_len[j] = ctx.prefix.tokens
        if not groups:
            return None
        # the tuple's pytree STRUCTURE is a jit trace key: bound the
        # recompiles from varying group counts by (a) sorting groups by
        # page-bucket size so (4,8) and (8,4) share a structure and
        # (b) padding the count to a power of two with dummy groups
        # (1 garbage page, all-zero pfx_len -> provably cold carry,
        # an exact no-op costing one tiny masked gather+einsum)
        groups.sort(key=lambda g: -len(g[0]))
        n = 1
        while n < len(groups):
            n *= 2
        while len(groups) < n:
            groups.append(
                (
                    np.zeros((1,), np.int32),
                    np.zeros((self.B,), np.int32),
                )
            )
        return tuple(groups)

    def _commit_verified(self, active, past_len, gens) -> None:
        """After a verify dispatch's accept loop, for a model that keeps
        conv state beside K/V: commit each row's state at the inputs
        its acceptance consumed (``pos`` advanced by exactly that), so
        a partial accept rolls the state back by a gather and no second
        forward. A row released in the loop commits nothing: its pages
        are free, and whoever takes them writes before it reads."""
        if not self._layout.has_state:
            return
        n = np.zeros((self.B,), np.int32)
        for i in active:
            s = self.slots[i]
            if s is not None and self._gen[i] == gens[i]:
                n[i] = s.pos - int(past_len[i])
        self.runner.commit_verified(n)

    def _note_route(self, stage: str, stats=None) -> None:
        """Fold the routing counts of the dispatch just fetched (a
        model that counts them: runner.take_route_stats) into
        ``stage``'s span attrs and the routed-rows counter. Called
        inside the stage's timed block, after its tokens were fetched:
        the counts came back with them."""
        if stats is None:
            take = getattr(self.runner, "take_route_stats", None)
            stats = take() if take is not None else None
        if stats is None or not self._tel_on:
            return
        a = np.asarray(stats, np.float64).reshape(-1, 6)
        self._route_attrs[stage] = {
            "experts_touched": round(float(a[:, 0].mean()), 2),
            "expert_rows_max": round(float(a[:, 1].mean()), 2),
            "expert_rows_mean": round(float(a[:, 2].mean()), 3),
            # of this chip's share (runner._route_stats): the experts it
            # holds a layer, and the assignments that landed on them
            "experts_held": int(a[0, 4]),
            "expert_rows_held": int(a[:, 3].sum()),
            "expert_rows_elsewhere": int(a[:, 5].sum()),
        }
        self._tel_attrs[stage] = {
            **(self._tel_attrs.get(stage) or {}), **self._route_attrs[stage]
        }
        telemetry.MOE_ROUTED_ROWS_TOTAL.inc(float(a[:, 3].sum()))
        if a[:, 5].any():
            telemetry.MOE_ROWS_ELSEWHERE_TOTAL.inc(float(a[:, 5].sum()))

    def _pad_mask(self, mask: np.ndarray) -> np.ndarray:
        """Constraint masks are sized to the *tokenizer* vocab; pad to the
        (possibly larger, padded) model vocab with False so padding token
        ids are never sampled under a schema constraint."""
        if len(mask) == self.vocab:
            return mask
        out = np.zeros((self.vocab,), bool)
        out[: len(mask)] = mask[: self.vocab]
        return out

    def _constraint_mask(self, c: TokenConstraint, remaining: int) -> np.ndarray:
        # Probe the signature once per implementation: a TypeError raised
        # *inside* a budget-aware allowed_tokens must propagate, not
        # silently disable budget enforcement.
        bound = c.allowed_tokens
        takes_budget = _method_takes_budget(c, bound, "_sutro_takes_budget")
        m = bound(remaining=remaining) if takes_budget else bound()
        return self._pad_mask(m)

    def _ones_packed(self, n: int) -> np.ndarray:
        """[n, ceil(V / 8)] uint8 of all-True rows, as ``np.packbits``
        gives them: the tail bits past the vocabulary zero."""
        return np.repeat(self._ones_row[None], n, axis=0)

    def _constraint_packed(
        self, c: TokenConstraint, remaining: int, out: np.ndarray,
        shared: dict,
    ) -> str:
        """Write the constraint's mask into ``out``, one bit-packed row
        of the model's vocabulary, and say how it was come by (the
        ``path`` of ``sutro_fsm_mask_rows_total``): ``cached``, a kept
        packed array copied as it is; ``filtered``, a budget bit and one
        row was computed and packed; ``packed_here``, the implementation
        answers in bools only (``_constraint_mask``) and its row is
        packed at this one place. A tokenizer's vocabulary narrower
        than the model's leaves the ids past it False, like
        ``_pad_mask``."""
        fn = getattr(c, "allowed_packed", None)
        if fn is None:
            out[:] = np.packbits(self._constraint_mask(c, remaining))
            return "packed_here"
        bits, filtered = fn(remaining=remaining, shared=shared)
        n = min(len(bits), len(out))
        out[:n] = bits[:n]
        out[n:] = 0
        if len(bits) >= len(out):
            # a vocabulary wider than the model's: no id past the model's
            out[-1] &= self._ones_row[-1]
        return "filtered" if filtered else "cached"

    def _fsm_masks(self, rows) -> np.ndarray:
        """[B, ceil(V / 8)] uint8 — each listed slot's FSM mask,
        BIT-PACKED as the device programs take it (all-True for
        unconstrained slots): a kept packed array is copied, 19 KB a row
        at 151,936 ids, and the host holds no [B, V] bool array. Single
        assembly path for BOTH the masked single-step and the
        speculative window's allowed0 recovery, so the two cannot
        drift."""
        rows = list(rows)
        how = {"cached": 0, "filtered": 0, "packed_here": 0}
        with self.timer.host("fsm_mask", rows=len(rows)):
            allowed = self._ones_packed(self.B)
            shared: dict = {}
            for i in rows:
                self.timer.tick()
                s = self.slots[i]
                if s is None:
                    continue  # failed earlier in this assembly pass
                c = s.req.constraint
                if c is not None:
                    rem = self._remaining(s.req, len(s.out_ids), s.pos)
                    try:
                        path = self._constraint_packed(
                            c, rem, allowed[i], shared
                        )
                        how[path] += 1
                    except Exception as e:  # noqa: BLE001 — row isolation
                        # one row's broken FSM must not take the batch
                        # down: release it into the retry/quarantine
                        # path; its mask row samples a token that the
                        # (slot, gen) / None-slot checks then discard
                        self._fail_slot(i, e)
            self.timer.count("cached", how["cached"])
        if self._tel_on:
            for path, n in how.items():
                if n:
                    telemetry.FSM_MASK_ROWS_TOTAL.inc(float(n), path)
        return allowed

    def _remaining(self, req: GenRequest, emitted: int, pos: int) -> int:
        """Tokens of generation budget left: request cap and context room."""
        return max(
            min(
                req.max_new_tokens - emitted,
                self.ecfg.max_context() - pos - 1,
            ),
            0,
        )

    def _sample_first(
        self,
        logits,
        reqs: List[GenRequest],
        slot_idxs: List[int],
    ):
        """Dispatch first-token sampling for ``len(reqs)`` fresh rows
        in one device call over ``logits``, the prefill program's
        [B, V] where it lies: its rows past the real ones are the
        bucket's padding, sampled greedily and never read, so the
        program compiles once a bucket and not once a group size.
        Returns ``(tok, logp)``, [B] each, ON THE DEVICE and on their
        way to the host: ``_resolve_wave`` reads them. The same program
        writes the rows' tokens into ``_first_dev`` at their slots, for
        a window dispatched before that."""
        n, nb = len(reqs), logits.shape[0]
        temps = np.zeros((nb,), np.float32)
        top_p = np.ones((nb,), np.float32)
        top_k = np.zeros((nb,), np.int32)
        temps[:n] = [r.temperature for r in reqs]
        top_p[:n] = [r.top_p for r in reqs]
        top_k[:n] = [r.top_k for r in reqs]
        allowed = None
        if any(r.constraint is not None for r in reqs):
            with self.timer.host("fsm_mask", rows=n):
                allowed = self._ones_packed(nb)
                shared: dict = {}
                for i, r in enumerate(reqs):
                    self.timer.tick()
                    if r.constraint is not None:
                        rem = self._remaining(r, 0, len(r.prompt_ids))
                        self._constraint_packed(
                            r.constraint, rem, allowed[i], shared
                        )
        row_seeds = None
        split = False
        if any(r.row_seed is not None for r in reqs):
            sub = self._fixed_key  # per-row keys derive from row_seed
            # unseeded rows in a mixed batch key off their SLOT index
            # (unique across same-step admit batches) under a salt
            # distinct from the decode loop's, so no two draws alias
            row_seeds = np.zeros((nb,), np.int32)
            row_seeds[:n] = [
                _step_seed(r.row_seed, 0)
                if r.row_seed is not None
                else _step_seed(0x0F1E57 ^ (slot_idxs[i] + 1), self._step)
                for i, r in enumerate(reqs)
            ]
        else:
            # the session's key, split inside the sample's own program
            sub, split = self._key, True
        first = slots = None
        if self._joins_on_device:
            # by slot; the bucket's padding rows go nowhere
            slots = np.full((nb,), self.B, np.int32)
            slots[:n] = slot_idxs
            first = self._first_dev
            if first is None:
                first = np.zeros((self.B,), np.int32)
            # one signature whether it is the upload or a result
            first = self._resident(first)
        # static: a stand-in runner (tests, host benches) has no counters
        ModelRunner.count_sample(temps)
        with self.timer.time("admit_sample"):
            tok, logp, self._first_dev, key = _admit_sample_jit(
                logits, sub, temps, top_p, top_k, allowed, row_seeds,
                first, slots, split=split,
            )
            if split:
                self._key = key
            tok.copy_to_host_async()
            logp.copy_to_host_async()
        return tok, logp

    def _deliver_token(self, slot: _Slot, tok: int, logp: float) -> None:
        """Fan one committed token out to the slot's job ``on_token``
        hook (the interactive streaming channel). Every commit path
        must call this — ``_accept_token``, the two prefill-sampled
        first-token sites, and the vectorized window accept."""
        j = slot.job
        if j is None or j.on_token is None:
            return
        try:
            j.on_token(slot.req.row_id, tok, float(logp))
        except Exception:  # noqa: BLE001 — a broken stream channel
            # must not kill the decode loop; the request's
            # should_cancel path tears it down
            logger.warning(
                "on_token hook failed for %s", j.job_id, exc_info=True
            )

    def _record_token(self, slot: _Slot, tok: int, logp: float) -> None:
        slot.out_ids.append(tok)
        slot.logprob_sum += float(logp)
        if slot.req.constraint is not None and tok not in self.stop_ids:
            slot.req.constraint.advance(tok)
        if slot.req.has_penalties() and tok not in self.stop_ids:
            slot.counts[tok] = slot.counts.get(tok, 0) + 1
            if slot.seen_bits is not None and 0 <= tok < self.vocab:
                slot.seen_bits[tok // 8] |= 0x80 >> (tok % 8)
        seqs = slot.req.stop_seqs
        if seqs and self.token_bytes is not None and not slot.hit_stop_seq:
            # match against the FULL tail+token first (a long token must
            # not push a boundary-spanning match out of the window),
            # then keep only what the next boundary match could need
            longest = slot.stop_longest
            if not longest:
                longest = slot.stop_longest = max(len(s) for s in seqs)
            grown = slot.tail + self.token_bytes(tok)
            for s in seqs:
                if s in grown:
                    slot.hit_stop_seq = True
                    break
            slot.tail = grown[-(longest - 1):] if longest > 1 else b""

    def _finish_reason(self, slot: _Slot, tok: int) -> Optional[str]:
        c = slot.req.constraint
        if slot.hit_stop_seq:
            return "stop"
        if tok in self.stop_ids:
            return "stop"
        if c is not None and c.is_complete():
            return "schema_complete"
        if len(slot.out_ids) >= slot.req.max_new_tokens:
            return "length"
        if slot.pos + 1 >= self._max_ctx:
            return "length"
        return None

    def _row_error(
        self, ctx: JobCtx, req: GenRequest, exc: BaseException
    ) -> None:
        """Row-level failure domain (one bad row must not kill the
        job): retry the row as a FRESH request up to ``ctx.row_retries``
        times — only when its constraint can be rebuilt (a directly
        supplied FSM has advanced and cannot be rewound) — then
        quarantine it as an error result the jobstore records in the
        ``error`` column. Every decision streams a failure_log event."""
        rid = req.row_id
        attempt = ctx.row_attempts.get(rid, 0) + 1
        ctx.row_attempts[rid] = attempt
        msg = f"{type(exc).__name__}: {exc}"
        rebuildable = (
            req.constraint is None or req.constraint_factory is not None
        )
        if attempt <= ctx.row_retries and rebuildable:
            logger.warning(
                "row %d failed (attempt %d/%d), retrying: %s",
                rid, attempt, ctx.row_retries, msg,
            )
            if ctx.on_row_event is not None:
                ctx.on_row_event(
                    {"event": "row_retry", "row_id": rid,
                     "attempt": attempt, "error": msg}
                )
            # fresh request: FSM state, prep handoff, and flags reset —
            # appended at the TAIL, which admission pops next
            ctx.pending.append(
                dataclasses.replace(
                    req,
                    constraint=None,
                    prepped_constraint=None,
                    prep_queued=False,
                )
            )
            return
        logger.warning(
            "row %d quarantined after %d attempt(s): %s", rid, attempt, msg
        )
        if ctx.on_row_event is not None:
            ctx.on_row_event(
                {"event": "row_quarantined", "row_id": rid,
                 "attempt": attempt, "error": msg}
            )
        ctx.stats["rows"] += 1
        ctx.on_result(
            GenResult(
                row_id=rid,
                token_ids=[],
                cumulative_logprob=0.0,
                finish_reason="error",
                input_tokens=len(req.prompt_ids),
                error=msg,
            )
        )

    def _fail_slot(self, i: int, exc: BaseException) -> None:
        """Release slot ``i`` after a per-row exception WITHOUT emitting
        its partial output, then route the row through
        :meth:`_row_error` (retry or quarantine). Mirrors ``_release``'s
        bookkeeping; the in-flight-window dead-store argument documented
        there covers the pages freed here too."""
        slot = self.slots[i]
        self._pools.release(slot.pages[slot.shared_n :])
        if self.native is not None:
            self.native.release(i)
        else:
            self.allocator.free(slot.pages[slot.shared_n :])
        ctx = slot.job
        if ctx is not None:
            ctx.n_slots -= 1
        self.slots[i] = None
        self._gen[i] += 1
        self._needs_mask.discard(i)
        if ctx is not None:
            self._row_error(ctx, slot.req, exc)

    def _accept_token(
        self, i: int, tok: int, logp: float, release: bool = True
    ) -> int:
        """Record one sampled token for slot ``i``; release on finish.
        Returns 1 if the row completed, 2 if the row FAILED (slot
        released into the retry/quarantine path — the token was NOT
        recorded), else 0. ``release=False`` defers the release to the
        caller (speculative windows must commit the accepted K/V to
        pages BEFORE freeing them). Results and token accounting route
        through the SLOT'S job (co-batched sessions interleave jobs
        within one decode batch)."""
        s = self.slots[i]
        try:
            if faults.ACTIVE is not None:
                faults.inject(
                    "row.decode", row=s.req.row_id,
                    job=s.job.job_id if s.job is not None else None,
                )
            s.pos += 1  # last_token's KV is now cached
            if self.native is not None:
                self.native.note_token(i, tok)
            self._record_token(s, tok, logp)
        except Exception as e:  # noqa: BLE001 — row isolation boundary
            self._fail_slot(i, e)
            return 2
        s.last_token = tok
        if s.job is not None:
            s.job.stats["out"] += 1
        self._deliver_token(s, tok, float(logp))
        try:
            done = self._finish_reason(s, tok)
        except Exception as e:  # noqa: BLE001 — row isolation (FSM state)
            self._fail_slot(i, e)
            return 2
        # a token of a row that failed on it is not committed: whoever
        # is returned 2 counts this position among the row-steps lost
        self._n_accepted += 1
        if done:
            if release:
                self._emit(i)
            return 1
        return 0

    def _emit(self, i: int, reason: Optional[str] = None) -> None:
        """Release slot ``i`` and stream its result through its job."""
        ctx = self.slots[i].job
        tm = self.timer
        # the engine's result handling and store appends run right here,
        # on the scheduler thread
        # (a run of rows finishing in one accept loop is one span)
        with tm.host("emit", merge=True):
            tm.count("rows")
            res = self._release(i)
            if reason is not None:
                res.finish_reason = reason
            if ctx is not None:
                ctx.stats["rows"] += 1
                ctx.on_result(res)

    def _token_ok(
        self, c: TokenConstraint, tok: int, remaining: int
    ) -> bool:
        """Single-token FSM validity, used to verify speculative window
        tokens. Prefers the optional O(1) ``token_allowed`` fast path
        when the constraint offers one (signature-probed like
        ``allowed_tokens``, so implementations without a ``remaining``
        parameter still work); otherwise falls back to the full
        (padded) mask."""
        fn = getattr(c, "token_allowed", None)
        if fn is not None:
            if _method_takes_budget(c, fn, "_sutro_tok_takes_budget"):
                return bool(fn(tok, remaining=remaining))
            return bool(fn(tok))
        return bool(self._constraint_mask(c, remaining)[tok])

    def _checkpoint_slot(self, slot: _Slot) -> Optional[set]:
        """Session KV checkpoint (``JobCtx.kv_checkpoint``): transfer
        the finished row's page-aligned OWN pages into the radix prefix
        store keyed by its full (prompt + emitted) token sequence, so
        the session's next turn — whose prompt extends this sequence —
        admits by prefix hit instead of re-prefilling the whole
        conversation. Once store-owned the pages age like any other
        leaves: under pressure they demote down the tiers rather than
        being dropped. Returns the set of page ids now store-owned (the
        caller must keep them out of the allocator), or None."""
        store = self._prefix_store
        PS = self.ecfg.kv_page_size
        try:
            full = np.concatenate(
                [
                    np.asarray(slot.req.prompt_ids, np.int32),
                    np.asarray(slot.out_ids, np.int32),
                ]
            )
            # positions [0, pos) hold KV for full[:pos] — the last
            # sampled token's KV was never written
            aligned = min(slot.pos, len(full)) // PS
            if aligned <= slot.shared_n:
                return None  # nothing beyond the shared head to keep
            handle = store.lookup_pin(full[: aligned * PS])
            try:
                d = len(handle.nodes)
                if d < slot.shared_n or d >= aligned:
                    # the store path stops inside the job-owned prefix
                    # head (pages we cannot transfer) or already covers
                    # everything this row could contribute
                    return None
                pages = [int(p) for p in slot.pages[d:aligned]]
                if not store.extend(
                    handle, full[d * PS : aligned * PS], pages
                ):
                    return None
                if self._tel_on and slot.job.trace_id is not None:
                    telemetry.TRACES.event(
                        slot.job.trace_id, "kv_checkpoint",
                        {"row_id": int(slot.req.row_id),
                         "pages": len(pages)},
                    )
                return set(pages)
            finally:
                store.release(handle)
        except Exception:  # noqa: BLE001 — a checkpoint is an
            # optimization; on any failure the pages free normally and
            # the next turn re-prefills (the pre-tier behavior)
            logger.warning("kv checkpoint failed", exc_info=True)
            return None

    def _release(self, i: int) -> GenResult:
        """Free slot ``i``'s pages and emit its result.

        ORDERING DEPENDENCY: a release can happen while pipelined
        windows referencing this slot are still in flight; those stale
        windows keep writing KV into the freed pages even though the
        (slot, gen) check discards their *tokens*. If the pages are
        reallocated to a newly admitted row, correctness rests on
        per-device in-order execution of dispatched programs: the new
        row's prefill + decode steps are dispatched AFTER the stale
        window and rewrite every KV position they will ever read, so the
        stale writes are dead stores. JAX/TPU executes one program at a
        time per device, which guarantees this today; a multi-stream or
        relaxed-ordering backend would need frees deferred until every
        pipe entry referencing the slot has drained (see
        ``_pipe_capacity_ok`` for the companion invariant)."""
        slot = self.slots[i]
        assert slot is not None
        kept = None
        if (
            slot.job is not None
            and slot.job.kv_checkpoint
            and self._kv_tier is not None
            and self._prefix_store is not None
            and not slot.prefilling
        ):
            kept = self._checkpoint_slot(slot)
        self._pools.release(slot.pages[slot.shared_n :])
        if self.native is not None:
            self.native.release(i)
            if kept and not self.native.reserve_pages(
                sorted(kept)
            ):  # pragma: no cover — release just freed exactly these
                # ids; a failure would mean the store and the allocator
                # both think they own them, so drop the store wholesale
                logger.warning(
                    "kv checkpoint re-reserve failed; resetting store"
                )
                self._prefix_store.reset()
        else:
            # shared-prefix pages at the table head belong to the JOB
            # (freed once at end of run), not this slot; checkpointed
            # pages now belong to the prefix store
            own = slot.pages[slot.shared_n :]
            self.allocator.free(
                [p for p in own if int(p) not in kept] if kept else own
            )
        if slot.job is not None:
            slot.job.n_slots -= 1
        self.slots[i] = None
        self._gen[i] += 1
        self._needs_mask.discard(i)  # flag must not leak to a new occupant
        out = list(slot.out_ids)
        reason = "stop"
        if out and out[-1] in self.stop_ids:
            out = out[:-1]
            reason = "stop"
        elif slot.hit_stop_seq:
            reason = "stop"
        elif slot.req.constraint is not None and slot.req.constraint.is_complete():
            reason = "schema_complete"
        else:
            reason = "length"
        return GenResult(
            row_id=slot.req.row_id,
            token_ids=out,
            cumulative_logprob=slot.logprob_sum,
            finish_reason=reason,
            input_tokens=len(slot.req.prompt_ids),
        )

    # ------------------------------------------------------------------
    # one decode iteration: build the batch, choose a path, take it
    # ------------------------------------------------------------------

    def _build_batch(self, active: List[int]) -> _DecodeBatch:
        """The dense operands of this iteration's decode dispatch and
        the facts of its rows, in one walk over the active slots."""
        if self.native is not None:
            # dense arrays live in the C++ core, always current
            nat = self.native
            last, past_len, table = nat.last, nat.past_len, nat.table
            temp, top_p, top_k = nat.temp, nat.top_p, nat.top_k
        else:
            last = np.zeros((self.B,), np.int32)
            past_len = np.zeros((self.B,), np.int32)
            table = np.zeros((self.B, self.MP), np.int32)
            temp = np.zeros((self.B,), np.float32)
            top_p = np.ones((self.B,), np.float32)
            top_k = np.zeros((self.B,), np.int32)
        has_constraint = has_row_seed = has_penalty = False
        n_constrained = 0
        all_greedy = constrained_greedy = True
        PS = self.ecfg.kv_page_size
        room = self.MP * PS
        row_seeds = np.zeros((self.B,), np.int32)
        for i in active:
            s = self.slots[i]
            r = s.req
            if r.has_penalties():
                has_penalty = True
            if self.native is None:
                last[i] = s.last_token
                past_len[i] = s.pos
                table[i, : len(s.pages)] = s.pages
                temp[i] = r.temperature
                top_p[i] = r.top_p
                top_k[i] = r.top_k
            if r.row_seed is not None:
                has_row_seed = True
                row_seeds[i] = _step_seed(r.row_seed, len(s.out_ids))
            else:
                # mixed batch: unseeded rows still need fresh
                # per-step keys (the batch-wide rng is pinned to
                # _fixed_key when any row is seeded)
                row_seeds[i] = _step_seed(
                    0x5EED0000 ^ (i + 1), self._step
                )
            greedy = r.temperature <= 0.0
            if not greedy:
                all_greedy = False
            if r.constraint is not None:
                has_constraint = True
                n_constrained += 1
                if not greedy:
                    constrained_greedy = False
            room = min(room, len(s.pages) * PS - s.pos)
        if active:
            self._slide_windows(active, past_len, table)
        return _DecodeBatch(
            active, last, past_len, table, temp, top_p, top_k, row_seeds,
            _DecodeFacts(
                has_constraint=has_constraint,
                has_row_seed=has_row_seed,
                has_penalty=has_penalty,
                all_greedy=all_greedy,
                constrained_greedy=constrained_greedy,
                flagged=bool(self._needs_mask),
                room=room,
                wrapped=self._wrapped,
                constrained=n_constrained / max(len(active), 1),
                unmasked_ok=self._unmasked_ok,
                stepping=self._stepping,
            ),
        )

    def _slide_windows(self, active, past_len, table) -> None:
        """Give back what has slid out behind each active row's
        COMMITTED length (``past_len`` here is what the host has
        accepted, ``RowPools.slide``); where something slides, note the
        full pool's pages beside it."""
        tel = self._tel_on
        if self._pools.slide(table, past_len, active, tel) and tel:
            free = self.free_page_count
            telemetry.KV_PAGES.set(float(free), "full", "free")
            telemetry.KV_PAGES.set(
                float(self.runner.num_pages - 1 - free), "full", "used"
            )

    def _choose_path(
        self, f: _DecodeFacts, in_flight: int, probed: bool = False
    ) -> str:
        """THE choice of a decode path, from the facts of the batch and
        the number of fused windows in flight; every gate is read here
        and nowhere else. Returns what to try: ``pipelined`` (refill the
        pipe, fetch its oldest window), ``drain`` (fetch only),
        ``fastforward`` (the fast-forward probe of a constrained greedy
        batch; where it disengages the loop asks again with ``probed``),
        ``window`` (the speculative window) or ``single`` (one step,
        masked where a row has an FSM). A constrained greedy batch takes
        the window while the FSMs accept enough of its rows' UNMASKED
        tokens for a window to commit more tokens a second than masked
        steps would (``f.unmasked_ok``, ``f.constrained``,
        ``_window_gain``), and masked steps while they do not. The
        path's method returns the label its iteration is counted
        under."""
        KS = self.ecfg.decode_multi_step
        if self._block > 1:
            # a model that generates by blocks has ONE decode program,
            # the window of blocks, and it pipelines: what its rows may
            # not ask for (a constraint, penalties, a seed a row) is
            # refused at submit (engine/api.py), and an active row always
            # has a block's room (its ``pos``, its pages and the
            # context are multiples of the block)
            if f.room < self._block and not in_flight:
                raise RuntimeError(
                    f"a row of a block model has room for {f.room} "
                    f"positions, under one block of {self._block}"
                )
            return "pipelined" if f.room >= self._block else "drain"
        # Fuse K decode steps into one device program when no row needs
        # host work between steps: one dispatch + one fetch per window
        # instead of per token.
        fused = (
            KS > 1
            and not f.has_row_seed
            and not f.has_penalty  # counts update host-side
        )
        # all-or-nothing: every distinct K is a separate XLA compilation
        # of the fused window (steps is static), so near-capacity tails
        # run single-step instead of walking through K-1 recompiles
        fits = f.room >= KS
        # Pipelined fused windows: window k+1 is dispatched chained off
        # window k's device-resident tokens BEFORE window k's results
        # are fetched, so the device runs the next window while the
        # host accepts this one (the host is 2-4 % of a generate window:
        # PERF.md §5). Page capacity at dispatch covers every in-flight
        # window, and (slot, generation) snapshots make stale windows'
        # tokens discardable after a slot is released/reused
        # mid-pipeline.
        pipe_ok = fused and not f.has_constraint and not f.flagged
        if pipe_ok and (in_flight or fits):
            return "pipelined"
        if in_flight:
            # pipe_ok went false (e.g. a constrained row admitted
            # mid-pipeline): windows drain one per iteration, then
            # other paths resume
            return "drain"
        # (a plain batch with nothing in flight and room for less than
        # one window takes the single step, below)
        #
        # Constrained rows fuse K steps into one device program too
        # when they are GREEDY (classify-style jobs): the window samples
        # unmasked, the host verifies tokens against each row's FSM,
        # and only the longest valid prefix is committed to pages —
        # exact for greedy (masked argmax == unmasked argmax when the
        # unmasked argmax is valid). A rejecting row takes its
        # FSM-masked step as the FIRST step of its next window
        # (allowed0) — per-row recovery; other rows keep full window
        # cadence.
        # Flagged rows are fine on either side: the window FSM-masks
        # their first step (allowed0), the single step masks every row
        # and clears the flags itself.
        if fused and fits and f.has_constraint and f.constrained_greedy:
            # FSM fast-forward first: when enough rows sit in a forced
            # scaffold run, one parallel verify commits the whole run
            # per row, where a window would reject its unmasked samples
            # and masked steps would take one forward a token. Flagged
            # SINGLETON rows are candidates too (the peel is their
            # masked step); a flagged row in a non-singleton state
            # makes the probe disengage. The verify forward has no
            # ring/pipeline wrapper.
            if not probed and not f.wrapped and f.all_greedy:
                return "fastforward"
            # A window with no draft model never beats masked steps in
            # DEVICE time (K steps, at most K tokens a row either way):
            # what it saves is the host's round between steps, and only
            # while its unmasked tokens verify. So: the window where it
            # commits more tokens a second than masked steps, by the
            # share of unmasked tokens the FSMs have been accepting;
            # whichever the rows took last stays until the other is
            # worth _SWITCH_GAIN of it.
            gain = _window_gain(f.unmasked_ok, KS, f.constrained)
            if f.stepping:
                return "window" if gain > _SWITCH_GAIN else "single"
            return "window" if gain * _SWITCH_GAIN >= 1.0 else "single"
        return "single"

    def _state_attrs(self, rows: int) -> Dict[str, int]:
        """Span attrs of a dispatch that advances ``rows`` rows' slot
        state: the rows, and the bytes of state a step of it reads
        (runner.state_step_bytes). Nothing for any other model."""
        if not self._layout.state_slots:
            return {}
        attrs = {
            "state_rows": int(rows),
            "state_bytes": int(self.runner.state_step_bytes(rows)),
        }
        if self._layout.state_kind == "kda":
            # the delta-rule layers' matrices alone (no conv columns)
            attrs["kda_state_bytes"] = self.runner.state_matrix_bytes(rows)
        return attrs

    def _stream_attrs(self, form: str, tokens: int, steps: int = 1):
        """Span attr of a dispatch of a model whose residual stream is
        several lanes (``ModelConfig.hc_mult``): the bytes the stream of
        its ``tokens`` real tokens has to move (``form``: prefill |
        decode; ``steps`` forward steps), counted as it is read. Nothing
        for any other model."""
        sublayers = getattr(self.runner.mcfg, "hc_sublayers", 0)
        if not sublayers:
            return {}
        needed = self.runner.stream_bytes(tokens)
        # the callers test the switch too; sutro_tpu.analysis wants every
        # count behind it in the function that makes it
        if self._tel_on:
            telemetry.HC_SUBLAYERS_TOTAL.inc(float(sublayers * steps), form)
            telemetry.HC_STREAM_BYTES_NEEDED_TOTAL.inc(float(needed))
        return {"hc_stream_bytes": needed}

    def _prefill_attrs(self, lengths) -> Dict[str, Any]:
        """Span attrs of a prefill dispatch of rows of ``lengths``
        tokens, and its count in ``sutro_prefill_tokens_total``: the
        rows' own tokens and what its programs' ``rows x length`` hold
        beyond them (``ModelRunner.prefill_buckets``; host arithmetic
        at dispatch, like ``_count_kv_pages``)."""
        lengths = [int(n) for n in lengths]
        real = sum(lengths)
        buckets = self.runner.prefill_buckets(lengths)
        cells = sum(B * T for B, T in buckets)
        # the callers test the switch too; sutro_tpu.analysis wants every
        # count behind it in the function that makes it
        if self._tel_on:
            telemetry.PREFILL_TOKENS_TOTAL.inc(float(real), "real")
            telemetry.PREFILL_TOKENS_TOTAL.inc(
                float(max(cells - real, 0)), "padded"
            )
        return {
            "rows": len(lengths), "bucket": list(buckets[-1]),
            "real_tokens": real,
            # each row's own length: attention's products go by its square
            "row_tokens": lengths,
        }

    def _kv_attrs(self, ctx) -> Dict[str, float]:
        """Span attrs of a dispatch of a model that keeps K/V a pool a
        kind: the mean over its rows of the tokens a full layer reads
        (the context) and a window layer reads (the context, at most
        the window), and how many layers read each pool
        (``ModelConfig.kv_readers``); of a model whose latent layers
        have an indexer,
        the rows of the context and the rows the attention reads.
        ``ctx`` is an iterable of the rows' contexts, not walked for
        any other model: nothing for those."""
        topk = getattr(self.runner.mcfg, "index_topk", 0)
        window = self._layout.window
        if not window and not topk:
            return {}
        c = np.fromiter(ctx, np.float64)
        if not c.size:
            return {}
        if topk:
            # latent layers under an indexer: the rows a query's context
            # holds and the rows its attention reads (at most index_topk)
            return {
                "kv_rows_context": round(float(c.mean()), 1),
                "kv_rows_selected": round(
                    float(np.minimum(c, topk).mean()), 1
                ),
            }
        m = self.runner.mcfg
        return {
            "kv_tokens_full": round(float(c.mean()), 1),
            "kv_tokens_window": round(
                float(np.minimum(c, window).mean()), 1
            ),
            # the layers that read each pool a step (a "cross" layer
            # reads a full layer's again) and the layers that keep a
            # state: a step's bytes counted from what the program says
            "kv_readers_full": m.kv_readers("attention"),
            "kv_readers_window": m.kv_readers("swa"),
            "state_layers": m.num_state_layers + m.num_conv_layers,
        }

    def _note_window(self, b: _DecodeBatch, steps: int) -> None:
        """Window attribution for the doctor's roofline grade:
        occupancy x fused steps over the span's duration is the
        window's attempted token rate."""
        if self._tel_on:
            n = len(b.active)
            self._tel_attrs["decode_window"] = {
                "batch": n,
                "steps": steps,
                "avg_ctx": round(
                    sum(int(b.past_len[i]) for i in b.active) / max(n, 1),
                    1,
                ),
                **self._state_attrs(n),
                **self._kv_attrs(b.past_len[i] for i in b.active),
                **self._stream_attrs("decode", n * steps, steps),
                **self._route_attrs.get("decode_window", {}),
            }
            if self._block > 1:
                self._tel_attrs["decode_window"].update(
                    self._block_attrs(b, steps)
                )
            f = b.facts
            if f.has_constraint and f.constrained_greedy:
                # WHY window or step: what _choose_path read
                self._tel_attrs["decode_window"]["unmasked_ok"] = round(
                    f.unmasked_ok, 4
                )

    def _block_steps(self, r: GenRequest) -> int:
        """Denoising forwards a block of request ``r`` takes."""
        m = self.runner.mcfg
        return min(
            max(int(r.denoising_steps or m.denoising_steps or self._block), 1),
            self._block,
        )

    def _block_attrs(self, b: _DecodeBatch, tokens: int) -> Dict[str, Any]:
        """A block model's ``decode_window`` span: ``steps`` is the
        FORWARDS a window runs (so that the readers of a step's device
        time read milliseconds a forward): what the last fetched window
        ran, and before any was fetched the most its rows may ask for;
        beside it the window's blocks, their length, the forwards by
        kind and the positions a row it yields."""
        blocks = tokens // self._block
        commit = blocks
        denoise = self._bd_forwards - commit if self._bd_forwards else (
            blocks * max(
                (self._block_steps(self.slots[i].req) for i in b.active),
                default=1,
            )
        )
        return {
            "steps": int(denoise + commit), "blocks": blocks,
            "block_length": self._block, "denoise_forwards": int(denoise),
            "commit_forwards": commit, "tokens": tokens,
        }

    # ------------------------------------------------------------------
    # pipelined fused windows (unconstrained decode fast path)
    # ------------------------------------------------------------------

    def _pipelined_step(
        self, pipe: List[Any], b: _DecodeBatch, refill: bool
    ) -> str:
        """Keep ``decode_lookahead`` fused windows in flight (entries of
        ``pipe``: toks_dev, logps_dev, active, gens, K, route_dev, jobs) and
        fetch the oldest. At a depth of one the window dispatched here
        is the one fetched: dispatch, fetch and accept in one
        iteration. Without ``refill`` the pipe only drains. A wave that
        admission held (``_hold_wave``) is resolved between the two:
        its prefills are in front of the window just dispatched, so the
        device has that window queued while the host waits for them,
        arms the rows and accepts the oldest window."""
        KS = self._window_tokens()
        self._note_window(b, KS)
        n0 = len(pipe)
        if refill:
            while len(pipe) < max(self.ecfg.decode_lookahead, 1):
                proj = self._pipe_projection(pipe)
                K = KS if self._block == 1 else min(
                    KS, self._block_room(b.active, proj)
                )
                if K <= 0 or not self._pipe_capacity_ok(b.active, proj, K):
                    break
                self._dispatch_pipelined(pipe, b, proj, K)
        if self._wave:
            self._resolve_held(b.active if len(pipe) > n0 else ())
        if pipe:
            self._process_pipelined(pipe.pop(0))
        return "pipelined"

    def _resolve_held(self, in_window) -> None:
        """Resolve the wave that admission held, behind the decode
        dispatch (``in_window``: the slots that dispatch took), then end
        the rows whose first token ended them: what the loop's sweep
        does for a wave resolved in front of the build. Such a row's
        steps in the window are lost as ``stale``."""
        rows = self._wave_rows()
        self.timer.enter("admit_host")
        self._resolve_wave(frozenset(in_window))
        self.timer.enter("emit")
        for i, s in rows:
            if self.slots[i] is s and self._finish_reason(s, s.last_token):
                self._emit(i)

    def _block_room(self, active, proj: np.ndarray) -> int:
        """Positions, in whole blocks, that every active row of a block
        model has pages for past what is in flight: a row near its
        table's end takes a shorter window (one more compile a length,
        and there are ``decode_multi_step / block`` lengths)."""
        PS = self.ecfg.kv_page_size
        room = min(
            (len(self.slots[i].pages) * PS - self.slots[i].pos - int(proj[i])
             for i in active), default=0,
        )
        return room // self._block * self._block

    def _pipe_projection(self, pipe) -> np.ndarray:
        """[B] extra decode steps already dispatched (in-flight windows)
        but not yet processed, per slot — only windows whose (slot, gen)
        snapshot still matches count."""
        proj = np.zeros((self.B,), np.int32)
        for _, _, w_active, w_gens, wK, *_ in pipe:
            for idx, i in enumerate(w_active):
                if self._gen[i] == w_gens[idx]:
                    proj[i] += wK
        return proj

    def _pipe_capacity_ok(
        self, active, proj: np.ndarray, K: int
    ) -> bool:
        """True when a window of ``K`` more steps BEYOND everything
        already in flight stays inside every active row's TABLE ROW.

        A row's up-front reservation covers every token it may still
        commit, so the positions a window writes past the row's reserved
        pages are past the row's cap: its table holds 0 there, the
        garbage page, where an empty slot's writes go, and the tokens
        sampled there are dropped when the window is accepted
        (``finished``). Until PR 58 the rule asked for ``K`` positions
        of the row's OWN pages, so a row in its last window or two held
        back the lookahead of the whole batch: each time a job's rows
        ended, the window they ended in was the only one in flight and
        the device's queue ran empty behind it (PERF.md section 5, "The
        drain"). What the old rule did give: a slot whose row ENDS in
        the windows in flight is dead weight in one more, and a row
        that waits for a slot gets it a window later. So a window goes
        out ahead only if some row can still commit a token of it (not
        behind a warm-up job's only window), and, while rows wait for
        slots, only if the rows ending in flight are at most
        ``_AHEAD_ENDING`` of the batch (a job of sixteen of 256 rows:
        yes; a job that is a quarter of the batch, or a batch of rows a
        window long, half of them ending every iteration: no, the depth
        stays one for that window and it goes out full).
        A block model keeps the old rule: it reserves its windows' room
        at admission (``_reserve``).

        Caveat: this covers LIVE slots only. A slot released
        mid-pipeline leaves stale in-flight windows writing into freed
        pages; that case is safe only via the dispatch-order argument
        documented on ``_release``."""
        if not active:
            return False
        PS = self.ecfg.kv_page_size
        width = self.MP * PS
        if self._block > 1:
            return all(
                len(self.slots[i].pages) * PS - self.slots[i].pos
                - int(proj[i]) >= K for i in active
            )
        ending = 0
        for i in active:
            s = self.slots[i]
            if width - s.pos - int(proj[i]) < K:
                return False
            # tokens the row may still commit (its first token, if
            # still on the device, is one of max_new_tokens)
            left = s.req.max_new_tokens - max(len(s.out_ids), 1)
            if min(left, self._max_ctx - 1 - s.pos) <= proj[i]:
                ending += 1
        if ending == len(active):
            verdict = "held_unused"
        elif self._rows_waiting and ending > _AHEAD_ENDING * len(active):
            verdict = "held_ending"
        else:
            verdict = "sent"
        if self._tel_on and proj.any():  # a window AHEAD of one in flight
            telemetry.DECODE_AHEAD_WINDOWS_TOTAL.inc(1.0, verdict)
        return verdict == "sent"

    def _dispatch_pipelined(
        self, pipe, b: _DecodeBatch, proj: np.ndarray, K: int
    ) -> None:
        """Dispatch one fused window WITHOUT waiting for in-flight ones.

        ``proj`` is the in-flight projection (``_pipe_projection``): the
        window starts that many steps past each row's ``pos``. The last
        tokens chain from the previous window's device-resident sample
        row; slots admitted (or re-admitted) since that dispatch take
        their host-known token via a device-side merge, and the rows of
        a wave not resolved yet (``_Slot.first_pending``) the token
        admission's sample left on the device — no host sync anywhere
        on this path."""
        active = b.active
        if self._block > 1:
            return self._dispatch_block_window(pipe, b, proj, K)
        chained: set = set()
        prev_last = None
        if pipe:
            prev_toks, _, p_active, p_gens, *_ = pipe[-1]
            chained = {
                i
                for idx, i in enumerate(p_active)
                if p_gens[idx] == self._gen[i]
            }
            prev_last = prev_toks[-1]
        unread = [
            i for i in active
            if self.slots[i].first_pending and i not in chained
        ]
        if unread or (pipe and not all(i in chained for i in active)):
            refresh = np.ones((self.B,), bool)
            for i in chained:
                refresh[i] = False
            host_last = np.asarray(b.last, np.int32)
            if prev_last is None:
                prev_last = host_last  # nothing in flight to chain from
            if unread:
                first_mask = np.zeros((self.B,), bool)
                first_mask[unread] = True
                last_arg = self.runner.merge_first(
                    prev_last, refresh, host_last, first_mask,
                    self._first_dev,
                )
            else:
                last_arg = self.runner.merge_last(
                    prev_last, refresh, host_last
                )
        else:
            # steady state: every active row chains from the previous
            # window, no merge program at all (tokens at non-active
            # slots are garbage either way); or nothing is in flight and
            # the host knows every token (resident, as every other
            # window's last tokens are: one decode program, not two)
            last_arg = (
                self._resident(jnp.asarray(b.last, jnp.int32))
                if prev_last is None else prev_last
            )
        self._key, sub = jax.random.split(self._key)
        with self.timer.time("decode"):
            toks_dev, logps_dev = self.runner.decode_multi_async(
                last_arg, b.past_len + proj, b.table, sub, b.temp,
                b.top_p, K, top_k=b.top_k, pfx=self._split_pfx(active),
            )
        self._step += K
        pipe.append(
            (
                toks_dev,
                logps_dev,
                list(active),
                [self._gen[i] for i in active],
                K,
                # the window's routing counts, on the device beside its
                # tokens (None for a model that does not count them)
                getattr(self.runner, "window_route", None),
                # whose rows they were: a window that finds its row
                # gone counts its steps lost to that row's job
                [self.slots[i].job for i in active],
            )
        )

    def _dispatch_block_window(
        self, pipe, b: _DecodeBatch, proj: np.ndarray, K: int
    ) -> None:
        """``_dispatch_pipelined`` for a model that generates by blocks:
        a window of ``K / block`` whole blocks a row, from each row's
        ``pos`` past what is in flight. Nothing chains from the window
        before it: a block starts as masks, but for a row's FIRST block,
        which starts with the prompt's leftover tokens (``_Slot.given``,
        while no window of the row is in flight)."""
        from ..models.configs import REMASKING

        m = self.runner.mcfg
        Bk = self._block
        first = np.zeros((self.B, Bk), np.int32)
        live = np.zeros((self.B,), bool)
        steps = np.ones((self.B,), np.int32)
        rule = np.zeros((self.B,), np.int32)
        tau = np.zeros((self.B,), np.float32)
        given = []
        for i in b.active:
            s, r = self.slots[i], self.slots[i].req
            live[i] = True
            first[i] = m.mask_token_id
            g = s.given if proj[i] == 0 else 0
            if g:
                first[i, :g] = r.prompt_ids[s.pos : s.pos + g]
            given.append(g)
            steps[i] = self._block_steps(r)
            rule[i] = REMASKING.index(r.remasking or m.remasking)
            tau[i] = (
                m.confidence_threshold if r.confidence_threshold is None
                else r.confidence_threshold
            )
        self._key, sub = jax.random.split(self._key)
        with self.timer.time("decode"):
            toks_dev, logps_dev, turns_dev = self.runner.decode_block_async(
                first, live, b.past_len + proj, b.table, sub, b.temp,
                b.top_p, K // Bk, top_k=b.top_k, steps=steps, rule=rule,
                tau=tau,
            )
        self._step += K
        pipe.append((
            toks_dev, logps_dev, list(b.active),
            [self._gen[i] for i in b.active], K,
            getattr(self.runner, "window_route", None),
            [self.slots[i].job for i in b.active],
            # the denoising forwards each block ran, on the device, and
            # the leading positions of each row that were given
            (turns_dev, given),
        ))

    def _process_pipelined(self, entry) -> None:
        """Fetch one in-flight window's results (the only host sync in
        the pipelined path) and accept its tokens. Tokens for slots
        whose generation changed since dispatch (released, possibly
        re-admitted) are discarded. Accounting and results stream
        through each slot's job (_accept_token).

        PLAIN rows — no constraint, no penalties, no stop sequences —
        take a vectorized window-acceptance path
        (round-5 host-overhead profile: the per-token Python loop cost
        ~26 ms per B=128 window, 2× the device window itself); rows with
        any per-token machinery keep the exact per-token loop."""
        (toks_dev, logps_dev, w_active, w_gens, wK, route_dev, w_jobs,
         *block) = entry
        # a block model's rows: the leading positions of each that were
        # GIVEN (the prompt's leftover tokens), which commit no token
        given: Dict[int, int] = {}
        with self.timer.time("decode"):
            toks = np.asarray(toks_dev)
            logps = np.asarray(logps_dev)
            if block:
                turns_dev, w_given = block[0]
                given = dict(zip(w_active, w_given))
                self._note_block_window(
                    np.asarray(turns_dev), len(w_active), route_dev
                )
            elif route_dev is not None:
                self._note_route("decode_window", route_dev)
        self.timer.enter("accept")
        n0 = self._n_accepted
        plain: List[int] = []
        rest: List[int] = []
        # every slot of w_active was dispatched wK POSITIONS (a row-step
        # is a position of a row, whatever forwards filled it)
        lost: Dict[str, int] = {}
        for idx, i in enumerate(w_active):
            ctx = w_jobs[idx]
            if ctx is not None:
                ctx.stats["row_steps"] += wK
            if self._gen[i] != w_gens[idx] or self.slots[i] is None:
                _lose(lost, ctx, "stale", wK)
                continue
            s = self.slots[i]
            r = s.req
            if given.get(i):
                # in the cache with the window's write, no token of the
                # row's output
                _lose(lost, ctx, "given", given[i])
                s.pos += given[i]
                s.given = 0
            if (
                r.constraint is None
                and not r.stop_seqs
                and not r.has_penalties()
            ):
                plain.append(i)
            else:
                rest.append(i)
        if plain:
            self._accept_plain_window(plain, toks, logps, wK, lost, given)
        for j in range(wK):
            for i in rest:
                s = self.slots[i]
                if s is None or j < given.get(i, 0):
                    continue  # finished earlier in this window; a given
                ctx = s.job
                rc = self._accept_token(
                    i, int(toks[j][i]), float(logps[j][i])
                )
                if rc == 2:
                    _lose(lost, ctx, "failed", wK - j)
                elif rc:
                    _lose(lost, ctx, "finished", wK - 1 - j)
        if block and self._tel_on:
            n_given = lost.get("given", 0)
            for fate, n in (
                ("accepted", self._n_accepted - n0), ("given", n_given),
                ("lost", sum(lost.values()) - n_given),
            ):
                telemetry.BLOCK_TOKENS_TOTAL.inc(float(n), fate)
        self._close_accept(n0, wK * len(w_active), lost)

    def _trace_resume(self, ctx: JobCtx, req: GenRequest) -> None:
        """Close a preempt_suspend pair: the row a preemption suspended
        is re-entering the batch (telemetry on, checked by caller)."""
        rid = int(req.row_id)
        if rid in ctx.trace_preempted:
            ctx.trace_preempted.discard(rid)
            if ctx.trace_id is not None:
                telemetry.TRACES.event(
                    ctx.trace_id, "resume", {"row_id": rid}
                )

    def _note_block_window(self, turns, rows: int, route_dev) -> None:
        """What a fetched window of blocks ran: its forwards by kind
        (``turns`` [blocks]: the denoising forwards of each block; one
        commit a block) into the counters and the next spans' ``steps``,
        its routing counts (the rows of forwards that ran) into the
        span's attrs."""
        denoise, commit = float(np.sum(turns)), float(len(turns))
        self._bd_forwards = denoise + commit
        if self._tel_on:
            for kind, n in (("denoise", denoise), ("commit", commit)):
                telemetry.BLOCK_FORWARDS_TOTAL.inc(n, kind)
                telemetry.BLOCK_ROW_FORWARDS_TOTAL.inc(n * rows, kind)
        if route_dev is not None:
            route = np.asarray(route_dev).reshape(-1, 6)
            ran = route[route[:, 4] > 0]
            if len(ran):
                self._note_route("decode_window", ran)

    def _accept_plain_window(
        self, idxs: List[int], toks: np.ndarray, logps: np.ndarray,
        wK: int, lost: Dict[str, int], given: Optional[Dict[int, int]] = None,
    ) -> None:
        """Accept a whole window for plain rows with one numpy pass per
        row instead of wK interpreter iterations. Semantics mirror
        _accept_token/_finish_reason exactly: tokens are taken up to and
        including the first trigger among stop-id ("stop"),
        max_new_tokens ("length"), and context limit ("length") — at
        the same position the stop-id check wins, as in the per-token
        order. The row-steps of these rows that committed nothing go
        into ``lost`` (behind a row's end; of rows that failed)."""
        ii = np.asarray(idxs, np.int64)
        tw = toks[:, ii]                             # [K, n]
        lw = logps[:, ii].astype(np.float64)         # [K, n]
        is_stop = (
            np.isin(tw, self._stop_arr)
            if self._stop_arr.size
            else np.zeros_like(tw, bool)
        )
        window = wK
        for col, i in enumerate(idxs):
            s = self.slots[i]
            # a block model's row: the window's positions behind those
            # given it (already counted, ``_process_pipelined``)
            g = given.get(i, 0) if given else 0
            wK = window - g
            INF = wK + 1
            if faults.ACTIVE is not None:
                # the vectorized path skips _accept_token, so the
                # per-row decode fault site fires here instead
                try:
                    faults.inject(
                        "row.decode", row=s.req.row_id,
                        job=s.job.job_id if s.job is not None else None,
                    )
                except Exception as e:  # noqa: BLE001 — row isolation
                    _lose(lost, s.job, "failed", wK)
                    self._fail_slot(i, e)
                    continue
            # first k (tokens accepted) at which the row finishes —
            # mirrors _finish_reason's per-token checks
            stops = np.flatnonzero(is_stop[g:, col])
            n_stop = int(stops[0]) + 1 if stops.size else INF
            n_len = s.req.max_new_tokens - len(s.out_ids)
            n_ctx = self._max_ctx - 1 - s.pos
            limit = min(n_stop, n_len, n_ctx)
            if limit <= 0:
                # budget already exhausted at window start (the row
                # should have been emitted earlier; a stale window can
                # still land here): finish NOW with zero tokens taken —
                # the old max(..., 1) silently accepted one token past
                # the cap
                _lose(lost, s.job, "finished", wK)
                self._emit(i)
                continue
            n_take = min(limit, wK)
            col_t = tw[g : g + n_take, col]
            s.out_ids.extend(col_t.tolist())  # C-speed, yields ints
            s.logprob_sum += float(lw[g : g + n_take, col].sum())
            s.pos += n_take
            self._n_accepted += n_take
            s.last_token = int(col_t[-1])
            if self.native is not None:
                self.native.note_bulk(i, s.last_token, n_take)
            if s.job is not None:
                s.job.stats["out"] += n_take
                if s.job.on_token is not None:
                    lcol = lw[g : g + n_take, col]
                    for k in range(n_take):
                        self._deliver_token(
                            s, int(col_t[k]), float(lcol[k])
                        )
            if limit <= wK:
                _lose(lost, s.job, "finished", wK - n_take)
                self._emit(i)

    # ------------------------------------------------------------------
    # host-synchronous paths: the constrained window, the single step
    # ------------------------------------------------------------------

    def _window_step(self, b: _DecodeBatch) -> str:
        """The speculative window of a constrained greedy batch, while
        the FSMs accept enough of its unmasked tokens (``_choose_path``):
        sample unmasked, verify host-side, commit only each row's
        FSM-valid prefix. Rows whose previous window rejected take
        their FSM-masked step as the window's FIRST step (allowed0):
        per-row recovery, full cadence for everyone else. What the
        verify finds (a row's speculative positions asked, and accepted,
        up to its first refusal) is what the choice is made from
        (``_observe``)."""
        tm = self.timer
        active = b.active
        K = self.ecfg.decode_multi_step
        self._note_window(b, K)
        self._key, sub = jax.random.split(self._key)
        self._stepping = False
        allowed0 = None
        flagged: set = self._needs_mask & set(active)
        if flagged:
            allowed0 = self._fsm_masks(flagged)
            self._needs_mask -= flagged
        with tm.time("decode"):
            toks_w, logps_w, handle = self.runner.decode_window(
                b.last, b.past_len, b.table, sub, b.temp, b.top_p, K,
                top_k=b.top_k, allowed0=allowed0,
                pfx=self._split_pfx(active),
            )
            self._note_route("decode_window")
        self._step += K
        tm.enter("accept")
        n0 = self._n_accepted
        accepted = np.zeros((self.B,), np.int32)
        finished: List[int] = []
        # every active row was dispatched K
        lost: Dict[str, int] = {}
        for i in active:
            s = self.slots[i]
            if s is None:
                # failed during mask assembly, its job gone with it
                _lose(lost, None, "failed", K)
                continue
            c = s.req.constraint
            ctx = s.job
            if ctx is not None:
                ctx.stats["row_steps"] += K
            asked = ok = 0
            for j in range(K):
                tok = int(toks_w[j][i])
                # a flagged row's step-0 token was chosen UNDER its FSM
                # mask — accept without re-verifying, exactly like the
                # masked single-step this replaces. Re-checking would
                # livelock in the budget-infeasible corner where
                # allowed_tokens degrades to unfiltered but
                # token_allowed still returns False (fsm.py degrade
                # semantics).
                if c is not None and not (j == 0 and i in flagged):
                    rem = self._remaining(s.req, len(s.out_ids), s.pos)
                    try:
                        tok_ok = self._token_ok(c, tok, rem)
                    except Exception as e:  # noqa: BLE001 — row isolation
                        self._fail_slot(i, e)
                        _lose(lost, ctx, "failed", K - j)
                        break
                    asked += 1
                    if not tok_ok:
                        # this row's NEXT dispatch opens with its
                        # FSM-masked step (a window's allowed0, or the
                        # masked single step) so it crosses the
                        # scaffold token; other rows keep full window
                        # cadence
                        self._needs_mask.add(i)
                        _lose(lost, ctx, "rejected", K - j)
                        break
                    ok += 1
                rc = self._accept_token(
                    i, tok, float(logps_w[j][i]), release=False,
                )
                if rc == 2:
                    # row failed: token NOT committed
                    _lose(lost, ctx, "failed", K - j)
                    break
                accepted[i] += 1
                if rc:
                    finished.append(i)
                    _lose(lost, ctx, "finished", K - 1 - j)
                    break
            self._observe(ctx, ok, asked)
        self._close_accept(n0, K * len(active), lost)
        # pages are still reserved for every row (releases were
        # deferred), so the accepted K/V lands safely
        with tm.time("decode"):
            self.runner.commit_window(handle, accepted)
        tm.enter("emit")
        for i in finished:
            self._emit(i)
        return "window"

    def _penalty_operands(self, active: List[int]):
        """The single step's penalty operands: per row the packed seen
        bits and the distinct generated ids with their counts."""
        # Distinct generated ids carried per row. K is a jit shape, so
        # grow it in power-of-two buckets: exact presence/frequency
        # semantics at any generation length, with at most log2 extra
        # compiles.
        PK = 256
        max_distinct = max(
            (
                len(self.slots[i].counts)
                for i in active
                if self.slots[i].req.has_penalties()
            ),
            default=0,
        )
        while PK < max_distinct:
            PK *= 2
        if PK > 256 and PK not in self._pk_grown:
            self._pk_grown.add(PK)
            logger.info(
                "penalty id buffer grown to K=%d (a row has %d distinct "
                "generated ids)", PK, max_distinct,
            )
        nb = (self.vocab + 7) // 8
        seen_packed = np.zeros((self.B, nb), np.uint8)
        ids_p = np.full((self.B, PK), -1, np.int32)
        cnt_p = np.zeros((self.B, PK), np.float32)
        pres = np.zeros((self.B,), np.float32)
        freq = np.zeros((self.B,), np.float32)
        rep = np.ones((self.B,), np.float32)
        for i in active:
            s = self.slots[i]
            if not s.req.has_penalties():
                continue
            pres[i] = s.req.presence_penalty
            freq[i] = s.req.frequency_penalty
            rep[i] = s.req.repetition_penalty
            if s.seen_bits is not None:
                seen_packed[i] = s.seen_bits  # memcpy
            assert len(s.counts) <= PK  # growth above
            for j, t in enumerate(s.counts):
                ids_p[i, j] = t
                cnt_p[i, j] = s.counts[t]
        return seen_packed, ids_p, cnt_p, pres, freq, rep

    def _single_step(self, b: _DecodeBatch) -> str:
        """One decode step with whatever its rows need from the host
        between steps: FSM masks (sampled constrained rows, and greedy
        ones while a window's unmasked tokens would be refused:
        ``_choose_path``), per-row seeds, penalties; also the
        near-capacity tail of any batch. A masked step says of each row
        whether its unmasked argmax lay inside its mask, which is what
        a window's verify would have found there (``_observe``): how a
        batch on masked steps finds its way back to windows."""
        f = b.facts
        active = b.active
        self._note_window(b, 1)
        self._stepping = f.has_constraint and f.constrained_greedy
        self._key, sub = jax.random.split(self._key)
        allowed = None
        if f.has_constraint:
            # masked step: per-row FSM vocab masks (fused windows verify
            # tokens instead; their allowed0 recovery masks come from
            # the same helper)
            allowed = self._fsm_masks(active)
        penalties = (
            self._penalty_operands(active) if f.has_penalty else None
        )
        with self.timer.time("decode"):
            toks, logps = self.runner.decode_step(
                b.last, b.past_len, b.table,
                # row-seeded sampling needs a batch-independent base
                # key so a row's stream reproduces regardless of batch
                # composition
                self._fixed_key if f.has_row_seed else sub,
                b.temp, b.top_p, top_k=b.top_k, allowed=allowed,
                row_seeds=b.row_seeds if f.has_row_seed else None,
                penalties=penalties, pfx=self._split_pfx(active),
            )
            self._note_route("decode_window")
        self._step += 1
        # masked single-step crossed every flagged row's rejected
        # scaffold token
        self._needs_mask.clear()
        self.timer.enter("accept")
        n0 = self._n_accepted
        unmasked_ok = None
        if self._stepping:
            take = getattr(self.runner, "take_unmasked_ok", None)
            unmasked_ok = take() if take is not None else None
        lost: Dict[str, int] = {}  # every active row was dispatched one
        for i in active:
            s = self.slots[i]
            if s is None:
                # failed during mask assembly, its job gone with it
                _lose(lost, None, "failed", 1)
                continue
            ctx = s.job
            if ctx is not None:
                ctx.stats["row_steps"] += 1
            if unmasked_ok is not None and s.req.constraint is not None:
                self._observe(ctx, int(unmasked_ok[i]), 1)
            if self._accept_token(i, int(toks[i]), float(logps[i])) == 2:
                _lose(lost, ctx, "failed", 1)
        self._close_accept(n0, len(active), lost)
        return "single"

    # ------------------------------------------------------------------

    def run(
        self,
        requests: List[GenRequest],
        *,
        on_result: Callable[[GenResult], None],
        on_progress: Optional[Callable[[Dict[str, Any]], None]] = None,
        should_cancel: Optional[Callable[[], bool]] = None,
        should_yield: Optional[Callable[[], bool]] = None,
        progress_every: float = 1.0,
        row_retries: int = 0,
        on_row_event: Optional[Callable[[Dict[str, Any]], None]] = None,
        job_id: str = "_single",
    ) -> str:
        """Run all requests to completion, streaming results/progress.

        Returns ``"completed"``, ``"cancelled"``, or ``"yielded"``.
        ``should_yield`` is polled between decode steps (same cadence as
        ``should_cancel``): on True the batcher drops its in-flight
        slots WITHOUT emitting results (those rows regenerate when the
        caller re-runs the job; completed rows were already emitted) and
        returns immediately — the preemption primitive behind priority
        scheduling (reference two-priority semantics, README.md:168-171).

        ``row_retries``/``on_row_event`` configure the row-level failure
        domain (see JobCtx) — DP shards get the same retry/quarantine
        semantics as co-batched sessions.

        ``job_id`` tags this run's telemetry spans (dp shard runs pass
        their engine job id so the flight-recorder timeline is
        attributable; the default keeps ad-hoc callers anonymous).

        Single-job convenience over :meth:`run_multi`."""
        outcome: Dict[str, str] = {}
        ctx = JobCtx(
            job_id=job_id,
            pending=list(requests),
            on_result=on_result,
            on_progress=on_progress,
            should_cancel=should_cancel,
            progress_every=progress_every,
            row_retries=row_retries,
            on_row_event=on_row_event,
        )
        state = self.run_multi(
            [ctx],
            on_job_done=lambda c, o: outcome.__setitem__("v", o),
            should_yield=should_yield,
        )
        if state == "yielded":
            return "yielded"
        return outcome.get("v", "completed")

    def _start_job(self, ctx: JobCtx) -> None:
        """Prepare a job for the session: truncation policy pass, the
        shortest-first admission order, and the job's shared-prefix
        prefill."""
        self.timer.wake("job_start")
        pending = []
        # lazy-constraint jobs share one factory: probe its room ONCE
        # per job instead of instantiating an FSM per row here
        factory_room: Dict[int, int] = {}
        for req in ctx.pending:
            # truncation must leave enough generation room to honor the
            # row's schema: a prompt that fills the context would leave
            # a constrained row 1 token ("{") and silently break the
            # guaranteed-JSON contract. Plain rows keep >=1 token.
            need = 1
            if req.constraint is not None:
                from .constrain.fsm import constraint_room

                need = constraint_room(req.constraint)
            elif req.constraint_factory is not None:
                from .constrain.fsm import constraint_room

                key = id(req.constraint_factory)
                if key not in factory_room:
                    try:
                        factory_room[key] = constraint_room(
                            req.constraint_factory()
                        )
                    except Exception:  # noqa: BLE001 — row isolation
                        # a failing factory surfaces PER ROW at
                        # materialization (retry/quarantine); the probe
                        # only loses the schema-room truncation reserve
                        logger.warning(
                            "constraint probe failed at job start; "
                            "surfacing per-row at admission",
                            exc_info=True,
                        )
                        factory_room[key] = 1
                need = factory_room[key]
            max_prompt = self.ecfg.max_context() - need
            if len(req.prompt_ids) > max_prompt:
                if req.allow_truncate and max_prompt > 0:
                    req = dataclasses.replace(
                        req, prompt_ids=req.prompt_ids[:max_prompt]
                    )
                else:
                    # schema minimum cannot fit the context at all —
                    # an explicit per-row error beats invalid JSON
                    msg = (
                        f"prompt of {len(req.prompt_ids)} tokens leaves "
                        f"no room for generation (max context "
                        f"{self.ecfg.max_context()}, reserve {need}) "
                        "and truncate_rows is off"
                    )
                    if ctx.on_row_event is not None:
                        ctx.on_row_event(
                            {"event": "row_quarantined",
                             "row_id": req.row_id, "attempt": 0,
                             "error": msg}
                        )
                    ctx.stats["rows"] += 1
                    ctx.on_result(
                        GenResult(
                            row_id=req.row_id,
                            token_ids=[],
                            cumulative_logprob=0.0,
                            finish_reason="error_too_long",
                            input_tokens=len(req.prompt_ids),
                            error=msg,
                        )
                    )
                    continue
            pending.append(req)
        # pop() serves the SHORTEST prompts first: batched prefill pads
        # every row in a dispatch to the group's bucket, so grouping
        # similar lengths cuts padding FLOPs on mixed-length jobs (and
        # quick rows finish early for progress). Results are keyed by
        # row_id — output order is unaffected (reference 1:1 contract).
        pending.sort(key=lambda r: len(r.prompt_ids), reverse=True)
        ctx.pending = pending
        # shared-prefix setup is LAZY (_admit_pending): a job attached
        # behind a full batch must not pin prefix pages while it waits
        ctx.started = ctx.t_last = time.monotonic()
        if self._tel_on:
            if ctx.trace_id is None:
                # batch jobs get a per-job trace at adoption (the
                # gateway already assigned one to interactive requests)
                ctx.trace_id = f"tr-{ctx.job_id}"
                telemetry.TRACES.start_trace(
                    ctx.trace_id, "batch",
                    {"job_id": ctx.job_id, "rows": len(ctx.pending)},
                )
            if ctx.trace_enq_mono:
                # admission queue wait: from submit/park to session
                # adoption — the leg the queue_wait_bound verdict grades
                telemetry.TRACES.add(
                    ctx.trace_id, "queue_wait", ctx.trace_enq_mono,
                    ctx.started - ctx.trace_enq_mono,
                )

    def _job_progress(self, ctx: JobCtx, force: bool = False) -> None:
        if ctx.on_progress is None:
            return
        now = time.monotonic()
        if not force and now - ctx.t_last < ctx.progress_every:
            return
        ctx.t_last = now
        elapsed = max(now - ctx.started, 1e-9)
        payload = {
            "rows_completed": ctx.stats["rows"],
            "input_tokens": ctx.stats["in"],
            "output_tokens": ctx.stats["out"],
            "total_tokens_processed_per_second": (
                (ctx.stats["in"] + ctx.stats["out"]) / elapsed
            ),
        }
        ctx.on_progress(payload)

    def _finish_job(
        self, ctx: JobCtx, outcome: str, on_job_done,
        emit_cancel: bool = False,
    ) -> None:
        """Terminal transition for one job of the session. With
        ``emit_cancel`` the job's live slots are released as
        ``cancelled`` results and its pending rows dropped (the
        jobstore layer records never-run rows)."""
        self.timer.wake("emit")
        if emit_cancel:
            self._resolve_wave()
            for i, s in enumerate(self.slots):
                if s is not None and s.job is ctx:
                    self._emit(i, reason="cancelled")
            ctx.pending.clear()
        if ctx.prefix is not None:
            self._release_prefix(ctx.prefix)
            ctx.prefix = None
        if self._hibernated:
            self._purge_hibernated(ctx)
        ctx.done = True
        if self.ladder is not None:
            self.ladder.forget(ctx)  # drop the aging-clock entry
        self._job_progress(ctx, force=True)
        on_job_done(ctx, outcome)

    def _suspend_job(self, ctx: JobCtx) -> None:
        """Yield path: drop the job's live slots WITHOUT emitting
        results (those rows regenerate on resume; completed rows were
        already streamed) and return its shared-prefix pages."""
        self._resolve_wave()
        for i, s in enumerate(self.slots):
            if s is not None and s.job is ctx:
                self._drop_slot(i)
        if ctx.prefix is not None:
            self._release_prefix(ctx.prefix)
            ctx.prefix = None
        if self._hibernated:
            # the session layer rebuilds pending on resume; a stale
            # hibernation entry must not shadow those fresh requests
            self._purge_hibernated(ctx)
        ctx.prefix_ready = False  # a resumed ctx re-detects its prefix

    def _after_step(
        self, live: List[JobCtx], on_job_done, path: str, n_active: int
    ) -> None:
        """The tail every decode path shares: count the iteration, and
        what the dispatch it accepted yielded, by the path it took;
        finish drained jobs, tick progress streams."""
        if self._tel_on:
            row_steps, tokens, lost = self._yield
            self._yield = (0, 0, {})
            telemetry.SCHED_ITERATIONS_TOTAL.inc(1.0, path)
            telemetry.SCHED_DISPATCH_ROWS_TOTAL.inc(float(n_active))
            telemetry.SCHED_ROW_STEPS_TOTAL.inc(float(row_steps), path)
            telemetry.SCHED_TOKENS_COMMITTED_TOTAL.inc(float(tokens), path)
            for reason, n in lost.items():
                telemetry.SCHED_ROW_STEPS_LOST_TOTAL.inc(
                    float(n), path, reason
                )
        self.timer.enter("emit")
        self._sweep_done(live, on_job_done)
        for ctx in live:
            if not ctx.done:
                self._job_progress(ctx)

    def _ready_rows(self, live: List[JobCtx], on_job_done) -> List[int]:
        """End the rows that their first token ended (a stop id, one
        token asked for), finish the jobs that leaves drained, and
        return the slots of this iteration's decode batch. A row seated
        with its first token still on the device is in the batch and is
        passed by until its wave is resolved."""
        for i, s in enumerate(self.slots):
            if (
                s is not None
                and not s.prefilling
                and not s.first_pending
                and self._finish_reason(s, s.last_token)
            ):
                self._emit(i)
        self._sweep_done(live, on_job_done)
        return [
            i
            for i, s in enumerate(self.slots)
            if s is not None and not s.prefilling
        ]

    def _sweep_done(self, live: List[JobCtx], on_job_done) -> None:
        for ctx in live:
            if not ctx.done and not ctx.pending and ctx.n_slots == 0:
                if ctx.hold_open is not None and ctx.hold_open():
                    # stage-graph downstream ctx: drained for NOW, but
                    # upstream feeders are still producing rows
                    continue
                self._finish_job(ctx, "completed", on_job_done)

    def _interactive_slots_used(self) -> int:
        return sum(
            1
            for s in self.slots
            if s is not None and s.job is not None and s.job.interactive
        )

    def _hibernate_slot(self, i: int) -> bool:
        """Suspend slot ``i`` by demoting its own KV — INCLUDING the
        partial tail page (``ceil(pos/PS)`` own pages) — into the
        tiered pool instead of discarding it, so the preempted row
        resumes by pure page-upload with zero re-prefilled tokens.
        (Positions >= pos inside the tail page are garbage, but
        attention masks to pos and the resumed decode overwrites them
        in place through the page table.) The demote is SYNCHRONOUS
        and pinned: the device pages free only after the pool owns the
        payload, so a torn demotion (fault site ``kvtier.demote``)
        degrades to the caller's plain regenerate suspend — never a
        corrupt row. Returns True when the slot was hibernated and its
        ORIGINAL request (live constraint and all) re-queued."""
        if not self._can_hibernate:
            if self._tier_refused and self.slots[i] is not None:
                # the tier is there, the row's state (or its window
                # layers' K/V) is in no page the tier can take: the
                # caller's plain suspend regenerates the row
                self._count_state_fallback(
                    self.slots[i].pos, self._layout.refuses("tiers")
                )
            return False
        s = self.slots[i]
        if s is None or s.prefilling or s.job is None:
            return False
        ctx = s.job
        PS = self.ecfg.kv_page_size
        end = -(-s.pos // PS)  # ceil: the partial tail page rides along
        if self._layout.has_state:
            # conv state is ONE value a page, the state after the last
            # token written there; windows in flight when the row is
            # preempted have written the tail page past ``pos``, so its
            # state is ahead of the row. Whole pages hold the state
            # after their last position whatever ran past them: capture
            # those, and the resume prefills the tail again (counted)
            end = s.pos // PS
            tail = s.pos - max(s.shared_n, end) * PS
            if tail > 0 and self._tel_on:
                telemetry.STATE_FALLBACK_PREFILL_TOKENS_TOTAL.inc(
                    float(tail), "hibernated_tail_page"
                )
        own_aligned = [
            int(p) for p in s.pages[s.shared_n : max(s.shared_n, end)]
        ]
        key = b""
        if own_aligned:
            self._hib_seq += 1
            key = b"hib:%d:%d:%d" % (
                id(ctx), int(s.req.row_id), self._hib_seq,
            )
            try:
                with self.timer.time("kv_demote"):
                    raw = self.runner.read_pages(own_aligned)
                    self._kv_tier.put_row(key, raw)
                self.tier_demotes += len(own_aligned)
            except Exception:  # noqa: BLE001 — HBM copy stays
                # authoritative: fall back to the plain suspend
                logger.warning(
                    "hibernation demote failed; row %d regenerates",
                    s.req.row_id, exc_info=True,
                )
                return False
        self._hibernated[(id(ctx), int(s.req.row_id))] = _Hib(
            key=key,
            pos=s.pos,
            last_token=s.last_token,
            out_ids=list(s.out_ids),
            logprob_sum=s.logprob_sum,
            tail=s.tail,
            hit_stop_seq=s.hit_stop_seq,
            stop_longest=s.stop_longest,
            counts=dict(s.counts),
            seen_bits=s.seen_bits,
            shared_tokens=s.shared_n * PS,
            n_pages=len(own_aligned),
        )
        self._drop_slot(i)
        # the ORIGINAL request re-queues — its live constraint object
        # continues in place at resume (the stripped retry-style copy
        # is built only if the tier loses the payload)
        ctx.pending.insert(0, s.req)
        return True

    def _resume_hibernated(
        self, req: GenRequest, ctx: JobCtx, r, hib: _Hib
    ) -> Optional[GenRequest]:
        """Re-admit a hibernated row into reservation ``r``: upload its
        tier payload into the fresh pages and arm the slot exactly
        where it stopped — a pure upload, since hibernation captures
        the partial tail page (the legacy sub-page re-prefill branch
        survives only for aligned-capture entries, and is refused under
        sp/pp where suffix prefill is unsupported). Returns None on
        success (the slot is live); on a tier miss — torn demotion,
        host-LRU drop without a disk tier, or a shared-prefix coverage
        change across a session suspend — returns a FRESH request for
        the caller to admit through the normal path (the pre-tier
        full-regenerate behavior)."""
        self._resolve_wave()
        slot_idx, own_pages, table = r
        PS = self.ecfg.kv_page_size
        shared = ctx.prefix.tokens if ctx.prefix is not None else 0
        payload = None
        ok = shared == hib.shared_tokens
        if ok and hib.n_pages:
            payload = self._kv_tier.take_row(hib.key)
            ok = (
                payload is not None
                and int(payload["k"].shape[1]) == hib.n_pages
            )
            if ok and self._layout.has_state and (
                "c" not in payload
            ):
                # K/V without the conv state would resume the row from
                # a wrong state: regenerate instead, and count it
                ok = False
                if self._tel_on:
                    telemetry.STATE_FALLBACK_PREFILL_TOKENS_TOTAL.inc(
                        float(hib.pos), "tier_payload_without_state"
                    )
        start = shared + hib.n_pages * PS
        if ok and hib.pos > start and self._wrapped:
            # aligned-capture entry on a sharded runner: the sub-page
            # tail would need prefill(start>0), which sp/pp forbids —
            # treat as a miss and regenerate rather than assert
            ok = False
        if ok:
            try:
                with self.timer.time("kv_promote"):
                    if payload is not None:
                        self.runner.write_pages(
                            [int(p) for p in own_pages[: hib.n_pages]],
                            payload,
                        )
                    if hib.pos > start:
                        full = np.concatenate(
                            [
                                np.asarray(req.prompt_ids, np.int32),
                                np.asarray(hib.out_ids, np.int32),
                            ]
                        )
                        # the truly novel tail: KV for the sub-page
                        # positions the aligned payload cannot carry
                        self.runner.prefill(
                            full[start : hib.pos],
                            np.asarray(table, np.int32),
                            start=start,
                        )
            except Exception:  # noqa: BLE001 — the reservation stays;
                # normal admission below overwrites every position
                logger.warning(
                    "hibernation resume failed; row %d regenerates",
                    req.row_id, exc_info=True,
                )
                ok = False
        if not ok:
            ctx.stats["resumes_reprefill"] = (
                ctx.stats.get("resumes_reprefill", 0) + 1
            )
            if self._tel_on:
                telemetry.KV_RESUMES_TOTAL.inc(1.0, "reprefill")
            # victim selection guaranteed the constraint is rebuildable
            return dataclasses.replace(
                req,
                constraint=None,
                prepped_constraint=None,
                prep_queued=False,
            )
        pfx = ctx.prefix
        slot = _Slot(
            req=req,
            pages=(
                (list(pfx.pages) + list(own_pages))
                if pfx is not None
                else list(own_pages)
            ),
            pos=hib.pos,
            last_token=hib.last_token,
            job=ctx,
            shared_n=pfx.n_pages if pfx is not None else 0,
            out_ids=list(hib.out_ids),
            logprob_sum=hib.logprob_sum,
            tail=hib.tail,
            hit_stop_seq=hib.hit_stop_seq,
            stop_longest=hib.stop_longest,
            counts=dict(hib.counts),
            seen_bits=hib.seen_bits,
        )
        self.slots[slot_idx] = slot
        ctx.n_slots += 1
        if self.native is not None:
            self.native.arm_slot(
                slot_idx, hib.pos, hib.last_token,
                req.temperature, req.top_p, req.top_k,
            )
        self.tier_promotes += hib.n_pages
        ctx.stats["resumes_upload"] = (
            ctx.stats.get("resumes_upload", 0) + 1
        )
        if self._tel_on:
            telemetry.KV_RESUMES_TOTAL.inc(1.0, "upload")
            if ctx.trace_id is not None:
                telemetry.TRACES.event(
                    ctx.trace_id, "hibernate_resume",
                    {"row_id": int(req.row_id),
                     "pages": int(hib.n_pages),
                     "reprefilled_tokens": max(0, int(hib.pos - start))},
                )
        return None

    def _purge_hibernated(self, ctx: JobCtx) -> None:
        """Drop every hibernated entry of ``ctx`` (job finished, or the
        whole session is suspending). Pending requests for those rows
        carry LIVE advanced constraints that only a resume could have
        continued — with the host state gone they must re-admit as
        fresh requests, exactly the retry-path rebuild."""
        stale = [k for k in self._hibernated if k[0] == id(ctx)]
        if not stale:
            return
        rows = set()
        keys: List[bytes] = []
        for k in stale:
            h = self._hibernated.pop(k)
            rows.add(k[1])
            if h.key:
                keys.append(h.key)
        if self._kv_tier is not None and keys:
            self._kv_tier.discard(keys)
        for j, r in enumerate(ctx.pending):
            if int(r.row_id) in rows and (
                r.constraint is not None or r.prep_queued
            ):
                ctx.pending[j] = dataclasses.replace(
                    r,
                    constraint=None,
                    prepped_constraint=None,
                    prep_queued=False,
                )

    def _evict_for_interactive(self, ctx: JobCtx) -> bool:
        """Latency-priority admission (Sarathi-style mixed windows): when
        an INTERACTIVE row finds the batch full, suspend one batch row —
        inside the ``EngineConfig.interactive_slots`` budget — so the
        request enters the live decode window now instead of waiting for
        a batch row to finish. The victim re-admits row-granularly (same
        rebuild rule as the retry path: a directly supplied FSM cannot
        be rewound); its partial output regenerates, exactly like a
        session-yield suspend. Returns True when a victim was freed."""
        budget = getattr(self.ecfg, "interactive_slots", 0)
        if not ctx.interactive or budget <= 0:
            return False
        # a victim is chosen among ARMED rows, and its slot goes
        self._resolve_wave()
        if self._interactive_slots_used() >= budget:
            return False  # the tier already holds its reserved share
        best: Optional[int] = None
        best_cost = -1
        for i, s in enumerate(self.slots):
            if s is None or s.job is None or s.job.interactive:
                continue
            if s.req.constraint is not None and (
                s.req.constraint_factory is None
            ):
                continue  # not rebuildable — cannot re-admit from scratch
            cost = len(s.out_ids) + (s.prefill_pos if s.prefilling else 0)
            if best is None or cost < best_cost:
                best, best_cost = i, cost
        if best is None:
            return False
        s = self.slots[best]
        victim = s.job
        hibernated = self._hibernate_slot(best)
        if not hibernated:
            self._drop_slot(best)
            # fresh request at the HEAD of pending (admission pops the
            # tail), so the victim's other rows keep their order and
            # this one re-admits once the batch has room again
            victim.pending.insert(
                0,
                dataclasses.replace(
                    s.req,
                    constraint=None,
                    prepped_constraint=None,
                    prep_queued=False,
                ),
            )
        victim.stats["preempted"] = victim.stats.get("preempted", 0) + 1
        if self._tel_on:
            telemetry.INTERACTIVE_PREEMPTIONS_TOTAL.inc(1.0)
            if victim.trace_id is not None:
                victim.trace_preempted.add(int(s.req.row_id))
                telemetry.TRACES.event(
                    victim.trace_id, "preempt_suspend",
                    {"row_id": int(s.req.row_id), "by": ctx.job_id,
                     "lost_tokens": 0 if hibernated else int(best_cost),
                     "hibernated": bool(hibernated)},
                )
        logger.debug(
            "interactive admit: suspended batch row %d of %s (%s)",
            s.req.row_id, victim.job_id,
            "hibernated" if hibernated
            else "%d tokens regenerate" % best_cost,
        )
        return True

    def _evict_for_priority(self, ctx: JobCtx) -> bool:
        """Priority-ladder admission (engine/control.py): when a
        higher-priority BATCH job finds the batch full, suspend one
        decode row of a lower-priority job — the same row-granular
        suspend/re-admit recipe as ``_evict_for_interactive`` (pages
        free, the row re-enters its job's pending queue and
        regenerates). Who outranks whom — including anti-starvation
        aging and the soft-deadline veto — is the ladder's call;
        this method only does the slot mechanics. A ladder error
        disables the ladder, never admission."""
        lad = self.ladder
        if lad is None or ctx.interactive:
            return False
        try:
            if not lad.active():
                return False
        except Exception:  # noqa: BLE001 — as below
            return self._ladder_failed()
        # a victim is chosen among ARMED rows (outside the ladder's
        # backstop: a wave that fails is no policy error)
        self._resolve_wave()
        try:
            now = time.monotonic()
            best: Optional[int] = None
            best_cost = -1
            for i, s in enumerate(self.slots):
                if s is None or s.job is None or s.job.interactive:
                    continue
                if s.job is ctx:
                    continue  # never cannibalize the preemptor itself
                if s.req.constraint is not None and (
                    s.req.constraint_factory is None
                ):
                    continue  # not rebuildable — cannot re-admit
                if not lad.may_preempt(ctx, s.job, now):
                    continue
                cost = len(s.out_ids) + (
                    s.prefill_pos if s.prefilling else 0
                )
                if best is None or cost < best_cost:
                    best, best_cost = i, cost
            if best is None:
                return False
            s = self.slots[best]
            victim = s.job
            hibernated = self._hibernate_slot(best)
            if not hibernated:
                self._drop_slot(best)
                victim.pending.insert(
                    0,
                    dataclasses.replace(
                        s.req,
                        constraint=None,
                        prepped_constraint=None,
                        prep_queued=False,
                    ),
                )
            victim.stats["preempted"] = (
                victim.stats.get("preempted", 0) + 1
            )
            if self._tel_on and victim.trace_id is not None:
                victim.trace_preempted.add(int(s.req.row_id))
                telemetry.TRACES.event(
                    victim.trace_id, "preempt_suspend",
                    {"row_id": int(s.req.row_id), "by": ctx.job_id,
                     "lost_tokens": 0 if hibernated else int(best_cost),
                     "hibernated": bool(hibernated)},
                )
            lad.record(ctx, victim)
            logger.debug(
                "priority ladder: P%d %s suspended row %d of P%d %s (%s)",
                ctx.priority, ctx.job_id, s.req.row_id,
                victim.priority, victim.job_id,
                "hibernated" if hibernated
                else "%d tokens regenerate" % best_cost,
            )
            return True
        except Exception:  # noqa: BLE001 — policy errors must never
            # break admission; the control plane degrades itself on
            # its own sites, this is the scheduler-side backstop
            return self._ladder_failed()

    def _ladder_failed(self) -> bool:
        logger.warning(
            "priority ladder failed — disabling it", exc_info=True
        )
        self.ladder = None
        return False

    def _admit_pending(self, order: List[JobCtx]) -> bool:
        """Admit as many pending rows as slots/pages allow, pulling from
        jobs in (priority, seq) order; rows prefill in batches of up to
        ``prefill_batch_size`` per device dispatch (long rows chunk one
        at a time — see runner.prefill), and one batch may span jobs
        (per-row suffix offsets). Every dispatch joins the admission
        wave; the caller resolves it."""
        admitted = False
        while True:
            batch = []
            reserved_tokens = 0
            reserved_idxs = set()
            while len(batch) < self.ecfg.prefill_batch_size:
                ctx = next(
                    (c for c in order if not c.done and c.pending), None
                )
                if ctx is None:
                    break
                if not ctx.prefix_ready:
                    if not any(s is None for s in self.slots) and not (
                        # a freshly attached latency/priority job must
                        # not wait for natural churn when it outranks a
                        # running row — evict here or the reserve loop's
                        # eviction path below is never reached
                        self._evict_for_interactive(ctx)
                        or self._evict_for_priority(ctx)
                    ):
                        break  # no slot anyway — defer prefix setup
                    # shared-prefix KV: prefill this job's common prefix
                    # once, right when its rows first stand a chance of
                    # admission
                    self.timer.enter("job_start")
                    self._setup_prefix(ctx)
                    self.timer.enter("admit_host")
                    ctx.prefix_ready = True
                req = ctx.pending[-1]
                shared = ctx.prefix.tokens if ctx.prefix else 0
                # "long" is what actually rides the chunked path: the
                # row's OWN suffix (the shared prefix, if any, was
                # prefilled once at job start)
                is_long = (
                    len(req.prompt_ids) - shared
                    > self.ecfg.prefill_chunk
                )
                if (
                    is_long
                    and getattr(self.ecfg, "prefill_piggyback", True)
                    # the chunked paged-prefill program has no ring/
                    # pipeline wrapper (same gate as _setup_prefix and
                    # runner.prefill's start>0 assert) — under sp/pp,
                    # long rows keep the stop-the-world full-sequence
                    # path below
                    and not self._wrapped
                ):
                    if batch:
                        break  # flush the short-row batch first
                    r = self._reserve(
                        req, ctx, reserved=reserved_tokens,
                        exclude=reserved_idxs,
                    )
                    while r is None and (
                        self._evict_for_interactive(ctx)
                        or self._evict_for_priority(ctx)
                    ):
                        r = self._reserve(
                            req, ctx, reserved=reserved_tokens,
                            exclude=reserved_idxs,
                        )
                    if r is None:
                        break
                    ctx.pending.pop()
                    if self._tel_on and ctx.trace_preempted:
                        self._trace_resume(ctx, req)
                    if self._hibernated:
                        hib = self._hibernated.pop(
                            (id(ctx), int(req.row_id)), None
                        )
                        if hib is not None:
                            req2 = self._resume_hibernated(
                                req, ctx, r, hib
                            )
                            if req2 is None:
                                admitted = True
                                continue  # armed in place — no prefill
                            req = req2  # tier miss: admit from scratch
                    try:
                        self._materialize_constraint(req)
                    except Exception as e:  # noqa: BLE001 — row isolation
                        # a row whose FSM won't compile fails ALONE:
                        # roll the reservation back and retry/quarantine
                        self._unreserve(r[0], r[1])
                        self._row_error(ctx, req, e)
                        continue
                    # Sarathi-style: reserve now, prefill ONE chunk per
                    # scheduler iteration (_prefill_tick) so active rows
                    # keep decoding instead of stalling for the whole
                    # multi-chunk prefill
                    self._admit_prefilling(req, ctx, *r)
                    admitted = True
                    continue
                if is_long and batch:
                    break  # flush the short-row batch first
                r = self._reserve(
                    req, ctx, reserved=reserved_tokens,
                    exclude=reserved_idxs,
                )
                while r is None and (
                    self._evict_for_interactive(ctx)
                    or self._evict_for_priority(ctx)
                ):
                    r = self._reserve(
                        req, ctx, reserved=reserved_tokens,
                        exclude=reserved_idxs,
                    )
                if r is None:
                    break
                ctx.pending.pop()
                if self._tel_on and ctx.trace_preempted:
                    self._trace_resume(ctx, req)
                if self._hibernated:
                    hib = self._hibernated.pop(
                        (id(ctx), int(req.row_id)), None
                    )
                    if hib is not None:
                        req2 = self._resume_hibernated(req, ctx, r, hib)
                        if req2 is None:
                            admitted = True
                            continue  # armed in place — no prefill
                        req = req2  # tier miss: admit from scratch
                try:
                    self._materialize_constraint(req)
                except Exception as e:  # noqa: BLE001 — row isolation
                    self._unreserve(r[0], r[1])
                    self._row_error(ctx, req, e)
                    continue
                batch.append((req, ctx) + r)
                reserved_tokens += self._max_total(req)
                reserved_idxs.add(r[0])
                if is_long:
                    break  # long rows prefill alone (chunked path)
            if not batch:
                return admitted
            self._admit_batch(batch)
            admitted = True

    def run_multi(
        self,
        jobs: List[JobCtx],
        *,
        on_job_done: Callable[[JobCtx, str], None],
        poll_new: Optional[Callable[[], Optional[JobCtx]]] = None,
        should_yield: Optional[Callable[[], bool]] = None,
    ) -> str:
        """Drive a multi-job co-batching session to completion.

        Jobs share the decode batch; admission pulls rows across jobs
        in (priority, seq) order; each job's results/progress stream
        through its own callbacks, and ``on_job_done(ctx, outcome)``
        fires the moment a job reaches a terminal outcome ("completed"
        or "cancelled") — other jobs keep running. ``poll_new`` is
        polled every loop iteration so the caller can ATTACH
        newly-submitted same-model jobs mid-session. ``should_yield``
        preempts the WHOLE session (returns "yielded"; non-done jobs'
        slots are dropped for row-granular resume)."""
        live: List[JobCtx] = []
        # the phase cursor (engine/profiling.py): from here to the
        # finally below every instant of this thread belongs to exactly
        # one named phase; each ``enter`` closes the one before it
        tm = self.timer
        tm.begin()
        try:
            for ctx in jobs:
                self._start_job(ctx)
                live.append(ctx)
            # in-flight fused windows (pipelined unconstrained decode):
            # entries are (toks_dev, logps_dev, active, gens, K)
            pipe: List[Any] = []
            while True:
                tm.enter("sched_poll")
                if poll_new is not None:
                    while True:
                        nctx = poll_new()
                        if nctx is None:
                            break
                        self._start_job(nctx)
                        live.append(nctx)
                        tm.enter("sched_poll")
                for ctx in live:
                    if (
                        not ctx.done
                        and ctx.should_cancel
                        and ctx.should_cancel()
                    ):
                        self._finish_job(
                            ctx, "cancelled", on_job_done,
                            emit_cancel=True,
                        )
                if should_yield and should_yield():
                    for ctx in live:
                        if not ctx.done:
                            self._suspend_job(ctx)
                    return "yielded"
                if self._kv_tier is not None:
                    # serving-side idle-session checkpoints: demote the
                    # coldest unpinned store leaves host-ward so a long
                    # think-time session stops holding HBM pages
                    for toks in self._kv_tier.pop_demote_requests():
                        self._demote_store_pages(
                            max(len(toks) // self.ecfg.kv_page_size, 1)
                        )
                ajobs = [c for c in live if not c.done]
                if self._tel_on:
                    # batch-wide spans (prefill/decode/accept) carry the
                    # live job ids; a tuple rebuild per iteration is a
                    # few hundred ns against a multi-ms device window
                    self._tel_jobs = tuple(c.job_id for c in ajobs)
                    self._tel_traces = tuple(
                        c.trace_id
                        for c in ajobs
                        if c.trace_id is not None
                    )
                if not ajobs:
                    break
                order = sorted(
                    ajobs, key=lambda c: (c.priority, c.seq)
                )
                if tm.dozing and any(c.pending for c in order):
                    # a feeder handed a held-open ctx rows (poll_new)
                    tm.wake("admit_host")
                tm.enter("admit_host")
                admitted = self._admit_pending(order)
                self._rows_waiting = any(c.pending for c in order)
                # double-buffered admission: hand the NEXT group's lazy
                # constraint builds to the prep thread now — they
                # overlap the device window dispatched below
                self._prep_pump(order)
                # one chunk of piggybacked prefill per iteration: long
                # admits advance while the decode batch below keeps its
                # cadence (bounded degradation, never a pause)
                self._prefill_tick()
                # the iteration's one wait for its admissions: every
                # prefill above is dispatched, their first tokens come
                # back together. Where the wave's rows can enter the
                # next fused window by the tokens on the device, that
                # wait lies BEHIND the window's dispatch
                # (_pipelined_step), so the device's queue holds the
                # window while the host waits, arms and accepts; else
                # it is here
                held = self._hold_wave()
                while True:
                    tm.enter("emit")
                    active = self._ready_rows(live, on_job_done)
                    if not active:
                        break
                    n_active = len(active)
                    tm.enter("batch_build", active=n_active)
                    batch = self._build_batch(active)
                    plan = self._choose_path(batch.facts, len(pipe))
                    if not held or plan == "pipelined":
                        break
                    # the step these rows' batch takes next wants their
                    # first tokens on the host: resolve, then build anew
                    tm.enter("admit_host")
                    self._resolve_wave()
                    held = False
                if not active:
                    ajobs = [c for c in live if not c.done]
                    if not ajobs:
                        break
                    if not admitted and not any(
                        s is not None for s in self.slots
                    ):
                        # The head row can never fit an EMPTY machine
                        # (prompt+max_new exceeds total KV capacity).
                        # Fail that one row and keep the session going —
                        # one bad row must not fail its whole job. (A
                        # PREFILLING slot means the machine is NOT empty
                        # — the row may fit once it completes.)
                        ctx = next(
                            (c for c in order if not c.done and c.pending),
                            None,
                        )
                        if ctx is not None:
                            req = ctx.pending.pop()
                            msg = (
                                "row cannot fit an empty machine: "
                                f"prompt + max_new_tokens need more KV "
                                "than the engine's total page pool"
                            )
                            if ctx.on_row_event is not None:
                                ctx.on_row_event(
                                    {"event": "row_quarantined",
                                     "row_id": req.row_id, "attempt": 0,
                                     "error": msg}
                                )
                            ctx.stats["rows"] += 1
                            ctx.on_result(
                                GenResult(
                                    row_id=req.row_id,
                                    token_ids=[],
                                    cumulative_logprob=0.0,
                                    finish_reason="error_capacity",
                                    input_tokens=len(req.prompt_ids),
                                    error=msg,
                                )
                            )
                            self._sweep_done(live, on_job_done)
                    for ctx in live:
                        if not ctx.done:
                            self._job_progress(ctx)
                    if self._tel_on:
                        telemetry.SCHED_ITERATIONS_TOTAL.inc(1.0, "idle")
                    if not admitted and not any(
                        s is not None for s in self.slots
                    ) and all(not c.pending for c in live if not c.done):
                        # Only held-open stage-graph ctxs remain and no
                        # feeder can run on THIS thread until poll_new /
                        # cancel checks fire — doze instead of spinning:
                        # ONE sched_idle span, however many spins
                        tm.doze()
                        time.sleep(0.0005)
                    continue
                if plan == "fastforward" and not self._fastforward_step(
                    batch
                ):
                    # the probe disengaged (a failed one stays
                    # ``fsm_plan`` up to here): window or masked step
                    tm.enter("batch_build")
                    plan = self._choose_path(
                        batch.facts, len(pipe), probed=True
                    )
                if plan == "fastforward":
                    path = plan
                elif plan in ("pipelined", "drain"):
                    path = self._pipelined_step(
                        pipe, batch, refill=plan == "pipelined"
                    )
                elif plan == "window":
                    path = self._window_step(batch)
                else:
                    path = self._single_step(batch)
                self._after_step(live, on_job_done, path, n_active)
            return "completed"
        finally:
            tm.wake("sched_other")
            try:
                # a raise out of admission: the rows dispatched before
                # it are armed, as a sync a row always left them (on
                # every other path the wave is empty here)
                self._resolve_wave()
            except Exception:  # noqa: BLE001 — the raise under way wins
                logger.warning("admission wave lost", exc_info=True)
            # every exit path (completed / yielded / raise) returns any
            # live job's shared-prefix pages to the pool (_finish_job
            # and _suspend_job already None the refs they freed) and
            # parks the admission-prep thread
            self._prep_stop()
            for ctx in live:
                if ctx.prefix is not None:
                    self._release_prefix(ctx.prefix)
                    ctx.prefix = None
                if self._hibernated:
                    self._purge_hibernated(ctx)
            if self._hibernated and self._kv_tier is not None:
                # entries of jobs no longer in ``live`` (defensive —
                # purge runs on every terminal transition above)
                self._kv_tier.discard(
                    [h.key for h in self._hibernated.values() if h.key]
                )
                self._hibernated.clear()
            tm.end()
