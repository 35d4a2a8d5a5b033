"""Token sampling with optional constrained-decoding masks.

Implements the ``sampling_params`` surface the reference forwards to its
service (temperature / top_p / top_k; /root/reference/sutro/sdk.py:202-216
payload) plus the logit-mask hook used by schema-constrained decoding
(engine/constrain/): a boolean ``allowed`` mask computed host-side from the
token FSM is applied before sampling, guaranteeing schema-valid JSON.
``unpack_mask`` turns the bit-packed form the masks travel in back into it.

Everything here is the ``sample`` part of a step (``lowering.PARTS``).

Everything is jit-safe and static-shape, and one executable serves greedy
and drawing rows alike: per ROW, a ``where`` on the temperature picks the
argmax or the draw. ``sample`` also holds three ``lax.cond``s. Each
predicate is over the WHOLE batch, is read on the device from the
function's own operands, and guards work whose result no row of such a
batch reads, so every branch returns the ids the straight-line code would:

- ``all(temperature <= 0)``: every row is greedy and the batch is sampled
  by its argmax alone (no top-k head, no cumulative sum, no draw).
  Otherwise the whole stochastic path runs, for every row. The scaled
  logits and their logsumexp are made BEFORE this cond, for both sides
  (``sample`` says why).
- inside that path, ``any(0 < top_k <= 32)`` (plain rows; a constrained
  batch takes the exact head statically): the exact ``lax.top_k`` head
  where a small top-k would feel ``approx_max_k``'s recall, else the
  approximate one.
- inside that path, ``all(filtered | greedy)`` (batches without
  ``row_seeds``): the float32 full-vocabulary categorical runs only where
  some drawing row has both filters disabled.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .lowering import part

NEG_INF = -1e30
# widest nucleus/top-k head considered for sampling (see sample())
NUCLEUS_CAP = 256


@part("sample")
def unpack_mask(packed: jax.Array, vocab_size: int) -> jax.Array:
    """[B, V] bool from ``np.packbits(mask, axis=1)``'s [B, ceil(V / 8)]
    uint8: FSM masks and penalty seen-bits travel host->device bit-packed
    (8x less transfer on the per-step critical path of constrained
    decoding)."""
    return jnp.unpackbits(packed, axis=1, count=vocab_size).astype(bool)


@part("sample")
def apply_penalties(
    logits: jax.Array,      # [B, V] float32 (raw, pre-temperature)
    seen_rep: jax.Array,    # [B, V] bool — repetition scope: PROMPT +
    #                         generated tokens (vLLM/HF semantics)
    pen_ids: jax.Array,     # [B, K] int32, -1 padded — distinct GENERATED ids
    pen_cnt: jax.Array,     # [B, K] float32 — their counts
    presence: jax.Array,    # [B] float32; 0 disables
    frequency: jax.Array,   # [B] float32; 0 disables
    repetition: jax.Array,  # [B] float32; 1 disables
) -> jax.Array:
    """Sampling penalties applied to raw logits before temperature:
    repetition divides positive / multiplies negative logits of tokens
    in ``seen_rep`` (prompt + output); presence subtracts a flat bias
    and frequency a count-proportional bias from GENERATED tokens only
    (both derived on-device from the sparse [B, K] id/count list —
    outputs rarely exceed K distinct ids; overflow ids keep the
    repetition penalty via ``seen_rep`` but lose presence/frequency).

    Dtype-preserving: every [B, V] expression stays in ``logits.dtype``
    (the count scatter accumulates in f32, then the bias casts back),
    so bf16 logits keep their bandwidth saving through this path."""
    B, V = logits.shape
    dt = logits.dtype
    rep = repetition[:, None].astype(dt)
    rep_l = jnp.where(
        logits > 0, logits / rep, logits * rep
    )
    logits = jnp.where(seen_rep, rep_l, logits)
    ids = jnp.clip(pen_ids, 0, V - 1)
    counts = jnp.zeros((B, V), jnp.float32).at[
        jnp.arange(B)[:, None], ids
    ].add(jnp.where(pen_ids >= 0, pen_cnt, 0.0))
    logits = logits - (presence[:, None] * (counts > 0)).astype(dt)
    return logits - (frequency[:, None] * counts).astype(dt)


@part("sample")
def sample(
    logits: jax.Array,                  # [B, V] float32 OR bfloat16
    key: jax.Array,
    *,
    temperature: jax.Array,             # scalar or [B]
    top_p: jax.Array,                   # scalar or [B]; 1.0 disables
    top_k: jax.Array = 0,               # scalar or [B] int32; 0 disables
    allowed: Optional[jax.Array] = None,  # [B, V] bool — constrained decoding
    row_seeds: Optional[jax.Array] = None,  # [B] int32 — per-row derived keys
) -> jax.Array:
    """Returns sampled token ids [B].

    ``row_seeds`` implements the reference's ``random_seed_per_input``
    (sdk.py payload): each row samples with a key folded from its own seed
    (gumbel-max, equivalent to categorical), so a row's output stream is
    reproducible independent of batch composition.

    bfloat16 logits are supported: the wide [B, V] scans (top-k head,
    greedy argmax, logsumexp input) stay in the input dtype while every
    accumulation and the small [B, K] head math upcast to float32 — the
    converts fuse into the reduction loops. Two deliberate exceptions
    pay a full f32 pass
    for unbiased gumbel noise: the unfiltered full-vocab categorical
    (rare: top_k=0 AND top_p>=1) and the row-seeded full-vocab draw —
    bf16 gumbel over 150k near-ties would resolve quantized ties toward
    low token ids."""
    B, V = logits.shape
    if allowed is not None:
        logits = jnp.where(allowed, logits, jnp.asarray(NEG_INF, logits.dtype))

    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))

    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None].astype(
        logits.dtype
    )
    # f32 accumulation regardless of input dtype (a bf16 accumulator
    # over 150k terms drifts); the convert fuses into the reduction
    lse = jax.scipy.special.logsumexp(
        scaled.astype(jnp.float32), axis=-1, keepdims=True
    )

    # A batch whose rows are all greedy reads nothing of the head, its
    # probabilities or the draw: the device takes the argmax side alone
    # (a masked step of 64 constrained rows over a 152k vocabulary paid
    # 7.4 ms for the exact head; PERF.md section 6, PR 53). ``scaled`` and
    # ``lse`` stay OUTSIDE the cond on purpose: here XLA writes the scaled
    # copy the head's custom call needs from the same pass that sums the
    # exponentials, reading the vocabulary product as the model's head
    # left it; inside a branch it reads the float32 logits once a
    # reduction and writes ``scaled`` in a pass of its own (+0.2 ms a step
    # of the 4B generate cell, same section). A greedy batch pays that
    # one fused pass (~0.1 ms) and the argmax.
    return jax.lax.cond(
        jnp.all(temperature <= 0.0),
        # of the SCALED logits, as ``greedy_tok`` below: the divide can
        # round two neighbouring values into one, and the tie goes to the
        # lower id on both sides
        lambda: jnp.argmax(scaled, axis=-1).astype(jnp.int32),
        lambda: _drawn(
            scaled, lse, key, temperature, top_p, top_k,
            exact_head=allowed is not None, row_seeds=row_seeds,
        ),
    )


def _drawn(
    scaled, lse, key, temperature, top_p, top_k, *, exact_head, row_seeds
):
    """``sample`` for a batch in which some row draws: [B] ids from the
    masked, temperature-scaled ``scaled`` [B, V] and its float32
    logsumexp ``lse`` [B, 1]; greedy rows among them still take their
    argmax."""
    B, V = scaled.shape
    # A full [B, V] argsort is pathologically slow on TPU (sorting networks
    # over 150k lanes). Filtered rows instead use the top NUCLEUS_CAP
    # logits — the nucleus/top-k filters only ever *keep* a head of the
    # distribution — normalized against the exact full-vocab logsumexp, so
    # probabilities are exact. Rows with filtering disabled (top_k==0 and
    # top_p>=1) sample the FULL vocabulary via gumbel-argmax
    # (== categorical, no sort), honoring the "0 disables" contract.
    # Remaining approximations: top_k above the cap clamps to the cap-wide
    # head; a *nucleus* wider than NUCLEUS_CAP tokens (near-uniform
    # distributions with top_p<1) truncates to the cap.
    K = min(NUCLEUS_CAP, V)
    # approx_max_k is ~3x faster than exact top_k on TPU for 150k vocabs;
    # the head feeds *stochastic* nucleus sampling, where a ~2% recall
    # miss in the tail of the head is statistically invisible. Greedy
    # stays exact via a separate argmax (determinism contract). Two cases
    # need the EXACT head: (a) FSM-constrained rows, whose allowed set
    # may be smaller than the approx recall can resolve, and (b) small
    # top_k (a ~5%/element miss inside a 2-wide head is a visible
    # distribution change). (a) is static; (b) is a runtime cond so the
    # common unconstrained/top_p path keeps the fast kernel.
    def _exact():
        return jax.lax.top_k(scaled, K)

    def _approx():
        return jax.lax.approx_max_k(
            scaled, K, recall_target=0.95, aggregate_to_topk=True
        )

    if exact_head:
        top_vals, top_idx = _exact()
    else:
        top_vals, top_idx = jax.lax.cond(
            jnp.any((top_k > 0) & (top_k <= 32)), _exact, _approx
        )
    greedy_tok = jnp.argmax(scaled, axis=-1).astype(jnp.int32)

    top_vals = top_vals.astype(jnp.float32)           # [B, K] — tiny
    probs = jnp.exp(top_vals - lse)                   # exact probabilities

    ranks = jnp.arange(K, dtype=jnp.int32)[None, :]
    k_active = top_k > 0
    # top_k beyond the cap is clamped to the cap-wide head (closest
    # realizable restriction), never silently disabled
    k_eff = jnp.where(k_active, jnp.minimum(top_k, K), K)[:, None]
    keep_k = ranks < k_eff
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < top_p[:, None]           # always keeps rank-0
    vals = jnp.where(keep_k & keep_p, top_vals, NEG_INF)

    filtered = k_active | (top_p < 1.0)

    if row_seeds is not None:
        keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(row_seeds)
        g_head = jax.vmap(
            lambda k, lg: jax.random.gumbel(k, lg.shape, jnp.float32)
        )(keys, vals)
        choice = jnp.argmax(vals + g_head, axis=-1)
        g_full = jax.vmap(
            lambda k, lg: jax.random.gumbel(
                jax.random.fold_in(k, 1), lg.shape, jnp.float32
            )
        )(keys, scaled)
        full_tok = jnp.argmax(scaled + g_full, axis=-1)
    else:
        choice = jax.random.categorical(key, vals, axis=-1)
        # the full-vocab draw only matters for rows with filtering
        # disabled — skip the [B, V] gumbel pass when every row filters
        full_tok = jax.lax.cond(
            jnp.all(filtered | (temperature <= 0.0)),
            lambda: jnp.zeros((B,), jnp.int32),
            # f32 ALWAYS: categorical draws gumbel in the logits dtype,
            # and bf16 gumbel over 150k near-ties quantizes into mass
            # exact ties resolved toward low token ids (biased). This
            # rare branch (filters disabled) pays the f32 pass for
            # unbiasedness.
            lambda: jax.random.categorical(
                jax.random.fold_in(key, 1),
                scaled.astype(jnp.float32),
                axis=-1,
            ).astype(jnp.int32),
        )
    head_tok = jnp.take_along_axis(top_idx, choice[:, None], axis=1)[:, 0]
    sampled = jnp.where(filtered, head_tok, full_tok)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled).astype(jnp.int32)


@part("sample")
def cumulative_logprob(
    logits: jax.Array, token: jax.Array
) -> jax.Array:
    """Per-step logprob of the chosen token (for ``include_cumulative_logprobs``,
    reference sdk.py:1138-1151). Gather-then-logsumexp so the full [B, V]
    log_softmax is never materialized."""
    chosen = jnp.take_along_axis(logits, token[:, None], axis=-1)[
        :, 0
    ].astype(jnp.float32)
    return chosen - jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1
    )


# ---------------------------------------------------------------------------
# Generation by blocks (``ModelConfig.block_length`` > 1)
# ---------------------------------------------------------------------------


@part("sample")
def sample_with_confidence(
    logits: jax.Array,                  # [N, V] float32: a row a POSITION
    key: jax.Array,
    *,
    temperature: jax.Array,             # [N]
    top_p: jax.Array,                   # [N]
    top_k: jax.Array,                   # [N] int32
    exclude: int,                       # an id no position may draw
):
    """``(token [N], confidence [N], logprob [N])`` of a denoising
    forward: ``sample``'s draw with the id ``exclude`` (the mask token)
    at minus infinity, the probability of the drawn token under the
    distribution it was drawn from (after temperature, top-k and top-p;
    a greedy position takes its argmax, and its probability under the
    softmax at temperature 1, so that the confidence rules order a
    greedy row's positions too), and its log-probability under the
    unscaled softmax (what ``cumulative_logprob`` reports on every
    path).

    ``sample``'s rules, written out here because the confidence of a
    filtered draw is read from the SAME head the draw was made from: a
    second head for it (an exact top-256 over ``[512, 151,936]``) took
    59 ms of a 71 ms denoising forward on a v5e (PERF.md section 6,
    PR 57). Passes over ``[N, V]`` (N = rows x block: 311 MB in float32
    at the benchmark's batch): the column write of ``exclude`` (in
    place); ONE read that makes the scaled copy and sums the
    exponentials at the row's temperature and at temperature 1; the
    argmax; and, under ``cond``s that only a batch with such a row
    takes, the approximate top-256 head (a filtered, drawing row) and
    the full-vocabulary float32 draw (a drawing row with both filters
    off). A batch whose rows are all greedy takes the argmax alone."""
    N, V = logits.shape
    with jax.named_scope("bd_confidence"):
        logits = logits.astype(jnp.float32).at[:, exclude].set(NEG_INF)
        greedy = temperature <= 0.0
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        lse = jax.scipy.special.logsumexp(scaled, axis=-1)
        lse_raw = jax.scipy.special.logsumexp(logits, axis=-1)
    greedy_tok = jnp.argmax(scaled, axis=-1).astype(jnp.int32)
    filtered = ((top_k > 0) | (top_p < 1.0)) & ~greedy
    plain = ~filtered & ~greedy
    K = min(NUCLEUS_CAP, V)

    def from_head():
        # ``_drawn``'s head: exact where a small top_k would feel the
        # approximate one's recall
        vals, idx = jax.lax.cond(
            jnp.any((top_k > 0) & (top_k <= 32)),
            lambda: jax.lax.top_k(scaled, K),
            lambda: jax.lax.approx_max_k(
                scaled, K, recall_target=0.95, aggregate_to_topk=True
            ),
        )
        probs = jnp.exp(vals - lse[:, None])
        ranks = jnp.arange(K, dtype=jnp.int32)[None]
        k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, K), K)[:, None]
        cum = jnp.cumsum(probs, axis=-1)
        keep = (ranks < k_eff) & ((cum - probs) < top_p[:, None])
        choice = jax.random.categorical(
            key, jnp.where(keep, vals, NEG_INF), axis=-1
        )
        tok = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
        p = jnp.take_along_axis(probs, choice[:, None], axis=1)[:, 0]
        return tok.astype(jnp.int32), p / jnp.maximum(
            jnp.sum(jnp.where(keep, probs, 0.0), axis=-1), 1e-30
        )

    def from_all():
        tok = jax.random.categorical(
            jax.random.fold_in(key, 1), scaled, axis=-1
        ).astype(jnp.int32)
        at = jnp.take_along_axis(scaled, tok[:, None], axis=-1)[:, 0]
        return tok, jnp.exp(at - lse)

    nothing = lambda: (jnp.zeros((N,), jnp.int32), jnp.ones((N,), jnp.float32))
    head_tok, head_p = jax.lax.cond(jnp.any(filtered), from_head, nothing)
    full_tok, full_p = jax.lax.cond(jnp.any(plain), from_all, nothing)
    with jax.named_scope("bd_confidence"):
        tok = jnp.where(
            greedy, greedy_tok, jnp.where(filtered, head_tok, full_tok)
        )
        logp = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0] - lse_raw
        conf = jnp.where(
            greedy, jnp.exp(logp), jnp.where(filtered, head_p, full_p)
        )
    return tok, jnp.minimum(conf, 1.0), logp


#: ``transfer``'s rules by number (``models.configs.REMASKING``)
STATIC, DYNAMIC, SEQUENTIAL = 0, 1, 2


@part("sample")
def transfer(
    x: jax.Array,        # [B, Bk] int32: the block, ``mask_id`` where open
    x0: jax.Array,       # [B, Bk] int32: a forward's draw, every position
    conf: jax.Array,     # [B, Bk] float32: its confidence
    n: jax.Array,        # [B] int32: positions this forward should fill
    rule: jax.Array,     # [B] int32: STATIC | DYNAMIC | SEQUENTIAL
    tau: jax.Array,      # [B] float32: DYNAMIC's threshold
    mask_id: int,
):
    """``(x, taken)``: the block after a denoising forward's transfer and
    the positions it filled, open positions only. STATIC: the ``n`` open
    positions of largest confidence (ties to the left). DYNAMIC: every
    open position whose confidence is over ``tau`` where there are at
    least ``n`` of them, else STATIC's. SEQUENTIAL: the leftmost ``n``
    open positions. ``n`` is at most the open positions left. A masked
    top-n over ``Bk`` lanes a row: ranks by an all-pairs compare, no
    sort."""
    with jax.named_scope("bd_transfer"):
        Bk = x.shape[1]
        is_open = x == mask_id
        n = jnp.minimum(n, jnp.sum(is_open, axis=1))[:, None]
        c = jnp.where(is_open, conf, -1.0)
        lane = jnp.arange(Bk, dtype=jnp.int32)
        # rank of lane i: the lanes that beat it (larger, or equal and
        # further left)
        beats = (c[:, None, :] > c[:, :, None]) | (
            (c[:, None, :] == c[:, :, None])
            & (lane[None, None, :] < lane[None, :, None])
        )
        top = is_open & (jnp.sum(beats, axis=2) < n)
        over = is_open & (conf > tau[:, None])
        enough = jnp.sum(over, axis=1, keepdims=True) >= n
        left = is_open & (jnp.cumsum(is_open, axis=1) <= n)
        rule = rule[:, None]
        taken = jnp.where(
            rule == SEQUENTIAL, left,
            jnp.where((rule == DYNAMIC) & enough, over, top),
        )
        return jnp.where(taken, x0, x), taken
