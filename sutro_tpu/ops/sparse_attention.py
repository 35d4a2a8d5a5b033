"""Learned sparse attention over latent rows: the attention of a latent
layer that has an INDEXER (``ModelConfig.index_topk``; models/
transformer.py ``mla_mixer``).

A query attends to the ``topk`` positions of its row's past (itself
included) that the indexer scores highest,

    I(t, s) = sum_j w_j(t) * relu(q_I_j(t) . k_I(s)),      s <= t,

all of them while there are no more than ``topk``; ties go to the lower
position. The index keys ``k_I`` are one vector a token, cached beside
the latent rows in a pool of their own on the same page table
(engine/kvcache.py). Scores are bf16 products accumulated in float32;
the selection is EXACT (never an approximate top-k): two forms of it
give the same set.

- ``selected_decode`` (ONE query a row over a paged past, every decode
  step): ``I`` over the row's cached index keys (the rows' tables
  gathered for them alone, a fifth of a row's bytes), a fused window's
  pending ones and its own; then one of two bodies, both counted
  ``gathered`` (one query over its selected rows):

  - under ``use_pallas``, where the paged kernel's gate takes the shape
    (``pallas_paged.paged_decode_supported``: pages of half a lane tile
    or more): the selection as a MASK over the candidates in the order
    of their positions (``topk_mask``: no sort, nothing needs positions)
    and ONE call of ``pallas_paged.paged_decode_attention``'s latent
    variant with that mask as one more operand (``keep`` over the row's
    paged positions, ``keep_tail`` over the pending slots and the own
    row: either can fall out of the top ``topk``). The row's pages are
    read where they lie, through the ring the dense step uses; both
    products and the softmax stay in VMEM. It walks every page up to the
    row's last token's, 3.4 times the selected rows at a third selected,
    and skips none: a page of 64 holds no chosen row once in 3e9 at that
    share. Counted ``paged_decode`` ``lowered`` /
    ``interpreted`` under ``select=keep`` (``lowering.
    paged_decode_forms``), which tells it from the dense branch's call
    in the same program;
  - else in XLA, the tests' reference: ``lax.top_k`` (equal scores: the
    lower index first), the chosen latent rows fetched BY POSITION from
    the pool (one row gather ``[B, topk, width]``), a ``where`` against
    the pending rows, the absorbed products over them with the float32
    scores through HBM. Under ``use_pallas`` counted ``paged_decode``
    ``reference`` with the gate's name.
- ``masked_attention`` (a chunk of queries: a prefill with no past in
  the expanded form, a chunk over a paged past in the absorbed form): a
  block of queries at a time, ``I`` against every key the block may see,
  the ``topk``-th largest by bisection over the float32 bits (32 counting
  passes, exact; ``topk_mask``), and the softmax over the kept pairs. A
  masked dense product computes the same numbers as a gather of ``topk``
  keys a query. Counted ``masked``, whichever of two bodies attends:

  - under ``use_pallas``, an EXPANDED chunk with no past whose length a
    block of ``ops/attention._LATENT_FLASH_BLOCKS`` divides: the blocks
    select only, their kept pairs go into ONE ``[B, T, T]`` int8 and one
    ``pallas_flash.flash_prefill(keep=...)`` over the whole chunk runs
    both products and the softmax in VMEM (the float32 scores of every
    head never reach HBM). Counted ``flash_prefill`` ``lowered`` too
    (``lowering.snapshot()``);
  - everything else, in XLA a block at a time: ``use_pallas`` off
    (nothing more counted); under ``use_pallas`` an expanded chunk whose
    length no block divides (``flash_prefill`` ``reference``); the
    absorbed form over a paged past (nothing more counted: no cell runs
    it).

``sparse_latent_attention`` picks between them and the dense paths of
``ops/attention.latent_attention``, which are exact while no row's
context passes ``topk`` (the selection is everything): a chunk with no
past of at most ``topk`` tokens statically, one decode step by a
``lax.cond`` on the rows' lengths, so that the program of a short
dispatch is PR 42's (the paged kernel's latent variant, the flash body)
and a long one's differs from it by the selection alone.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import lowering
from .attention import (
    NEG_INF, latent_attention, latent_flash, latent_flash_block,
)

_F32 = jnp.float32
#: float32 scores a block of queries may hold (``masked_attention``)
_SCORE_BYTES = 0.6e9


class Indexer(NamedTuple):
    """What the indexer hands the attention of a chunk."""

    q: jax.Array        # [B, T, NHi, Di]: the chunk's index queries
    w: jax.Array        # [B, T, NHi] float32: a weight a head
    k: jax.Array        # [B, T, Di]: the chunk's own index keys
    topk: int
    pages: Optional[jax.Array] = None   # [L, NP, PS, Di]: the index pool
    win: Optional[jax.Array] = None     # [B, W, Di]: a fused window's keys


class _Segment(NamedTuple):
    """Keys a block of queries may see: a paged past, a fused window's
    rows, or the chunk's own."""

    keys: jax.Array         # [B, X, W] rows shared by the heads, or [B, X, NH, D]
    values: jax.Array       # the same rows (absorbed), or [B, X, NH, Dv]
    index_keys: jax.Array   # [B, X, Di]
    pos: jax.Array          # [B, X] positions
    valid: jax.Array        # [B, X] bool


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """``I`` [B, t, X] float32 of queries ``q`` [B, t, NHi, Di] (weights
    ``w`` [B, t, NHi]) against ``keys`` [B, X, Di], one a position."""
    s = jnp.einsum("btnd,bxd->btnx", q, keys, preferred_element_type=_F32)
    return jnp.einsum("btnx,btn->btx", jax.nn.relu(s), w.astype(_F32))


def _ordered_bits(scores: jax.Array) -> jax.Array:
    """float32 -> uint32, monotone: a larger score has larger bits."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)  # -0 -> +0
    return jnp.where(
        bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31)
    )


def topk_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """Boolean ``[..., X]``: the ``k`` largest ``scores`` among ``valid``
    along the last axis, equal scores to the lower index; every valid
    one where there are at most ``k``. Exact: the ``k``-th largest value
    is found bit by bit (32 passes that count ``>=``), no sort."""
    if scores.shape[-1] <= k:
        return valid
    u = jnp.where(valid, _ordered_bits(scores.astype(_F32)), jnp.uint32(0))

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    # the largest t with count(u >= t) >= k: the k-th largest (0 when
    # fewer than k are valid: everything valid is above it)
    t = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32)
    )[..., None]
    above = valid & (u > t)
    equal = valid & (u == t)
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)[..., None]

    def tied(_):
        # more positions AT the k-th value than places left: the lower
        # positions take them
        rank = jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
        return above | (equal & (rank <= room))

    def untied(_):
        return above | equal

    n_equal = jnp.sum(equal, axis=-1, dtype=jnp.int32)[..., None]
    return jax.lax.cond(jnp.any(n_equal > room), tied, untied, None)


def _gather_pages(pool: jax.Array, layer, page_table: jax.Array) -> jax.Array:
    """A layer's pages of every row's table, ``[B, MP * PS, width]``: ONE
    index on the major axis of the stacked pool (engine/kvcache.py
    ``gather_pages`` says why)."""
    L, NP, PS, W = pool.shape
    B, MP = page_table.shape
    at = layer * NP + page_table.reshape(-1)
    return pool.reshape(L * NP, PS, W)[at].reshape(B, MP * PS, W)


def selected_decode(
    q: jax.Array,              # [B, 1, NH, W]: the absorbed queries
    row: jax.Array,            # [B, 1, W]: the step's own latent rows
    index: Indexer,
    *, positions, scale: float, pages, layer, page_table, past_len,
    win_rows=None, win_len=None, value_width: int, valid_len=None,
    use_pallas: bool = False, return_selection: bool = False,
):
    """One decode step over the SELECTED rows, ``[B, 1, NH,
    value_width]`` (``valid_len`` is ``latent_attention``'s argument and
    says nothing here: a row's one query is real). Candidates in the
    order of their positions: the paged past (``< past_len``), a fused
    window's pending tokens (``past_len + slot``), the step's own.
    Under ``use_pallas``, where the paged kernel's gate takes the shape,
    the selection is a MASK over them and the kernel attends (the
    module's docstring); else ``lax.top_k``'s positions and the row
    gather, which ``return_selection`` (the tests' view) asks for too:
    it adds ``(positions [B, K], chosen [B, K] bool)``."""
    lowering.record_sparse("gathered")
    B = q.shape[0]
    L, NP, PS, W = pages.shape
    MP = page_table.shape[1]
    CTX = MP * PS
    has_win = win_rows is not None and win_rows.shape[1] > 0
    with jax.named_scope("dsa_indexer"):
        iks = [_gather_pages(index.pages, layer, page_table)]
        pos = [jnp.broadcast_to(jnp.arange(CTX, dtype=jnp.int32)[None], (B, CTX))]
        ok = [pos[0] < past_len[:, None]]
        pend = [row]
        if has_win:
            Wn = win_rows.shape[1]
            slot = jnp.arange(Wn, dtype=jnp.int32)[None]
            iks.append(index.win)
            pos.append(past_len[:, None] + slot)
            ok.append(jnp.broadcast_to(slot < win_len, (B, Wn)))
            pend.insert(0, win_rows)
        iks.append(index.k)
        pos.append(positions)
        ok.append(jnp.ones((B, 1), bool))
        pos, ok = jnp.concatenate(pos, axis=1), jnp.concatenate(ok, axis=1)
        ok = ok & (pos <= positions)
        score = index_scores(index.q, index.w, jnp.concatenate(iks, axis=1))
        score = jnp.where(ok, score[:, 0] + 0.0, -jnp.inf)
    if use_pallas and not return_selection:
        from .pallas_paged import paged_decode_attention, paged_decode_supported

        if paged_decode_supported(q[:, 0], pages, selection_pages=MP):
            with jax.named_scope("dsa_select"):
                keep = topk_mask(score, ok, index.topk)
            with jax.named_scope("dsa_attend"):
                win = dict(win_k=win_rows, win_len=win_len) if has_win else {}
                out = paged_decode_attention(
                    q[:, 0], pages, None, layer, page_table, past_len,
                    row, None, jnp.asarray(0, jnp.int32), scale=scale,
                    keep=keep[:, :CTX], keep_tail=keep[:, CTX:], **win,
                )
            return out[:, None, :, :value_width]
        lowering.record_reference(
            "paged_decode", q.shape[2],
            f"paged_decode_supported: a selection over pages of {PS}, "
            f"rows {W} wide",
        )
    with jax.named_scope("dsa_select"):
        K = min(index.topk, score.shape[1])
        top, at = jax.lax.top_k(score, K)            # ties: the lower index
        chosen = top > -jnp.inf
    with jax.named_scope("dsa_attend"):
        in_pool = at < CTX
        p_at = jnp.minimum(at, CTX - 1)
        page = jnp.take_along_axis(page_table, p_at // PS, axis=1)
        flat = (layer * NP + page) * PS + p_at % PS                # [B, K]
        rows = pages.reshape(L * NP * PS, W)[flat]                 # [B, K, W]
        pend = pend[0] if len(pend) == 1 else jnp.concatenate(pend, axis=1)
        if pend.shape[1] == 1:
            held = jnp.broadcast_to(pend, rows.shape)
        else:
            held = jnp.take_along_axis(
                pend, jnp.clip(at - CTX, 0, pend.shape[1] - 1)[..., None],
                axis=1,
            )
        rows = jnp.where(in_pool[..., None], rows, held.astype(rows.dtype))
        sc = jnp.einsum(
            "bnc,bkc->bnk", q[:, 0], rows, preferred_element_type=_F32
        )
        sc = jnp.where(chosen[:, None], sc * scale, NEG_INF)
        p = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
        out = jnp.einsum(
            "bnk,bkc->bnc", p.astype(rows.dtype), rows,
            preferred_element_type=_F32,
        ) / jnp.sum(p, axis=-1)[..., None]
        out = out[:, None, :, :value_width].astype(q.dtype)
    if return_selection:
        return out, (jnp.take_along_axis(pos, at, axis=1), chosen)
    return out


def _block_q(T: int, lanes: int) -> int:
    """Queries a block: the largest power of two that divides ``T`` and
    keeps ``lanes`` (batch x heads x keys) float32 scores a query under
    ``_SCORE_BYTES``; all of a short chunk."""
    limit = max(int(_SCORE_BYTES // (4 * max(lanes, 1))), 8)
    if T <= limit:
        return T
    return math.gcd(T, 1 << (limit.bit_length() - 1))


def masked_attention(
    q: jax.Array,                 # [B, T, NH, Dq]
    k: jax.Array,                 # expanded [B, T, NH, Dq]; absorbed: the
    #                               chunk's own ROWS [B, T, Dq]
    v: Optional[jax.Array],       # expanded [B, T, NH, Dv]; absorbed: None
    index: Indexer,
    *, positions, valid_len, scale: float,
    pages=None, layer=None, page_table=None, past_len=None,
    win_rows=None, win_len=None, value_width: Optional[int] = None,
    use_pallas: bool = False, block_q: Optional[int] = None,
    return_mask: bool = False,
):
    """``latent_attention``'s numbers with the softmax over the selected
    pairs alone: a block of queries at a time, ``I`` against every key
    segment (the paged past, a fused window's rows, the chunk's own up
    to the block's group), ``topk_mask`` over their concatenation in the
    order of the positions, one softmax over the kept pairs. The blocks
    of a GROUP (an eighth of the chunk) see the chunk's own keys up to
    the group's end and run under ``lax.map`` (one traced body a group,
    its temporaries reused); a later group sees more keys, which skips
    most of the causal square's upper half. Under ``use_pallas`` an
    EXPANDED chunk with no past whose shape the flash body takes has
    its blocks select only: their kept pairs are laid into ``[B, T, T]``
    int8 and ONE ``flash_prefill`` over the whole chunk runs both
    products and the softmax under that mask (the module's docstring).
    ``return_mask`` adds the kept pairs over the chunk's OWN keys
    ``[B, T, T]`` (the tests')."""
    lowering.record_sparse("masked")
    B, T, NH = q.shape[:3]
    absorbed = v is None
    # the side of the flash body's blocks where it takes the attention
    flash = None
    if use_pallas and not absorbed and pages is None:
        flash = latent_flash_block(q)
    segs = []
    if pages is not None:
        past = _gather_pages(pages, layer, page_table)
        CTX = past.shape[1]
        pos = jnp.broadcast_to(jnp.arange(CTX, dtype=jnp.int32)[None], (B, CTX))
        segs.append(_Segment(
            past, past, _gather_pages(index.pages, layer, page_table),
            pos, pos < past_len[:, None],
        ))
        if win_rows is not None and win_rows.shape[1] > 0:
            slot = jnp.arange(win_rows.shape[1], dtype=jnp.int32)[None]
            segs.append(_Segment(
                win_rows, win_rows, index.win, past_len[:, None] + slot,
                jnp.broadcast_to(slot < win_len, (B, win_rows.shape[1])),
            ))
    own_valid = jnp.arange(T, dtype=jnp.int32)[None] < valid_len[:, None]
    before = sum(s.keys.shape[1] for s in segs)
    bq = block_q or _block_q(T, B * NH * (before + T))
    nb = T // bq
    groups = math.gcd(nb, 8)
    per = nb // groups              # blocks a group

    def block(own, qp, qi, wi, qb=None):
        """One block of queries against ``segs`` and the chunk's ``own``
        keys: (out [B, t, NH, D], kept own pairs [B, t, X_own]); no out
        and the pairs as int8 where the flash body attends."""
        every = segs + [own]
        with jax.named_scope("dsa_indexer"):
            score = jnp.concatenate(
                [index_scores(qi, wi, s.index_keys) for s in every], axis=-1
            )
            ok = jnp.concatenate([
                (s.pos[:, None, :] <= qp[:, :, None]) & s.valid[:, None, :]
                for s in every
            ], axis=-1)
        with jax.named_scope("dsa_select"):
            keep = topk_mask(score, ok, index.topk)
            if flash:
                return None, keep.astype(jnp.int8)
        with jax.named_scope("dsa_attend"):
            scores, at = [], 0
            for seg in every:
                keys, X = seg.keys, seg.keys.shape[1]
                form = "btnc,bxc->bntx" if keys.ndim == 3 else "btnd,bxnd->bntx"
                sc = jnp.einsum(form, qb, keys, preferred_element_type=_F32)
                scores.append(jnp.where(
                    keep[:, None, :, at:at + X], sc * scale, NEG_INF
                ))
                at += X
            m = scores[0].max(axis=-1)
            for sc in scores[1:]:
                m = jnp.maximum(m, sc.max(axis=-1))
            denom = acc = None
            for sc, seg in zip(scores, every):
                vals = seg.values
                p = jnp.exp(sc - m[..., None])
                form = "bntx,bxc->btnc" if vals.ndim == 3 else "bntx,bxnd->btnd"
                o = jnp.einsum(
                    form, p.astype(vals.dtype), vals,
                    preferred_element_type=_F32,
                )
                d = jnp.sum(p, axis=-1)
                denom = d if denom is None else denom + d
                acc = o if acc is None else acc + o
            out = acc / jnp.moveaxis(denom, 1, 2)[..., None]
            if absorbed:
                out = out[..., :value_width]
        return out.astype(q.dtype), keep[..., before:]

    outs, kept = [], []
    for g in range(groups):
        g0, g1 = g * per * bq, (g + 1) * per * bq
        own = _Segment(
            k[:, :g1], k[:, :g1] if absorbed else v[:, :g1],
            index.k[:, :g1], positions[:, :g1], own_valid[:, :g1],
        )
        args = (positions[:, g0:g1], index.q[:, g0:g1], index.w[:, g0:g1])
        if not flash:
            args += (q[:, g0:g1],)
        if per == 1:
            o, kp = block(own, *args)
        else:
            # [B, per * bq, ...] -> [per, B, bq, ...]: a block a step
            split = [
                jnp.moveaxis(a.reshape((B, per, bq) + a.shape[2:]), 1, 0)
                for a in args
            ]
            o, kp = jax.tree.map(
                lambda a: jnp.moveaxis(a, 0, 1).reshape(
                    (B, per * bq) + a.shape[3:]),
                jax.lax.map(lambda xs: block(own, *xs), tuple(split)),
            )
        outs.append(o)
        if flash or return_mask:
            kept.append(jnp.pad(kp, ((0, 0), (0, 0), (0, T - g1))))
    if kept:
        kept = kept[0] if groups == 1 else jnp.concatenate(kept, axis=1)
    if flash:
        with jax.named_scope("dsa_attend"):
            out = latent_flash(
                q, k, v, scale=scale, block=flash, keep=kept
            )
    else:
        out = outs[0] if groups == 1 else jnp.concatenate(outs, axis=1)
    if return_mask:
        return out, kept.astype(bool)
    return out


def sparse_latent_attention(
    q, k, v, index: Indexer, *, positions, valid_len, scale: float,
    pages=None, layer=None, page_table=None, past_len=None,
    win_rows=None, win_len=None, value_width=None, use_pallas: bool = False,
):
    """``latent_attention`` under the indexer's selection (the module's
    docstring): the same arguments and result."""
    B, T = q.shape[:2]
    dense = dict(
        positions=positions, valid_len=valid_len, scale=scale, pages=pages,
        layer=layer, page_table=page_table, past_len=past_len,
        win_rows=win_rows, win_len=win_len, value_width=value_width,
    )
    if pages is None:
        if T <= index.topk:
            # no query has more than topk keys: the selection is everything
            return latent_attention(q, k, v, use_pallas=use_pallas, **dense)
        return masked_attention(
            q, k, v, index, use_pallas=use_pallas, **dense
        )
    if T > 1:
        return masked_attention(q, k, v, index, **dense)
    if page_table.shape[1] * pages.shape[2] + (
        0 if win_rows is None else win_rows.shape[1]
    ) + 1 <= index.topk:
        return latent_attention(q, k, v, use_pallas=use_pallas, **dense)
    pending = 0 if win_len is None else win_len
    return jax.lax.cond(
        jnp.max(past_len) + pending + 1 > index.topk,
        lambda: selected_decode(q, k, index, use_pallas=use_pallas, **dense),
        lambda: latent_attention(q, k, None, use_pallas=use_pallas, **dense),
    )
