"""SDAR-MoE (``model_type`` ``sdar_moe``): Qwen3-MoE's decoder under a mask
that is causal BY BLOCKS, generated by diffusion over a block. Plain
``jax.numpy`` in float32, no cache, no kernels, no batching.

Written from the equations of ``sdar_moe.md`` (the published config's
keys, the SDAR paper arXiv:2510.06303 and the family's published
sampler), independent of ``sutro_tpu``: nothing of it is imported. The
layer is Qwen3-MoE's, so the pieces that take every value from the
configuration come from ``qwen3_dense.py`` (RMSNorm, the rotary
embedding, the embedding, the head) and ``qwen3_moe.py`` (the router and
the experts, the near-tie count); neither file is edited. What is
written here is what is new:

- **the mask**, an explicit ``[T, T]`` boolean from ``i // Bk`` and
  ``j // Bk``: position ``i`` sees position ``j`` iff ``j // Bk <= i //
  Bk``: every earlier block and the WHOLE of its own, later positions of
  it too. It holds in every forward;
- **no shift**: the logits at position ``i`` are the distribution of the
  token AT ``i``. ``logits_at(ids, positions)`` therefore means the same
  as everywhere (the full forward's logits at those positions), and the
  harness compares position for position;
- **a denoising forward is a full forward**: a block sees nothing after
  it, so ``logits_at`` over ids whose LAST block holds ``mask_token_id``s
  is the forward the generation runs on that block. No second entry
  point;
- ``transfer`` and ``generate``: the three rules and the loop over blocks
  and steps, as plain Python over numpy, each forward the whole sequence
  again. For the tests; the harness calls neither.

Weights arrive in the layout the system serves a model that lists its
layers by kind in: ``layers["attn"]`` (``attn_norm``, ``wq``, ``wk``,
``wv``, ``wo``, ``q_norm``, ``k_norm``, stacked over the layers) and
``layers["moe"]`` (``mlp_norm``, ``router`` [L, H, E], ``we_gate`` /
``we_up`` [L, E, H, F], ``we_down`` [L, E, F, H]); a one-stack layout
(``layers`` holding all of them) is read the same way.

What it refuses rather than guesses: what ``qwen3_moe.moe_dims_of``
refuses, a ``sliding_window`` in use, a rope scaling, an attention bias,
tied embeddings, and a configuration that does not state
``block_length`` and ``mask_token_id`` (the published file has neither:
the configuration's ``assumed`` says where they come from).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .qwen3_dense import F32, _embed, _head, _rms, _rope, layer_weight
from .qwen3_moe import TIE_MARGIN, moe_dims_of, routed_mlp

ROUTED = True
QUERY_BLOCK = 512
RULES = ("low_confidence_static", "low_confidence_dynamic", "sequential")
#: ``control_logits``: this reference computing ANOTHER model, each a way
#: the mechanism could be built wrong, which the system has to fail
CONTROLS = ("causal_prefill", "causal_block", "stale_commit", "shifted")

__all__ = [
    "ROUTED", "TIE_MARGIN", "RULES", "dims_of", "block_mask", "logits_at",
    "logits_and_near_ties", "transfer", "generate", "CONTROLS",
    "control_logits",
]


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    dims = moe_dims_of(cfg)
    if cfg.get("use_sliding_window") or cfg.get("attention_bias"):
        raise ValueError("sdar_moe: a sliding window or an attention bias is not described here")
    if cfg.get("rope_scaling"):
        raise ValueError("sdar_moe: rope_scaling is not described here")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("sdar_moe: tied embeddings are not described here")
    for key in ("block_length", "mask_token_id"):
        if key not in cfg:
            raise ValueError(f"sdar_moe: the configuration must state {key}")
    dims.update(block=int(cfg["block_length"]), mask=int(cfg["mask_token_id"]))
    if dims["block"] < 1:
        raise ValueError("sdar_moe: block_length under 1")
    return dims


def block_mask(T: int, block: int, lo: int = 0, hi: Optional[int] = None):
    """``[hi - lo, T]`` boolean: row ``i`` (a query at position ``lo +
    i``) sees column ``j`` iff ``j // block <= (lo + i) // block``."""
    i = jnp.arange(lo, T if hi is None else hi)[:, None]
    j = jnp.arange(T)[None, :]
    return j // block <= i // block


def attention(dims: Dict[str, Any], w, h, positions,
              causal: Optional[Tuple[int, int]] = None):
    """``h + Attn(RMSNorm(h)) Wo`` over a whole sequence [T, H] under
    the block mask (``causal`` = (lo, hi): the queries at positions
    ``lo <= i < hi`` under the causal mask in its place, a control)."""
    NH, KVH, Dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    eps, theta = dims["eps"], dims["theta"]
    T = h.shape[0]
    x = _rms(h, w("attn_norm"), eps)
    q = (x @ w("wq")).reshape(T, NH, Dh)
    k = (x @ w("wk")).reshape(T, KVH, Dh)
    v = (x @ w("wv")).reshape(T, KVH, Dh)
    q = _rope(_rms(q, w("q_norm"), eps), positions, theta)
    k = _rope(_rms(k, w("k_norm"), eps), positions, theta)
    group = NH // KVH
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, T)
        scores = jnp.einsum("tnd,snd->nts", q[lo:hi], k) / jnp.sqrt(F32(Dh))
        seen = block_mask(T, dims["block"], lo, hi)
        if causal is not None:
            i = jnp.arange(lo, hi)[:, None]
            seen = jnp.where(
                (i >= causal[0]) & (i < causal[1]),
                block_mask(T, 1, lo, hi), seen,
            )
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(
            jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
        )
    attn = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    return h + attn.reshape(T, NH * Dh) @ w("wo")


def _stacks(layers: Dict[str, Any]):
    """(attention tensors, routed tensors) of either layout."""
    return layers.get("attn", layers), layers.get("moe", layers)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_jit(frozen_dims, causal, layers, index, h, positions):
    """One block over a whole sequence: (h [T, H], gap [T])."""
    dims = dict(frozen_dims)
    attn, moe = _stacks(layers)
    h = attention(dims, layer_weight(attn, index), h, positions, causal)
    x = _rms(h, layer_weight(moe, index)("mlp_norm"), dims["eps"])
    y, gap = routed_mlp(dims, moe, index, x)
    return h + y, gap


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int], causal: Optional[Tuple[int, int]] = None,
):
    """Full forward of ``ids`` ([T] ints) under the block mask: float32
    logits ``[len(score_positions), V]`` AT those positions (no shift)
    and, per scored position, the number of layers whose routing there
    was a near tie."""
    dims = dims_of(cfg)
    frozen = tuple(sorted(dims.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for index in range(dims["layers"]):
            h, gap = _layer_jit(
                frozen, None if causal is None else tuple(causal),
                params["layers"], index, h, positions,
            )
            ties = ties + (gap[at] < TIE_MARGIN)
        if "lm_head" not in params:
            raise ValueError("sdar_moe: the head is untied and there is no lm_head")
        logits = _head(
            params["lm_head"], params["final_norm"], h[at], dims["eps"], False
        )
    return logits, ties


def logits_at(cfg, params, ids, score_positions, causal=None):
    return logits_and_near_ties(cfg, params, ids, score_positions, causal)[0]


def control_logits(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    n_prefill: int, score_positions: Sequence[int], control: str,
):
    """What ``logits_at`` would give if the mechanism were built wrong
    (``CONTROLS``), for sequences of ``n_prefill`` prefilled tokens and
    whole blocks after them. ``causal_prefill``: the causal mask in the
    block mask's place over the prefilled positions; ``causal_block``:
    the same over the positions after them (a block's queries no longer
    see its later keys: every position of a block but its last moves);
    ``stale_commit``: the block BEFORE the last was committed from the
    forward before its last transfer, so the cache holds a mask token's
    keys at its last position (score the last block); ``shifted``: the
    logits one position early (a model that predicts the NEXT token)."""
    dims = dims_of(cfg)
    ids = [int(t) for t in ids]
    T, Bk = len(ids), dims["block"]
    if control == "causal_prefill":
        return logits_at(cfg, params, ids, score_positions, (0, n_prefill))
    if control == "causal_block":
        return logits_at(cfg, params, ids, score_positions, (n_prefill, T))
    if control == "stale_commit":
        stale = list(ids)
        stale[T // Bk * Bk - Bk - 1] = dims["mask"]
        return logits_at(cfg, params, stale, score_positions)
    if control == "shifted":
        return logits_at(
            cfg, params, ids, [max(p - 1, 0) for p in score_positions]
        )
    raise ValueError(f"sdar_moe: unknown control {control!r}")


# ---------------------------------------------------------------------------
# Generation, for the tests: plain loops over blocks and steps
# ---------------------------------------------------------------------------


def transfer(x, x0, c, n: int, rule: str, tau: float, mask: int):
    """One row's block after a denoising forward. ``x`` [Bk] ints with
    ``mask`` where open, ``x0`` [Bk] the forward's tokens, ``c`` [Bk]
    their confidences, ``n`` the positions this forward should fill.
    ``low_confidence_static``: the ``n`` open positions of largest
    ``c``, ties to the left; ``low_confidence_dynamic``: every open
    position with ``c > tau`` if there are at least ``n`` of them, else
    the static rule's; ``sequential``: the leftmost ``n`` open
    positions."""
    x = [int(t) for t in x]
    is_open = [i for i, t in enumerate(x) if t == mask]
    n = min(int(n), len(is_open))
    if rule == "sequential":
        take = is_open[:n]
    else:
        take = sorted(is_open, key=lambda i: (-float(c[i]), i))[:n]
        if rule == "low_confidence_dynamic":
            over = [i for i in is_open if float(c[i]) > tau]
            if len(over) >= n:
                take = over
        elif rule != "low_confidence_static":
            raise ValueError(f"sdar_moe: unknown rule {rule!r}")
    for i in take:
        x[i] = int(x0[i])
    return x


def shares(block: int, steps: int) -> List[int]:
    """The even split of a block over its denoising steps."""
    return [block // steps + (t < block % steps) for t in range(steps)]


def generate(
    cfg: Dict[str, Any], params: Dict[str, Any], prompt: Sequence[int],
    new_tokens: int, steps: int, rule: str, tau: float,
    stop_ids: Sequence[int] = (),
):
    """Greedy generation by diffusion over blocks, every forward the
    whole sequence again (no cache): ``(tokens, top-two gaps)``, the
    ``new_tokens`` (fewer after a stop token, which is kept) that follow
    ``prompt`` and, for each, the gap between the largest and the
    second largest logit of the forward that filled it (so a test can
    leave out positions whose argmax a rounding could flip). The token
    at an open position is the argmax with ``mask_token_id`` left out,
    its confidence the softmax probability of it."""
    dims = dims_of(cfg)
    Bk, MASK = dims["block"], dims["mask"]
    seq = [int(t) for t in prompt]
    L = len(seq)
    n_s = shares(Bk, steps)
    gaps: Dict[int, float] = {}
    at = L // Bk * Bk                    # the first block not all prompt
    seq = seq[:at] + seq[at:] + [MASK] * (Bk - (L - at))
    while True:
        for t in range(steps + 1):
            block = seq[at : at + Bk]
            if MASK not in block:
                break
            logits = np.array(
                logits_at(cfg, params, seq, list(range(at, at + Bk))),
                np.float64,
            )
            logits[:, MASK] = -np.inf
            x0 = logits.argmax(-1)
            z = logits - logits.max(-1, keepdims=True)
            p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
            c = p[np.arange(Bk), x0]
            new = transfer(block, x0, c, n_s[min(t, steps - 1)], rule, tau, MASK)
            ranked = np.sort(logits, axis=-1)
            for i in range(Bk):
                if block[i] == MASK and new[i] != MASK:
                    gaps[at + i] = float(ranked[i, -1] - ranked[i, -2])
            seq[at : at + Bk] = new
        out = seq[L : at + Bk]
        stops = [i for i, tok in enumerate(out) if tok in set(stop_ids)]
        if stops and stops[0] < new_tokens:
            out = out[: stops[0] + 1]
            return out, [gaps[L + i] for i in range(len(out))]
        if len(out) >= new_tokens:
            out = out[:new_tokens]
            return out, [gaps[L + i] for i in range(len(out))]
        at += Bk
        seq = seq + [MASK] * Bk
