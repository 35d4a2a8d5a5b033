"""Config-driven decoder-only transformer in pure JAX.

The compute core of the engine (no analogue in the reference, which runs
models remotely — SURVEY §0). Design choices are TPU-first:

- Parameters are plain pytrees (nested dicts of ``jnp`` arrays) with all
  per-layer tensors **stacked on a leading layer axis**, so the layer loop
  is a single ``lax.scan`` (one trace, fast compiles) and shardings can be
  annotated per-leaf by path rules (parallel/sharding.py). A model whose
  layers are of several kinds (``ModelConfig.layer_types``,
  ``num_dense_layers``) stacks its parameters PER KIND,
  ``params["layers"][kind][name] [L_kind, ...]`` for the mixers "attn",
  "conv", "mamba", "mla", "kda", "mamba1", "gmu" and "cross" and the FFNs
  "dense" and "moe", and walks the
  config's own list of layers, scanning each repeated group
  (``_mixed_trunk``); a block may be a mixer alone or an FFN alone, under
  the one norm of its kind's stack (``ModelConfig.one_sublayer``).
- Static shapes everywhere: decode attends over a fixed ``CTX`` window
  gathered from the paged KV cache and masks invalid positions; prefill is
  bucketed by the runner. No data-dependent Python control flow.
- All matmuls run in ``bfloat16`` on the MXU; softmax/norms accumulate in
  ``float32``.
- One code path covers Qwen3 (dense+MoE), Llama 3, Gemma 3, gpt-oss,
  LFM2-MoE, Granite 4.0-H, Mellum 2, Nemotron-H, JoyAI-LLM-Flash and
  GLM-5 via ``ModelConfig`` fields (QK-norm, sliding windows, attention
  sinks, post norms, MoE and its router's form, per-layer mixer kinds,
  latent attention, an indexer that selects the keys it runs over) —
  see models/configs.py.

The forward returns the chunk's K/V for each ATTENTION layer and, for a
model with conv layers, each conv layer's carried state followed by the
chunk's gated inputs; the *caller* (engine/runner) scatters them into the
paged cache. That keeps this module purely functional and
cache-layout-agnostic.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .configs import FFN_KINDS, ModelConfig
from ..ops import lowering, pallas_ssm
from ..ops.moe import moe_mlp, relu2
from ..ops.attention import chunk_attention, latent_attention
from ..ops.sparse_attention import Indexer, sparse_latent_attention
from ..ops.quant import materialize

Params = Dict[str, Any]

_HI = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MixedChunk:
    """What ``forward`` hands back in the place of the chunk's K for a
    model whose layers are of several kinds. It rides where K rides
    (``logits, hidden, (k, v)``: the runner's entry points and
    ``kvcache.write_kv`` pass the pair along unopened), so a caller
    that commits a chunk's K/V commits the conv state with it."""

    # [L_attn, B, T, KVH, Dh], or fused [L_attn, B, T, KD]; for a model
    # of latent layers each token's latent ROW [L_mla, B, T, page_width]
    # (and no V beside it: ``forward`` returns None in V's place, or
    # the layers' INDEX KEYS [L_mla, B, T, index_head_dim] where they
    # have an indexer: ``kvcache.write_kv`` lands those in the index pool)
    k: jax.Array
    # each conv layer's carried state, then the chunk's gated inputs
    # g_1..g_T: the state after n <= T tokens is columns n..n+K-2
    conv: Optional[jax.Array] = None   # [L_conv, B, K-1+T, H]
    # rows each expert got, every routed layer (padding rows too)
    route: Optional[jax.Array] = None  # [L_moe, E] int32
    # what commits the mamba layers' state (``mamba_mixer``), stacked
    # over those layers. Always "conv": [L_m, B, K-1+T', Cd]. Then
    # either "final" [L_m, B, N, I], the state after the chunk's
    # ``valid_len`` tokens (T' = 0: "conv" is the columns after them),
    # or, for a chunk whose accepted length is decided later (T' = T),
    # the tokens' "dt", "dA" [L_m, B, T, Hm], "x" [L_m, B, T, I] and
    # "B" [L_m, B, T, G*N], from which ``kvcache.write_kv`` computes
    # the state after ANY n <= T of them. For "kda" layers
    # (``kda_mixer``) the same keys "conv" and "final", or the tokens'
    # log-decays "g" [L_k, B, T, I] float32, keys "k" and solved
    # updates "u" [L_k, B, T, I]: ``S_n = Diag(exp G_n) S_0 + sum_{i<=n}
    # (k_i * exp(G_n - G_i)) u_i^T`` for any n. For "mamba1" layers
    # (``mamba1_mixer``) "conv" and "final", or the tokens' "dt" float32
    # and "x" [L_m, B, T, I], "B" [L_m, B, T, N] and the layers' "A"
    # [L_m, N, I]: ``kvcache._advance_mamba1``. A fused window hands
    # every key over as its scan carried it instead (``window_buffer``,
    # 3-D, step-major): ``chunk_tokens`` reads a layer's from either
    ssm: Optional[Dict[str, jax.Array]] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StatePast:
    """The per-sequence matrix state (Mamba-2 or delta-rule layers:
    ``ModelConfig.state_kind``) as ``forward`` reads it. The
    pool is a CONSTANT of every scan (like the page pool): no forward
    writes it; ``kvcache.write_kv`` commits a chunk."""

    ssm: jax.Array      # [L_m, NS, N, I]: the slot pool
    slots: jax.Array    # [B] int32: each row's slot (0: the garbage slot)
    fresh: jax.Array    # [B] bool: the row starts a sequence (state 0)
    conv: jax.Array     # [L_m, B, K-1, Cd]: conv columns before the chunk
    # inside a fused window: the window's earlier tokens, not yet
    # committed, as the scan carries them (``window_buffer``), then the
    # step's index q0: (dt, dA, x, B, q0); for "kda" layers (g, k, u,
    # q0): ``pending_buffers`` names them, widths and dtypes. Rows at
    # and past q0 hold anything; the step's OWN token is not among them
    # (the mixer has it in registers). ``conv`` is then the window's
    # columns the same way: the K-1 before the window, then one a step
    window: Optional[Tuple[jax.Array, ...]] = None
    # inside a fused window of Mamba-1 layers: the rows' state after the
    # window's earlier tokens, [L_m, B, N, I] float32, carried by the
    # scan and never committed (``running_state``, ``mamba1_mixer``)
    running: Optional[jax.Array] = None


def _w(lp: Dict[str, Any], name: str, dtype) -> jax.Array:
    """Possibly-int8 weight leaf -> matmul-ready array (ops/quant.py)."""
    return materialize(lp[name], dtype)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def init_params(
    cfg: ModelConfig,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    shardings: Optional[Any] = None,
) -> Params:
    """Random init with per-layer stacking on axis 0 (scan layout).

    The whole tree comes out of ONE jitted program, so each leaf's
    ``normal * scale -> cast`` chain fuses and the float32 draw never
    exists as an array on the device (eagerly, qwen3-4b's ``w_gate``
    alone is 3.6 GB in f32 with two such alive during the multiply —
    on top of the 8 GB being built, that does not fit a 16 GB chip).
    ``shardings`` (a pytree of shardings matching the result, e.g.
    ``param_shardings(jax.eval_shape(init_params, ...), mesh)``) makes
    every leaf land sharded: a model that needs four chips never sits
    whole on the first. Values do not depend on the sharding."""
    dtype = jnp.dtype(dtype)
    if shardings is None:
        return _init_params_jit(cfg, key, dtype)
    build = functools.partial(_init_params, cfg, dtype=dtype)
    return jax.jit(build, out_shardings=shardings)(key)


def _init_mixed_layers(cfg: ModelConfig, dense, dtype) -> Dict[str, Any]:
    """Per-kind stacks of a model with layers of several kinds: the
    mixers "attn" [L_attn, ...] and "conv" [L_conv, ...], the FFNs
    "dense" [L_dense, ...] and "moe" [L_moe, ...]; each mixer stack
    carries its pre-norm (``attn_norm``), each FFN stack its
    ``mlp_norm`` (a block of ONE sublayer has the one norm of its
    kind's stack). The selection bias is drawn small and NON-zero, so
    that choosing by ``score + bias`` and weighting by ``score`` differ
    on random weights as they do on trained ones."""
    H, Dh = cfg.hidden_size, cfg.head_dim
    KVD = cfg.kv_size
    La, Lc = cfg.num_attn_layers, cfg.num_conv_layers
    Ld, Lm = cfg.ffns.count("dense"), cfg.ffns.count("moe")
    out: Dict[str, Any] = {}
    if cfg.num_mamba_layers:
        out["mamba"] = _init_mamba_layers(cfg, dense, dtype)
    if cfg.num_kda_layers:
        out["kda"] = _init_kda_layers(cfg, dense, dtype)
    if cfg.num_mamba1_layers:
        out["mamba1"] = _init_mamba1_layers(cfg, dense, dtype)
    if "gmu" in cfg.mixers:
        Lg, I = cfg.mixers.count("gmu"), cfg.mamba1_inner
        out["gmu"] = {
            "attn_norm": jnp.ones((Lg, H), dtype),
            "w_in": dense((Lg, H, I), H),
            "w_out": dense((Lg, I, H), I),
        }
    # full and window attention layers: the same block, a stack a kind,
    # at the kind's own count of query heads (``ModelConfig.heads_of``);
    # a "cross" layer is the block without K and V of its own
    for kind, mixer, Lk in (
        ("attn", "attention", La), ("swa", "swa", cfg.num_window_layers),
        ("cross", "cross", cfg.num_cross_layers),
    ):
        if not Lk:
            continue
        NH = cfg.heads_of(mixer)
        NHD = NH * Dh
        # (the draws in the order every seeded model has had them)
        out[kind] = {
            "attn_norm": jnp.ones((Lk, H), dtype),
            "wq": dense((Lk, H, NHD), H),
        }
        if kind != "cross":
            out[kind]["wk"] = dense((Lk, H, KVD), H)
            out[kind]["wv"] = dense((Lk, H, KVD), H)
        out[kind]["wo"] = dense((Lk, NHD, H), NHD)
        if cfg.attn_bias:
            # small and NON-zero, so that leaving one out shows
            small = jnp.asarray(0.1, dtype)
            out[kind]["bq"] = dense((Lk, NHD), 1) * small
            out[kind]["bo"] = dense((Lk, H), 1) * small
            if kind != "cross":
                out[kind]["bk"] = dense((Lk, KVD), 1) * small
                out[kind]["bv"] = dense((Lk, KVD), 1) * small
        if cfg.attn_differential:
            # the four lambda vectors normal at 0.1 and the inner norm's
            # weight 1, as the published code initialises them
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                out[kind][name] = (
                    dense((Lk, Dh), 1).astype(jnp.float32) * 0.1
                )
            out[kind]["diff_norm"] = jnp.ones((Lk, 2 * Dh), dtype)
        if cfg.qk_norm:
            out[kind]["q_norm"] = jnp.ones((Lk, Dh), dtype)
            out[kind]["k_norm"] = jnp.ones((Lk, Dh), dtype)
        if cfg.attn_gate:
            # a value a channel, or a scalar a head
            wide = NHD if cfg.attn_gate == "channel" else NH
            out[kind]["w_attn_gate"] = dense((Lk, H, wide), H)
    if cfg.num_latent_layers:
        Ll, NH = cfg.num_latent_layers, cfg.num_heads
        Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        Dn, Dr, Dv = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        )
        # ``seeded_peaked_attention`` (0: none): the key side of the
        # score drawn ``peak`` times 1 / fan-in in variance on each of
        # its two factors, so that a row's attention logits spread
        # ``peak`` standard deviations over its keys and not 1 (the
        # latent values are normed after ``w_kva``: its scale reaches
        # the rotary key alone; V's columns of ``w_kvb`` stay as they
        # are)
        peak = cfg.seeded_peaked_attention or 1.0
        out["mla"] = {
            "attn_norm": jnp.ones((Ll, H), dtype),
            "w_qa": dense((Ll, H, Rq), H),
            "q_norm": jnp.ones((Ll, Rq), dtype),
            # a head's columns: [q_nope | q_pe]
            "w_qb": dense((Ll, Rq, NH * (Dn + Dr)), Rq / peak),
            # [latent values | the shared rotary key]
            "w_kva": dense((Ll, H, Rkv + Dr), H / peak),
            "kv_norm": jnp.ones((Ll, Rkv), dtype),
            # a head's columns: [k_nope | v]
            "w_kvb": dense((Ll, Rkv, NH * (Dn + Dv)), Rkv),
            "wo": dense((Ll, NH * Dv, H), NH * Dv),
        }
        if peak != 1.0:
            cols = jnp.tile(
                jnp.where(jnp.arange(Dn + Dv) < Dn, peak ** 0.5, 1.0), NH
            )
            out["mla"]["w_kvb"] = (
                out["mla"]["w_kvb"].astype(jnp.float32) * cols
            ).astype(dtype)
        if cfg.index_topk:
            NHi, Di = cfg.index_n_heads, cfg.index_head_dim
            out["mla"].update({
                # the indexer: queries from the normed query latent, ONE
                # key a token under a LayerNorm (its bias drawn small and
                # non-zero, so that leaving it out shows), a weight a head
                "w_iqb": dense((Ll, Rq, NHi * Di), Rq),
                "w_ik": dense((Ll, H, Di), H),
                "ik_norm": jnp.ones((Ll, Di), dtype),
                "ik_bias": dense((Ll, Di), 1) * jnp.asarray(0.1, dtype),
                "w_iw": dense((Ll, H, NHi), H),
            })
    if Lc:
        K = cfg.conv_kernel
        out["conv"] = {
            "attn_norm": jnp.ones((Lc, H), dtype),
            "w_in": dense((Lc, H, 3 * H), H),    # [B | C | z] thirds
            "w_conv": dense((Lc, H, K), K),      # depthwise taps
            "w_out": dense((Lc, H, H), H),
        }
    if Ld:
        F = cfg.intermediate_size
        out["dense"] = {
            "mlp_norm": jnp.ones((Ld, H), dtype),
            "w_gate": dense((Ld, H, F), H),
            "w_up": dense((Ld, H, F), H),
            "w_down": dense((Ld, F, H), F),
        }
    if Lm:
        # the router is as wide as published; the expert stacks hold the
        # experts this chip holds (``ModelConfig.moe_experts_held``)
        E, Eh = cfg.moe_experts, cfg.experts_held
        Fm, Fs = cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size
        out["moe"] = {
            "mlp_norm": jnp.ones((Lm, H), dtype),
            "router": dense((Lm, H, E), H),
        }
        if cfg.moe_gated:
            out["moe"]["we_gate"] = dense((Lm, Eh, H, Fm), H)
            out["moe"]["we_up"] = dense((Lm, Eh, H, Fm), H)
        else:
            # two matrices an expert: the first OUTPUT-major (ops/moe.py)
            out["moe"]["we_up_t"] = dense((Lm, Eh, Fm, H), H)
        # 1 / fan-in keeps a unit-variance input's variance through a
        # matrix. The second matrix of a relu^2 expert sees a hidden row
        # of second moment E[relu(z)^4] = 1.5, not 1, and the routed sum
        # weights its experts by ``router_scale`` in all, not by 1: the
        # draw divides both out, so that the block's output stays the
        # size of its input as every other block's does. Drawn at
        # 1 / fan-in alone, a routed block's output is three times its
        # input's size, one selection flipped by a rounding moves the
        # stream by a third, and bfloat16 against float32 differ by half
        # the largest logit at one position in a hundred (PERF.md
        # section 6, PR 40)
        hidden = 1.5 if cfg.activation == "relu2" else 1.0
        out["moe"]["we_down"] = dense(
            (Lm, Eh, Fm, H),
            Fm * hidden * (cfg.router_scale / cfg.seeded_expert_gain) ** 2,
        )
        if Fs:
            if cfg.moe_gated:
                out["moe"]["shared_gate"] = dense((Lm, H, Fs), H)
            out["moe"]["shared_up"] = dense((Lm, H, Fs), H)
            out["moe"]["shared_down"] = dense((Lm, Fs, H), Fs * hidden)
            if cfg.moe_shared_gate:
                out["moe"]["shared_expert_gate"] = dense((Lm, H, 1), H)
        if cfg.router_select_bias:
            out["moe"]["router_bias"] = (
                dense((Lm, E), 1) * 0.02
            ).astype(jnp.float32)
    if cfg.hc_mult > 1:
        # a sublayer's hyper-connection (``hc_sublayer``) rides on its
        # kind's stack: ``hc_mix_*`` on a mixer's, ``hc_ffn_*`` on an
        # FFN's. ``phi`` (output-major) at variance 1 / (n C), so that
        # the dynamic half of a coefficient's logit is of unit size;
        # ``alpha`` = 1 (the paper's 0.01 is where TRAINING starts: there
        # the dynamic half lies under the bfloat16 rounding of the static
        # one and no check could tell that it was computed); the biases
        # of unit size, the mixing matrix's twice that, so that its
        # logits spread and one Sinkhorn pass is far from twenty
        n, K = cfg.hc_mult, cfg.hc_mult * (cfg.hc_mult + 2)
        for kind, stack in out.items():
            prefix = "hc_ffn_" if kind in FFN_KINDS else "hc_mix_"
            Lk = stack["mlp_norm" if kind in FFN_KINDS else "attn_norm"].shape[0]
            bias = dense((Lk, K), 1).astype(jnp.float32)
            stack[prefix + "phi"] = dense((Lk, K, n * H), n * H)
            stack[prefix + "b"] = bias * jnp.where(
                jnp.arange(K) < 2 * n, 1.0, 2.0
            )
            stack[prefix + "alpha"] = jnp.ones((Lk, 3), jnp.float32)
    if cfg.block_norm == "layernorm":
        # a LayerNorm's bias beside every block norm's scale, small and
        # NON-zero, so that leaving it out shows
        for kind, stack in out.items():
            name = "mlp_norm" if kind in FFN_KINDS else "attn_norm"
            stack[name + "_b"] = dense(stack[name].shape, 1) * jnp.asarray(
                0.1, dtype
            )
    return out


def _init_mamba_layers(cfg: ModelConfig, dense, dtype) -> Dict[str, Any]:
    """The "mamba" stack. ``a_log``, ``dt_bias`` and ``d_skip`` as the
    Mamba-2 reference code draws them: A uniform in [1, 16], dt
    log-uniform in [0.001, 0.1] through the inverse softplus, D = 1, so
    that the heads' decays span short and long memory. float32, like
    the router's selection bias."""
    L, H = cfg.num_mamba_layers, cfg.hidden_size
    I, Cd, Hm = cfg.mamba_inner, cfg.mamba_conv_dim, cfg.mamba_heads
    u = (dense((L, Hm), 1), dense((L, Hm), 1))  # normal draws -> uniform
    ua, ud = (
        jax.scipy.stats.norm.cdf(x.astype(jnp.float32)) for x in u
    )
    dt = jnp.exp(ud * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
    return {
        "attn_norm": jnp.ones((L, H), dtype),
        # the published in_proj's columns [z | x B C | dt] as two
        # matrices: its last ``heads`` columns (dt) apart, so that each
        # is a whole number of 128-lane tiles wide
        "w_in": dense((L, H, I + Cd), H),        # [z | x B C]
        "w_dt": dense((L, H, Hm), H),
        "w_conv": dense((L, Cd, cfg.mamba_conv), cfg.mamba_conv),
        "b_conv": dense((L, Cd), 16),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
        "a_log": jnp.log(1.0 + 15.0 * ua),
        "d_skip": jnp.ones((L, Hm), jnp.float32),
        "gate_norm": jnp.ones((L, I), dtype),
        "w_out": dense((L, I, H), I),
    }


def _init_mamba1_layers(cfg: ModelConfig, dense, dtype) -> Dict[str, Any]:
    """The "mamba1" stack, as the Mamba reference code draws it: ``A =
    1..N`` a channel (``a_log`` its log, kept ``[N, I]`` as the state
    lies: the state axis major), dt log-uniform in [0.001, 0.1] through
    the inverse softplus, D = 1, so that the columns' decays span short
    and long memory. float32, like Mamba-2's."""
    L, H = cfg.num_mamba1_layers, cfg.hidden_size
    I, N, R, K = (
        cfg.mamba1_inner, cfg.mamba1_state, cfg.mamba1_dt_rank,
        cfg.mamba1_conv,
    )
    ud = jax.scipy.stats.norm.cdf(dense((L, I), 1).astype(jnp.float32))
    dt = jnp.exp(ud * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
    return {
        "attn_norm": jnp.ones((L, H), dtype),
        "w_in": dense((L, H, 2 * I), H),         # [x | z]
        "w_conv": dense((L, I, K), K),
        "b_conv": dense((L, I), 16),
        "w_x": dense((L, I, R + 2 * N), I),      # [dt's rank | B | C]
        "w_dt": dense((L, R, I), R),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (L, N, I),
        ),
        "d_skip": jnp.ones((L, I), jnp.float32),
        "w_out": dense((L, I, H), I),
    }


def _init_kda_layers(cfg: ModelConfig, dense, dtype) -> Dict[str, Any]:
    """The "kda" stack. ``a_log`` a head and ``dt_bias`` a channel as the
    published layer draws them (A uniform in [1, 16], dt log-uniform in
    [0.001, 0.1] through the inverse softplus), float32, so that seeded
    states neither vanish nor saturate; the gate's bias small and
    NON-zero, so that leaving it out shows."""
    L, H = cfg.num_kda_layers, cfg.hidden_size
    I, Hk, R = cfg.kda_inner, cfg.kda_heads, cfg.kda_rank
    ua, ud = (
        jax.scipy.stats.norm.cdf(x.astype(jnp.float32))
        for x in (dense((L, Hk), 1), dense((L, I), 1))
    )
    dt = jnp.exp(ud * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
    return {
        "attn_norm": jnp.ones((L, H), dtype),
        "w_qkv": dense((L, H, 3 * I), H),        # [q | k | v]
        "w_conv": dense((L, 3 * I, cfg.kda_conv), cfg.kda_conv),
        # the log-decay a channel: a pair of rank R, then a bias
        "w_fa": dense((L, H, R), H),
        "w_fb": dense((L, R, I), R),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
        "a_log": jnp.log(1.0 + 15.0 * ua),
        "w_beta": dense((L, H, Hk), H),
        # the output gate: a pair of rank R, a bias on the second
        "w_ga": dense((L, H, R), H),
        "w_gb": dense((L, R, I), R),
        "b_g": dense((L, I), 1) * jnp.asarray(0.1, dtype),
        "o_norm": jnp.ones((L, cfg.kda_head_dim), dtype),
        "w_out": dense((L, I, H), I),
    }


def _init_params(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    H, L = cfg.hidden_size, cfg.num_layers
    NHD, KVD = cfg.q_size, cfg.kv_size
    F, Dh = cfg.intermediate_size, cfg.head_dim
    keys = iter(jax.random.split(key, 64))

    def dense(shape, scale_dim):
        return (
            jax.random.normal(next(keys), shape, jnp.float32)
            * (scale_dim ** -0.5)
        ).astype(dtype)

    if cfg.homogeneous and (
        cfg.attn_gate or cfg.hc_mult > 1 or cfg.rotary_dim
        or cfg.window_num_heads or cfg.attn_differential
        or cfg.block_norm != "rmsnorm"
    ):
        raise NotImplementedError(
            f"{cfg.name}: an attention output gate (attn_gate), a "
            "residual stream of several lanes (hc_mult), a rotary part "
            "narrower than the head (rotary_dim), query heads a layer "
            "kind (window_num_heads), differential attention "
            "(attn_differential) or LayerNorm blocks (block_norm) in a "
            "model whose "
            "every layer is one block: the mixed walk builds them (list "
            "the layers' kinds, layer_types), the one scan of "
            "layer_apply does not"
        )
    if not cfg.homogeneous:
        _check_mixed(cfg)
        params = {
            # ``ModelConfig.seeded_unit_embedding``: unit elements, or 1 / H
            "embed": dense(
                (cfg.vocab_size, H), 1 if cfg.seeded_unit_embedding else H
            ),
            "final_norm": jnp.ones((H,), dtype),
            "layers": _init_mixed_layers(cfg, dense, dtype),
        }
        if cfg.block_norm == "layernorm":
            params["final_norm_b"] = dense((H,), 1) * jnp.asarray(0.1, dtype)
        if not cfg.tie_embeddings and cfg.head == "lm":
            params["lm_head"] = dense((H, cfg.vocab_size), H)
        return params

    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, H), dtype),
        "wq": dense((L, H, NHD), H),
        "wk": dense((L, H, KVD), H),
        "wv": dense((L, H, KVD), H),
        "wo": dense((L, NHD, H), NHD),
        "mlp_norm": jnp.ones((L, H), dtype),
    }
    if cfg.norm_zero_centered:
        layers["attn_norm"] = jnp.zeros((L, H), dtype)
        layers["mlp_norm"] = jnp.zeros((L, H), dtype)
    if cfg.attn_bias:
        layers["bq"] = jnp.zeros((L, NHD), dtype)
        layers["bk"] = jnp.zeros((L, KVD), dtype)
        layers["bv"] = jnp.zeros((L, KVD), dtype)
        layers["bo"] = jnp.zeros((L, H), dtype)
    if cfg.qk_norm:
        q_init = jnp.zeros if cfg.norm_zero_centered else jnp.ones
        layers["q_norm"] = q_init((L, Dh), dtype)
        layers["k_norm"] = q_init((L, Dh), dtype)
    if cfg.attention_sink:
        layers["sink"] = jnp.zeros((L, cfg.num_heads), dtype)
    if cfg.post_norms:
        init = jnp.zeros if cfg.norm_zero_centered else jnp.ones
        layers["post_attn_norm"] = init((L, H), dtype)
        layers["post_mlp_norm"] = init((L, H), dtype)
    if cfg.moe_experts:
        E, Fm = cfg.moe_experts, cfg.moe_intermediate_size
        layers["router"] = dense((L, H, E), H)
        layers["we_gate"] = dense((L, E, H, Fm), H)
        layers["we_up"] = dense((L, E, H, Fm), H)
        layers["we_down"] = dense((L, E, Fm, H), Fm)
        if cfg.moe_bias:
            layers["router_b"] = jnp.zeros((L, E), dtype)
            layers["we_gate_b"] = jnp.zeros((L, E, Fm), dtype)
            layers["we_up_b"] = jnp.zeros((L, E, Fm), dtype)
            layers["we_down_b"] = jnp.zeros((L, E, H), dtype)
    else:
        layers["w_gate"] = dense((L, H, F), H)
        layers["w_up"] = dense((L, H, F), H)
        layers["w_down"] = dense((L, F, H), F)

    params: Params = {
        "embed": dense((cfg.vocab_size, H), H),
        "final_norm": (jnp.zeros if cfg.norm_zero_centered else jnp.ones)(
            (H,), dtype
        ),
        "layers": layers,
    }
    if not cfg.tie_embeddings and cfg.head == "lm":
        params["lm_head"] = dense((H, cfg.vocab_size), H)
    return params


# one cached program per (config, dtype): runners are built often (tests)
_init_params_jit = jax.jit(_init_params, static_argnums=(0, 2))


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float, zero_centered: bool) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    scale = (1.0 + w.astype(jnp.float32)) if zero_centered else w.astype(jnp.float32)
    return (x32 * scale).astype(dt)


def block_norm(cfg: ModelConfig, x: jax.Array, lp: Dict[str, Any], name: str):
    """The norm in front of a block's sublayer (``lp[name]``:
    ``attn_norm`` | ``mlp_norm``) and of the head (``final_norm``), by
    ``ModelConfig.block_norm``: THE place it is read. A LayerNorm's
    bias is the leaf ``name + "_b"``."""
    if cfg.block_norm == "layernorm":
        return layer_norm(x, lp[name], lp[name + "_b"], cfg.norm_eps)
    return rms_norm(x, lp[name], cfg.norm_eps, cfg.norm_zero_centered)


def rope_inv_freq(
    theta, half: int, cfg: Optional[ModelConfig] = None, yarn: bool = False
) -> Tuple[jax.Array, float]:
    """``(inverse frequencies [half], what cos and sin are multiplied
    by)`` of a rotary embedding over ``2 * half`` elements: the plain
    power ``theta^(-i / half)`` (``theta`` may be traced: the scan's
    per-layer base), or the config's YaRN-scaled ones. THE place both
    pairings (``apply_rope``'s halves, ``apply_rope_interleaved``'s
    neighbours) take them from."""
    if yarn:
        freq, scale = _yarn_inv_freq(cfg, half)
        return jnp.asarray(freq), scale
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half), 1.0


def _yarn_inv_freq(cfg: ModelConfig, half: int) -> Tuple[np.ndarray, float]:
    """Static YaRN-scaled inverse frequencies + attention scaling
    (gpt-oss ships factor-32 YaRN over a 4096-token original window).
    NTK-by-parts: low dims (fast-rotating, within the original window)
    extrapolate, high dims interpolate by ``factor``, with a linear ramp
    between the beta_fast/beta_slow wavelength cutoffs; cos/sin are
    scaled by ``0.1 ln(factor) + 1``."""
    base = cfg.rope_theta
    factor = cfg.rope_scaling_factor
    orig = max(cfg.rope_original_max, 1)
    dim = 2 * half
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrap = 1.0 / pos_freqs
    interp = 1.0 / (factor * pos_freqs)

    def find_dim(n_rot: float) -> float:
        return (
            dim * np.log(orig / (n_rot * 2 * np.pi))
        ) / (2 * np.log(base))

    low = max(np.floor(find_dim(cfg.rope_beta_fast)), 0)
    high = min(np.ceil(find_dim(cfg.rope_beta_slow)), dim - 1)
    rng = np.arange(half, dtype=np.float64)
    ramp = np.clip((rng - low) / max(high - low, 1e-3), 0.0, 1.0)
    extrap_factor = 1.0 - ramp
    inv_freq = interp * (1 - extrap_factor) + extrap * extrap_factor
    attn_scale = cfg.rope_attention_factor
    if attn_scale is None:
        attn_scale = 0.1 * float(np.log(factor)) + 1.0
    return inv_freq.astype(np.float32), float(attn_scale)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: jax.Array,
    cfg: Optional[ModelConfig] = None,
    yarn: Optional[bool] = None,
    rotary_dim: int = 0,
) -> jax.Array:
    """rotate-half RoPE. x: [B, T, N, Dh]; positions: [B, T]. ``yarn``
    says whether this layer takes the config's YaRN scaling: the walk
    over layer kinds knows each layer's kind and says (a window layer:
    False, plain ``theta``); None leaves it to the config, for the one
    scan of a homogeneous model, whose layers all take it.
    ``rotary_dim`` (static; 0: the whole head): the head's first
    elements that turn, in half-split pairs inside them, under
    frequencies of that many elements; the rest pass through
    (``ModelConfig.rotary_dim_of``)."""
    dh = x.shape[-1]
    if rotary_dim and rotary_dim < dh:
        with jax.named_scope("partial_rope"):
            turned = apply_rope(
                x[..., :rotary_dim], positions, theta, cfg, yarn
            )
            return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    half = dh // 2
    if yarn is None:
        yarn = cfg is not None and bool(cfg.rope_scaling_factor)
        if yarn and cfg.local_rope_theta:
            # the scan's per-layer theta is traced and the YaRN
            # frequencies are static, from the GLOBAL base: a scanned
            # model that mixes the two would mis-rotate its local
            # layers. Listed ``layer_types`` ("swa") carry both
            raise NotImplementedError(
                "YaRN rope_scaling with local_rope_theta needs the "
                "layers listed by kind (layer_types), not sliding_pattern"
            )
    freq, scale = rope_inv_freq(theta, half, cfg, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freq  # [B, T, half]
    cos = jnp.cos(ang)[:, :, None, :] * scale
    sin = jnp.sin(ang)[:, :, None, :] * scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _router_form(cfg: ModelConfig, lp: Dict[str, Any]) -> Optional[dict]:
    """``ops/moe._route``'s keywords for this config; None for its
    default (softmax over the top-k)."""
    if (cfg.router_score, cfg.router_renorm, cfg.router_scale) == (
        "softmax", True, 1.0
    ) and not cfg.router_select_bias:
        return None
    form = dict(
        score=cfg.router_score,
        select_bias=lp["router_bias"] if cfg.router_select_bias else None,
        renorm=cfg.router_renorm,
        scale=cfg.router_scale,
    )
    if cfg.router_renorm_eps != 1e-6:       # ``_route``'s own
        form["renorm_eps"] = cfg.router_renorm_eps
    return form


def _ffn(cfg: ModelConfig, lp: Dict[str, Any], x: jax.Array, names) -> jax.Array:
    """A dense FFN over ``x`` from the leaves ``names`` = (gate, up,
    down); ``lp`` without the gate's leaf is the two-matrix form,
    ``down(relu(up x)^2)``."""
    gate_name, up_name, down_name = names
    up = x @ _w(lp, up_name, x.dtype)
    if gate_name not in lp:
        return relu2(up) @ _w(lp, down_name, x.dtype)
    gate = x @ _w(lp, gate_name, x.dtype)
    if cfg.activation == "gelu":
        act = jax.nn.gelu(gate.astype(jnp.float32), approximate=True).astype(x.dtype)
    elif cfg.activation == "swiglu_oss":
        g = jnp.clip(gate.astype(jnp.float32), max=7.0)
        act = (g * jax.nn.sigmoid(1.702 * g)).astype(x.dtype)
        up = jnp.clip(up.astype(jnp.float32), -7.0, 7.0).astype(x.dtype) + 1.0
    else:
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
    return (act * up) @ _w(lp, down_name, x.dtype)


def _mlp(
    cfg: ModelConfig, lp: Dict[str, Any], x: jax.Array, ep_mesh=None,
    use_pallas: bool = False, return_counts: bool = False,
    expert_stacks: Optional[Tuple[Dict[str, Any], jax.Array]] = None,
    kernel_mesh=None,
):
    """The layer's FFN over normed ``x``: routed when the layer's
    parameters hold a router, dense otherwise. ``return_counts`` (routed
    layers, not under EP) also returns the rows each expert got.
    ``kernel_mesh`` (the runner's: a mesh whose ``model`` axis shards
    the operands) keeps the routed products that GSPMD partitions on
    ``ragged_dot``: XLA cannot partition a Mosaic call, and only the EP
    path's ``shard_map`` hands the kernel whole operands a shard.
    ``expert_stacks`` = (every routed layer's ``we_*`` stacked, this
    layer's index) for a caller that must not slice the experts out
    (ops/moe.py ``moe_mlp``: ``layer``)."""
    layer = None
    if expert_stacks is not None:
        stacks, layer = expert_stacks
        if ep_mesh is None:
            lp = {**lp, **stacks}
        else:  # the EP path shards one layer's experts: a slice
            lp = {**lp, **jax.tree_util.tree_map(lambda a: a[layer], stacks)}
            layer = None
    if "router" in lp:
        kwargs = dict(
            top_k=cfg.moe_top_k,
            activation=cfg.activation,
            router_b=lp.get("router_b"),
            bias_gate=lp.get("we_gate_b"),
            bias_up=lp.get("we_up_b"),
            bias_down=lp.get("we_down_b"),
            route=_router_form(cfg, lp),
        )
        gated = "we_gate" in lp
        args = (
            x,
            lp["router"],
            _w(lp, "we_gate", x.dtype) if gated else None,
            _w(lp, "we_up" if gated else "we_up_t", x.dtype),
            _w(lp, "we_down", x.dtype),
        )
        counts = None
        if ep_mesh is not None and not gated:
            raise NotImplementedError(
                f"{cfg.name}: experts of two matrices under the shard_map "
                "expert-parallel path (ops/moe_ep.py takes three)"
            )
        if ep_mesh is not None:
            # explicit shard_map EP: expert weights stay resident at
            # 1/(ep*tp) per shard (ops/moe_ep.py) instead of GSPMD
            # all-gathering them for the ragged grouped GEMM
            from ..ops.moe_ep import moe_mlp_ep

            out = moe_mlp_ep(
                *args, mesh=ep_mesh, use_pallas=use_pallas, **kwargs
            )
            if return_counts:
                counts = jnp.zeros((lp["router"].shape[-1],), jnp.int32)
        else:
            out = moe_mlp(
                *args, return_counts=return_counts, layer=layer,
                first_expert=cfg.moe_first_expert,
                share_rows=cfg.moe_share_rows,
                token_tile=cfg.moe_token_tile,
                use_pallas=use_pallas and kernel_mesh is None, **kwargs
            )
            if return_counts:
                out, counts = out
        if "shared_up" in lp:
            # every chip that shares the layer computes it alike: it is
            # counted once where the shares are summed
            with jax.named_scope("shared_expert"):
                shared = _ffn(
                    cfg, lp, x, ("shared_gate", "shared_up", "shared_down")
                )
                if "shared_expert_gate" in lp:
                    # a sigmoid scalar a token on the shared expert's
                    # output (``ModelConfig.moe_shared_gate``)
                    g = jax.nn.sigmoid((
                        x @ _w(lp, "shared_expert_gate", x.dtype)
                    ).astype(jnp.float32))
                    shared = (shared.astype(jnp.float32) * g).astype(x.dtype)
                out = out + shared
        return (out, counts) if return_counts else out
    return _ffn(cfg, lp, x, ("w_gate", "w_up", "w_down"))


def attention_mixer(
    cfg: ModelConfig,
    lp: Dict[str, Any],          # one attention layer's params
    x: jax.Array,                # [B, T, H], normed
    *,
    positions: jax.Array,        # [B, T]
    valid_len: jax.Array,        # [B]
    window: jax.Array,           # scalar int32
    theta: jax.Array,            # scalar fp32 RoPE base
    k_pages=None, v_pages=None, k_scale=None, v_scale=None,
    layer=None,                  # this layer's index INTO THE POOL
    page_table=None, past_len=None, use_pallas: bool = False,
    ring_mesh=None, wk_l=None, wv_l=None, win_len=None,
    pfx_groups=None, kernel_mesh=None,
    yarn: Optional[bool] = None, live_window: int = 0,
    rotary_dim: int = 0,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None, depth=None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """GQA attention over the chunk and its paged past, through the
    output projection: ``(out [B, T, H], (k_chunk, v_chunk))``. The one
    attention block of every model (``layer_apply`` and the mixed walk
    both call it). The query heads are what ``wq`` is wide (a layer
    kind's own count: ``ModelConfig.heads_of``). ``yarn`` and
    ``rotary_dim``: ``apply_rope``'s. ``live_window`` (static;
    a "swa" layer's window) says the pool and table handed in are the
    WINDOW pool's, which holds a row's last ``live_window`` positions
    and nothing older (ops/attention.py). ``kv``: the chunk's K and V
    ``[B, T, KVH, Dh]`` of ANOTHER layer (a "cross" layer, which
    projects a query alone; the pool, ``layer`` and the window's buffers
    handed in are then that layer's too). ``depth``: the layer's place
    in the model, for ``ModelConfig.attn_differential``'s
    ``lambda_init``."""
    B, T = x.shape[:2]
    q = x @ _w(lp, "wq", x.dtype)
    if kv is None:
        k = x @ _w(lp, "wk", x.dtype)
        v = x @ _w(lp, "wv", x.dtype)
    if cfg.attn_bias:
        q = q + lp["bq"]
        if kv is None:
            k, v = k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, T, -1, cfg.head_dim)
    NH = q.shape[2]
    if kv is None:
        k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps, cfg.norm_zero_centered)
        if cfg.position_embedding != "nope":
            k = apply_rope(k, positions, theta, cfg, yarn, rotary_dim)
    else:
        k, v = kv
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps, cfg.norm_zero_centered)
    if cfg.position_embedding != "nope":
        q = apply_rope(q, positions, theta, cfg, yarn, rotary_dim)
    # what the kernels see as a head: a PAIR under differential attention
    Dk = cfg.kernel_head_dim
    if cfg.attention_multiplier is not None or cfg.attn_differential:
        # every attention path scales by 1/sqrt(its head): fold the ratio
        # into q (a power of two for the published multipliers)
        scale = cfg.attention_multiplier or cfg.head_dim ** -0.5
        q = q * jnp.asarray(scale * Dk ** 0.5, q.dtype)
    kq, vq = k, v
    if cfg.attn_differential:
        # the 128-wide call: a page's row of KVH heads IS KVH / 2 pairs
        # ``[k1 | k2]`` and ``[v1 | v2]`` lying as the write left them;
        # query head 2i is ``[q1 | 0]`` and 2i + 1 ``[0 | q2]``, so each
        # softmax runs over its own half of the pair's keys and BOTH
        # take the whole value: K and V are read once a pair
        even = (jnp.arange(NH) % 2 == 0)[:, None]
        zero = jnp.zeros((), q.dtype)
        q = jnp.concatenate(
            [jnp.where(even, q, zero), jnp.where(even, zero, q)], axis=-1
        )
        kq = k.reshape(B, T, -1, Dk)
        vq = v.reshape(B, T, -1, Dk)
    sink = lp.get("sink") if cfg.attention_sink else None
    attn = chunk_attention(
        q, kq, vq,
        positions=positions,
        valid_len=valid_len,
        past_k_pages=k_pages, past_v_pages=v_pages, layer=layer,
        past_k_scale=k_scale, past_v_scale=v_scale,
        page_table=page_table, past_len=past_len,
        window=window, sink=sink,
        use_pallas=use_pallas,
        ring_mesh=ring_mesh,
        win_k=wk_l, win_v=wv_l, win_len=win_len,
        pfx_groups=pfx_groups,
        kernel_mesh=kernel_mesh,
        live_window=live_window,
        block_length=cfg.block_length,
    )
    if cfg.attn_differential:
        with jax.named_scope("diff_heads"):
            attn = differential_heads(cfg, lp, attn, depth)
    if "w_attn_gate" in lp:
        # an output gate from the layer's input, a value a channel or a
        # scalar a head by what the leaf is wide (``ModelConfig.
        # attn_gate``; a dense FFN's ``w_gate`` shares a homogeneous
        # layer's dict, hence the longer name)
        with jax.named_scope("gqa_gate"):
            gate = jax.nn.sigmoid(
                (x @ _w(lp, "w_attn_gate", x.dtype)).astype(jnp.float32)
            )
            gate = gate.reshape(B, T, NH, -1)   # [.., Dh] or [.., 1]
            attn = (attn.astype(jnp.float32) * gate).astype(x.dtype)
    attn = attn.reshape(B, T, NH * cfg.head_dim)
    attn = attn @ _w(lp, "wo", x.dtype)
    if cfg.attn_bias:
        attn = attn + lp["bo"]
    return attn, (k, v)


def lambda_init(depth) -> jax.Array:
    """A differential layer's ``lambda_init`` at its place ``depth`` in
    the model (arXiv:2410.05258): ``0.8 - 0.6 exp(-0.3 depth)``."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def differential_heads(cfg: ModelConfig, lp: Dict[str, Any], a, depth):
    """``a`` [B, T, NH, 2 Dh]: the pair form's outputs, query head 2i
    the first softmax of differential head i over the pair's value,
    2i + 1 the second. Returns ``RMSNorm(a1 - lambda a2) (1 -
    lambda_init)`` a head, ``[B, T, NH / 2, 2 Dh]`` in the layer's dtype,
    which laid flat is the ``NH`` heads of ``Dh`` that ``wo`` takes. The
    subtraction and the norm in float32."""
    f32, dtype = jnp.float32, lp["diff_norm"].dtype
    B, T, NH, D2 = a.shape
    a = a.astype(f32).reshape(B, T, NH // 2, 2, D2)
    init = lambda_init(depth)
    lam = (
        jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32) * lp["lambda_k1"].astype(f32)))
        - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32) * lp["lambda_k2"].astype(f32)))
        + init
    )
    d = a[:, :, :, 0] - lam * a[:, :, :, 1]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + cfg.norm_eps)
    d = d * lp["diff_norm"].astype(f32) * (1.0 - init)
    return d.astype(dtype)


def apply_rope_interleaved(
    x: jax.Array, positions: jax.Array, theta: float,
    cfg: Optional[ModelConfig] = None,
) -> jax.Array:
    """The rotary embedding on pairs ``(2i, 2i+1)``, each turned by
    ``pos * theta^(-2i/D)`` (``rope_interleave``: the published layout
    of a latent layer's rotary part), or by the config's YaRN-scaled
    frequencies where ``cfg`` has a ``rope_scaling_factor`` (cos and
    sin then times its attention factor). x: [B, T, ..., D]; positions
    [B, T]. The pair's partner is fetched by a signed permutation as a
    [D, D] product (each output is one input times +-1: exact), which
    keeps D on the lanes; a [..., D/2, 2] view would put an axis of 2
    there."""
    D = x.shape[-1]
    freq, scale = rope_inv_freq(
        theta, D // 2, cfg, cfg is not None and bool(cfg.rope_scaling_factor)
    )
    ang = positions.astype(jnp.float32)[..., None] * freq     # [B, T, D/2]
    shape = ang.shape[:2] + (1,) * (x.ndim - 3) + (D,)
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1).reshape(shape)
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1).reshape(shape)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    i = np.arange(D)
    swap = np.zeros((D, D), np.float32)
    swap[i ^ 1, i] = np.where(i % 2 == 0, -1.0, 1.0)   # (a, b) -> (-b, a)
    xf = x.astype(jnp.float32)
    partner = jnp.matmul(xf, jnp.asarray(swap), precision=_HI)
    return (xf * cos + partner * sin).astype(x.dtype)


def mla_mixer(
    cfg: ModelConfig,
    lp: Dict[str, Any],          # one latent layer's params
    x: jax.Array,                # [B, T, H], normed
    *,
    positions: jax.Array,        # [B, T]
    valid_len: jax.Array,        # [B]
    pages=None,                  # [L, NP, PS, page_width]: the latent pool
    layer=None,                  # this layer's index into it
    page_table=None, past_len=None,
    win_rows=None,               # [B, W, page_width]: a fused window's rows
    win_len=None, use_pallas: bool = False,
    index_pages=None,            # [L, NP, PS, index_head_dim]: the index pool
    win_index=None,              # [B, W, index_head_dim]: a fused window's
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Latent attention over a chunk (``ModelConfig.q_lora_rank`` ...):

        c_q = RMSNorm(x W_qa) ;  [q_nope | q_pe] = c_q W_qb      a head
        [c_kv | k_pe] = x W_kva ;  c_kv = RMSNorm(c_kv)
        q_pe, k_pe = rope(q_pe), rope(k_pe)     k_pe ONE a token
        [k_nope | v] = c_kv W_kvb                                a head
        score = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)
        out = concat_h(softmax(score) v) W_o

    Under YaRN (``ModelConfig.rope_scaling_factor``) the rotary part
    turns by the by-parts frequencies and the score's scale grows by
    ``(0.1 mscale_all_dim ln(factor) + 1)^2``.

    With an indexer (``ModelConfig.index_topk``) the softmax runs over
    the positions it selects (``_indexer``; ops/sparse_attention.py): a
    third step between the projections and the attention.

    Returns ``(out [B, T, H], row [B, T, page_width], index key [B, T,
    index_head_dim] or None)``: ``row`` = ``[c_kv | k_pe | 0..]`` and the
    index key are all the cache keeps of a token. Two forms of
    the same numbers. With no ``pages`` (a chunk with no past) the
    EXPANDED form: K and V a head from the chunk's own rows. Over a
    paged past the ABSORBED form: ``W_kvb``'s K half folded into the
    query (``q~ = q_nope W_UK^T``, as wide as the latent values) and
    its V half applied after the sum, so every head reads the SAME
    stored row for both products and K and V are never rebuilt from
    the pool (ops/attention.py ``latent_attention``)."""
    B, T = x.shape[:2]
    NH, Rkv = cfg.num_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = rms_norm(x @ _w(lp, "w_qa", x.dtype), lp["q_norm"], cfg.norm_eps, False)
    q = (c_q @ _w(lp, "w_qb", x.dtype)).reshape(B, T, NH, Dn + Dr)
    kva = x @ _w(lp, "w_kva", x.dtype)
    c_kv = rms_norm(kva[..., :Rkv], lp["kv_norm"], cfg.norm_eps, False)
    scale = (Dn + Dr) ** -0.5

    def rotated():
        return tuple(
            apply_rope_interleaved(v, positions, cfg.rope_theta, cfg)
            for v in (q[..., Dn:], kva[..., Rkv:])
        )

    if cfg.rope_scaling_factor:
        # YaRN on the rotary part (the DeepSeek-V3 family's form): the
        # by-parts frequencies, and the softmax scale times
        # ``(0.1 mscale_all_dim ln(factor) + 1)^2``
        with jax.named_scope("mla_yarn"):
            q_pe, k_pe = rotated()
        scale *= float(
            0.1 * cfg.rope_mscale_all_dim * np.log(cfg.rope_scaling_factor)
            + 1.0
        ) ** 2
    else:
        q_pe, k_pe = rotated()
    # the pool's row: the latent values, the shared key, then zeros up
    # to whole lane tiles (``ModelConfig.page_width``)
    pad = cfg.page_width - cfg.latent_width
    row = jnp.concatenate(
        [c_kv, k_pe, jnp.zeros((B, T, pad), c_kv.dtype)], axis=-1
    )
    w_kvb = _w(lp, "w_kvb", x.dtype).reshape(Rkv, NH, Dn + Dv)
    index, attend = None, latent_attention
    if cfg.index_topk:
        with jax.named_scope("dsa_indexer"):
            index = _indexer(
                cfg, lp, x, c_q, positions, pages=index_pages, win=win_index
            )
        attend = functools.partial(sparse_latent_attention, index=index)
    if pages is None:
        with jax.named_scope("mla_expand"):
            kv = jnp.einsum("btc,cnd->btnd", c_kv, w_kvb)
            k = jnp.concatenate(
                [kv[..., :Dn],
                 jnp.broadcast_to(k_pe[:, :, None], (B, T, NH, Dr))],
                axis=-1,
            )
            qf = jnp.concatenate([q[..., :Dn], q_pe], axis=-1)
            o = attend(
                qf, k, kv[..., Dn:], positions=positions,
                valid_len=valid_len, scale=scale, use_pallas=use_pallas,
            )
    else:
        with jax.named_scope("mla_absorb"):
            q_abs = jnp.einsum("btnd,cnd->btnc", q[..., :Dn], w_kvb[..., :Dn])
            ql = jnp.concatenate(
                [q_abs, q_pe, jnp.zeros((B, T, NH, pad), q_abs.dtype)], axis=-1
            )                                      # [B, T, NH, page_width]
            o_lat = attend(
                ql, row, None, positions=positions, valid_len=valid_len,
                scale=scale, pages=pages, layer=layer,
                page_table=page_table, past_len=past_len,
                win_rows=win_rows, win_len=win_len, value_width=Rkv,
                use_pallas=use_pallas,
            )
            o = jnp.einsum("btnc,cnd->btnd", o_lat, w_kvb[..., Dn:])
    return (
        o.reshape(B, T, NH * Dv) @ _w(lp, "wo", x.dtype), row,
        None if index is None else index.k,
    )


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float):
    """LayerNorm with scale and bias over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (
        xf * w.astype(jnp.float32) + b.astype(jnp.float32)
    ).astype(x.dtype)


def _indexer(cfg: ModelConfig, lp, x, c_q, positions, pages=None,
             win=None) -> Indexer:
    """A latent layer's indexer over a chunk (``ModelConfig.index_topk``):

        q_I = c_q W_Iqb -> [B, T, NHi, Di]   from the normed query latent
        k_I = LayerNorm(x W_Ik) -> [B, T, Di]          ONE key a token
        rope on the first qk_rope_head_dim of q_I and k_I (interleaved)
        w   = x W_Iw / sqrt(NHi * Di) -> [B, T, NHi]   float32

    ``k_I``, in the dtype the cache keeps, is what later queries score
    against, so the chunk's own queries score against that too.
    ``pages`` / ``win``: the index pool and a fused window's pending
    keys, handed on to the attention."""
    B, T = x.shape[:2]
    NHi, Di, Dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim

    def turned(a):
        return jnp.concatenate([
            apply_rope_interleaved(a[..., :Dr], positions, cfg.rope_theta),
            a[..., Dr:],
        ], axis=-1)

    q_i = (c_q @ _w(lp, "w_iqb", x.dtype)).reshape(B, T, NHi, Di)
    k_i = layer_norm(
        x @ _w(lp, "w_ik", x.dtype), lp["ik_norm"], lp["ik_bias"],
        cfg.index_norm_eps,
    )
    w = (x @ _w(lp, "w_iw", x.dtype)).astype(jnp.float32) * (
        (NHi * Di) ** -0.5
    )
    return Indexer(
        q=turned(q_i), w=w, k=turned(k_i), topk=cfg.index_topk, pages=pages,
        win=win,
    )


def causal_taps(ext: jax.Array, taps: jax.Array, T: int) -> jax.Array:
    """The depthwise causal conv of a chunk in float32: ``ext`` [B, K-1+T,
    C] = the K-1 columns before the chunk, then its T; ``taps`` [C, K];
    ``out[:, t] = sum_j taps[:, j] * ext[:, t + j]``."""
    taps = taps.astype(jnp.float32)
    return sum(
        ext[:, j : j + T].astype(jnp.float32) * taps[:, j]
        for j in range(taps.shape[1])
    )


def columns_after(ext: jax.Array, n: jax.Array, K1: int) -> jax.Array:
    """``ext[b, n[b] : n[b] + K1]``: the conv columns a row keeps after
    ``n`` [B] of the chunk's tokens."""
    cols = n[:, None] + jnp.arange(K1, dtype=jnp.int32)
    return jnp.take_along_axis(ext, cols[..., None], axis=1)


#: batch rows that a fused window's buffers keep together as an axis of
#: their own: a tile of the TPU's HBM layout is 8 rows of float32 and 16
#: of bfloat16, so with 16 the tile is a part of the SHAPE and no layout
#: assignment can put the step axis into it
WINDOW_ROWS = 16


def window_buffer(
    tokens: int, layers: int, batch: int, width: int, dtype
) -> jax.Array:
    """Zeros for ``tokens`` tokens of ``layers`` state layers as a fused
    window's scan carries them: STEP-MAJOR, ``[tokens * layers * batch /
    R, R, width]`` with ``R = gcd(batch, WINDOW_ROWS)`` rows of a batch
    together: token ``i`` of layer ``l`` is the ``batch / R`` leading
    rows from ``(i * layers + l) * batch / R`` (``window_slab``), a
    step's token of every layer one dense slab of ``layers`` such
    (``window_put``). The step, layer and batch-tile axes are carried as
    ONE: XLA assigns a carried buffer's physical layout from what reads
    it after the scan, and an axis of its own for the step has been put
    among a tile's rows, where a step's write touches every tile of the
    buffer."""
    R = math.gcd(batch, WINDOW_ROWS)
    return jnp.zeros((tokens * layers * batch // R, R, width), dtype)


def window_put(buf: jax.Array, step, rows: jax.Array) -> jax.Array:
    """Token ``step`` of every layer (``rows`` [L, B, width]) into a
    ``window_buffer``: one dense slab, in place."""
    R = buf.shape[1]
    rows = rows.astype(buf.dtype).reshape((-1, R, rows.shape[-1]))
    return jax.lax.dynamic_update_slice(
        buf, rows, (step * rows.shape[0], 0, 0)
    )


def window_slab(buf: jax.Array, step, layer, layers: int, batch: int):
    """Token ``step`` of state layer ``layer`` in a ``window_buffer``:
    one dense slab ``[batch / R, R, width]``, sliced where it lies and
    left in the buffer's own rows-of-R shape (``rows_as``): a reader
    brings its own token's few arrays to that shape, not each slab to
    ``[batch, width]``."""
    n = batch // buf.shape[1]
    return jax.lax.dynamic_slice_in_dim(
        buf, (step * layers + layer) * n, n, axis=0
    )


def rows_as(buf: jax.Array, a: jax.Array) -> jax.Array:
    """``a`` [B, ...] with its rows grouped as a ``window_buffer``'s are:
    [B / R, R, ...]."""
    R = buf.shape[1]
    return a.reshape((a.shape[0] // R, R) + a.shape[1:])


def chunk_tokens(a: jax.Array, layer, layers: int, batch: int) -> jax.Array:
    """One state layer's tokens ``[B, W, width]`` from what
    ``MixedChunk.ssm`` holds of a chunk: the layers' stack [L, B, W,
    width] (a verify chunk, a single step), or a fused window's buffer
    as its scan carried it (``window_buffer``, 3-D), read a slab a
    token where it lies."""
    if a.ndim == 4:
        return jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    slabs = jnp.stack([
        window_slab(a, i, layer, layers, batch)
        for i in range(a.size // (layers * batch * a.shape[-1]))
    ])                                                    # [W, B / R, R, width]
    return jnp.swapaxes(slabs.reshape(-1, batch, a.shape[-1]), 0, 1)


def window_taps(
    cols: jax.Array,   # a fused window's conv columns (``window_buffer``)
    layer, layers: int,
    q0,                # the step: its K-1 columns before are q0..q0+K-2
    own: jax.Array,    # [B, 1, C]: the step's own column
    taps: jax.Array,   # [C, K]
) -> jax.Array:
    """``causal_taps`` of a fused window's step, float32, in the window's
    own rows-of-R shape ``[B / R, R, C]`` (``rows_as``; the caller
    reshapes once it has split the channels: a ``[B, 1, C]`` made by a
    reshape is laid a row a tile on the TPU and everything after it
    runs an eighth full): a weighted sum of K columns, each a ``[B, C]``
    slab; the K-1 before the token are read from the window where they
    lie (no slice of it is re-laid), its own is the last."""
    K, B = taps.shape[1], own.shape[0]
    slabs = [
        window_slab(cols, q0 + j, layer, layers, B) for j in range(K - 1)
    ] + [rows_as(cols, own[:, 0])]
    taps = taps.astype(jnp.float32)
    return sum(
        c.astype(jnp.float32) * taps[:, j] for j, c in enumerate(slabs)
    )


def conv_mixer(
    cfg: ModelConfig,
    lp: Dict[str, Any],          # one conv layer's params
    x: jax.Array,                # [B, T, H], normed
    state: jax.Array,            # [B, K-1, H]: g of the K-1 tokens before
) -> Tuple[jax.Array, jax.Array]:
    """The gated short convolution of the LFM2 family over a chunk:

        [B | C | z] = x W_in          (thirds, in this order)
        g_t = B_t * z_t
        c_t = sum_j w_conv[:, j] * g_{t-(K-1)+j}     causal, depthwise
        out_t = (C_t * c_t) W_out

    ``state`` holds g of the K-1 positions before the chunk (zeros at a
    sequence's start). Returns ``(out [B, T, H], g_ext [B, K-1+T, H])``
    with ``g_ext = [state, g_1..g_T]``: the state after n <= T tokens is
    ``g_ext[:, n : n+K-1]``, so a caller commits ANY accepted length by
    a gather. Products in the activation dtype, the K-tap sum in
    float32. Padding tokens sit after the valid ones and the conv is
    causal, so they need no mask."""
    T, H = x.shape[1], x.shape[2]
    K = cfg.conv_kernel
    bcz = x @ _w(lp, "w_in", x.dtype)
    g = bcz[..., :H] * bcz[..., 2 * H:]
    g_ext = jnp.concatenate([state.astype(g.dtype), g], axis=1)
    c = causal_taps(g_ext, lp["w_conv"], T)               # taps [H, K]
    y = bcz[..., H : 2 * H] * c.astype(x.dtype)
    return y @ _w(lp, "w_out", x.dtype), g_ext


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------


def per_channel(per_head: jax.Array, head_dim: int) -> jax.Array:
    """[..., Hm] -> [..., Hm * head_dim]: a head's value at each of its
    channels (the state's minor axis is the channels, fused)."""
    return jnp.repeat(per_head, head_dim, axis=-1)


def ssd_chunked(
    x: jax.Array,    # [B, T, Hm, P] float32
    dt: jax.Array,   # [B, T, Hm]    float32, 0 past a row's valid_len
    dA: jax.Array,   # [B, T, Hm]    float32 = dt * A (<= 0)
    Bm: jax.Array,   # [B, T, G, N]  float32: a group's heads share it
    Cm: jax.Array,   # [B, T, G, N]  float32
    S0: jax.Array,   # [B, N, Hm, P] float32: the state before the chunk
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence ``S_t = exp(dA_t) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t`` over T tokens in chunks of ``chunk``: within a
    chunk the masked ``C B^T`` product, between chunks the carried
    state. Returns ``(y [B, T, Hm, P], S_T)``. A token with ``dt`` 0
    neither decays nor feeds the state, so ``S_T`` is the state after a
    row's valid tokens when its padding has ``dt`` 0."""
    B, T, Hm, P = x.shape
    G, N = Bm.shape[2:]
    K = Hm // G                                   # heads a group
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, dA, Bm, Cm = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, dA, Bm, Cm)
        )
    nc = (T + pad) // Q

    def split(a, tail):  # [B, nc*Q, ...] -> [nc, B, Q, *tail]
        return jnp.moveaxis(a.reshape((B, nc, Q) + tail), 1, 0)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def step(S, c):
        x, dt, dA, Bm, Cm = c      # x [B,Q,G,K,P]; dt, dA [B,Q,G,K]
        cum = jnp.cumsum(dA, axis=1)
        # w[t, s] = exp(cum_t - cum_s) dt_s (C_t . B_s), s <= t
        seg = cum[:, :, None] - cum[:, None, :]               # [B, t, s, G, K]
        decay = jnp.where(
            tri[None, :, :, None, None], jnp.exp(jnp.minimum(seg, 0.0)), 0.0
        )
        g = jnp.einsum("btgn,bsgn->btsg", Cm, Bm, precision=_HI)
        w = g[..., None] * decay * dt[:, None]
        y = jnp.einsum("btsgk,bsgkp->btgkp", w, x, precision=_HI)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "btgn,bngkp->btgkp", Cm, S, precision=_HI
        )
        left = jnp.exp(cum[:, -1:] - cum) * dt                # [B, Q, G, K]
        S = jnp.exp(cum[:, -1])[:, None, :, :, None] * S + jnp.einsum(
            "bsgn,bsgkp->bngkp", Bm, left[..., None] * x, precision=_HI
        )
        return S, y

    S, y = jax.lax.scan(
        step, S0.reshape(B, N, G, K, P),
        (split(x, (G, K, P)), split(dt, (G, K)), split(dA, (G, K)),
         split(Bm, (G, N)), split(Cm, (G, N))),
    )
    y = jnp.moveaxis(y, 0, 1).reshape(B, nc * Q, Hm, P)
    return y[:, :T], S.reshape(B, N, Hm, P)


def ssd_pending(
    cfg: ModelConfig,
    ssm: jax.Array,    # [L_m, NS, N, I]: every mamba layer's slots,
    layer,             # and this layer's index: read in place
    slots: jax.Array,  # [B] int32
    fresh: jax.Array,  # [B] bool
    x: jax.Array,      # [B, W, I]   float32: the uncommitted tokens,
    dt: jax.Array,     # [B, W, Hm]  the chunk's own among them
    dA: jax.Array,     # [B, W, Hm]
    Bm: jax.Array,     # [B, W, G*N]
    Cq: jax.Array,     # [B, T, G*N]: C of the chunk's own tokens
    q0,                # index among the W of the chunk's first token
    *,
    window=None,       # a fused window's earlier tokens, step-major
    use_pallas: bool = False,
    kernel_mesh=None,
) -> jax.Array:
    """``y`` [B, T, I] of a short chunk whose state is NOT advanced:
    the committed state is read once, where it lies (every slot of the
    pool times its row's C, reduced over N on the major axis: no
    gather of the state, no write), and the uncommitted tokens up to
    each query enter through the masked ``C B^T`` product. Tokens after
    a query are masked out, so buffers may hold anything there.

    Inside a fused window (``window`` set) the chunk is ONE token, the
    window's step ``q0``, and ``x, dt, dA, Bm`` hold it ALONE (W = 1):
    the CALLER owns the step's token and never places it among the
    window's. ``window`` = ``(dt, dA, x, B, layers)``: the buffers as the
    scan carries them, step-major (``window_buffer``: ``dt, dA`` of Hm
    float32, ``x`` of I, ``B`` of G*N in the activation dtype). The
    EARLIER tokens are read from them a ``[B, width]`` slab at a time,
    where they lie (``window_slab``), by a loop of ``q0`` turns: rows at
    and past ``q0`` are never read and may hold anything; a slab is
    converted inside the sums that consume it: no float32 copy of a
    buffer, no token placed in one (``_ssd_window_step``).

    The state's read is the Pallas kernel of ops/pallas_ssm.py where
    the caller runs its kernels (``use_pallas``), no mesh shards the
    call (the pool is replicated under one and XLA cannot partition a
    Mosaic call) and the shapes pass its static gate; the XLA
    expression below otherwise, which is also what the kernel is held
    to. ``ops/lowering.ssm_state_read_counts()`` says which a process
    traced."""
    B, W, I = x.shape
    T = Cq.shape[1]
    Hm, P, G = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups
    if window is not None:
        assert T == W == 1, (T, W)
        y, cum_q = _ssd_window_step(
            cfg, x, dt, dA, Bm, Cq, window, layer, q0
        )
        return y + _ssd_committed(
            cfg, ssm, layer, slots, fresh, Cq, cum_q,
            use_pallas=use_pallas, kernel_mesh=kernel_mesh,
        )
    cum = jnp.cumsum(dA, axis=1)                              # [B, W, Hm]
    cum_q = jax.lax.dynamic_slice_in_dim(cum, q0, T, axis=1)  # [B, T, Hm]
    seen = (
        jnp.arange(W, dtype=jnp.int32)[None, :]
        <= q0 + jnp.arange(T, dtype=jnp.int32)[:, None]
    )                                                         # [T, W]
    seg = cum_q[:, :, None, :] - cum[:, None, :, :]           # [B, T, W, Hm]
    decay = jnp.where(
        seen[None, :, :, None], jnp.exp(jnp.minimum(seg, 0.0)), 0.0
    )
    g = jnp.einsum(
        "btgn,bsgn->btsg",
        Cq.reshape(B, T, G, -1), Bm.reshape(B, W, G, -1), precision=_HI,
    )
    w = jnp.repeat(g, Hm // G, axis=-1) * decay * dt[:, None, :, :]
    y = jnp.einsum(
        "btsh,bshp->bthp", w, x.reshape(B, W, Hm, P), precision=_HI
    ).reshape(B, T, I)
    return y + _ssd_committed(
        cfg, ssm, layer, slots, fresh, Cq, cum_q,
        use_pallas=use_pallas, kernel_mesh=kernel_mesh,
    )


def _ssd_committed(
    cfg: ModelConfig, ssm, layer, slots, fresh, Cq, cum_q, *,
    use_pallas: bool, kernel_mesh,
) -> jax.Array:
    """``ssd_pending``'s share of the COMMITTED state [B, T, I]: each
    row's slot times its C, decayed by ``cum_q`` [B, T, Hm], the
    log-decay from the state to each query; 0 for a fresh row."""
    B, T = Cq.shape[:2]
    I = ssm.shape[-1]
    P, G = cfg.mamba_head_dim, cfg.mamba_groups
    if (
        use_pallas and kernel_mesh is None
        and pallas_ssm.state_read_supported(ssm, Cq, G)
    ):
        # a row's slot streamed once, the groups inside its block
        by_row = pallas_ssm.ssm_state_read(ssm, layer, slots, Cq, groups=G)
    else:
        if use_pallas:
            lowering.record_reference(lowering.SSM_STATE_READ)
        pool = ssm[layer]                                     # [NS, N, I]
        NS = pool.shape[0]
        # the committed state: slot-major, so each slot takes ITS row's C
        row = jnp.zeros((NS,), jnp.int32).at[slots].set(
            jnp.arange(B, dtype=jnp.int32)
        )
        Cs = Cq[row]                                          # [NS, T, G*N]
        state = pool.astype(jnp.float32)
        yS = jnp.stack([
            jnp.concatenate([
                jnp.sum(state[..., ch] * c, axis=1)
                for ch, c in zip(group_channels(I, G), over_state(Cs[:, t], G))
            ], axis=-1)
            for t in range(T)
        ], axis=1)                                            # [NS, T, I]
        by_row = yS[slots]
    inter = per_channel(jnp.exp(cum_q), P) * by_row
    return jnp.where(fresh[:, None, None], 0.0, inter)


def _ssd_window_step(cfg: ModelConfig, x, dt, dA, Bm, Cq, window, layer, q0):
    """``ssd_pending`` for a fused window's step: ``(y [B, 1, I] of the
    uncommitted tokens, cum_q [B, 1, Hm])``. The step's own token
    (``x, dt, dA, Bm`` [B, 1, ...]) decays by nothing; token ``i < q0``
    by ``exp(min(seg_i, 0))``, ``seg_i = dA_own + sum_{i<j<q0} dA_j``
    summed from the step backwards (never a difference of cumulative
    sums); ``cum_q`` is the sum over all of them, the decay since the
    committed state. The loop runs ``q0`` times, a slab of each buffer
    an iteration, each read once and none past the step."""
    B, _, I = x.shape
    Hm, P, G = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups
    f32 = jnp.float32
    dtw, dAw, xw, Bw, layers = window
    Cg = rows_as(dtw, Cq.reshape(B, G, -1))                   # [n, R, G, N]

    def weight(Bs, decay_dt):  # [n, R, G*N], [n, R, Hm] -> [n, R, Hm, 1]
        g = jnp.sum(Cg * Bs.astype(f32).reshape(Cg.shape), axis=-1)
        return (jnp.repeat(g, Hm // G, axis=-1) * decay_dt)[..., None]

    def per_head(a):  # [n, R, I] -> [n, R, Hm, P]
        return a.astype(f32).reshape(a.shape[:2] + (Hm, P))

    def earlier(j, carry):
        seg, y = carry
        dti, dAi, xi, Bi = (
            window_slab(b, q0 - 1 - j, layer, layers, B)
            for b in (dtw, dAw, xw, Bw)
        )
        w = weight(Bi, jnp.exp(jnp.minimum(seg, 0.0)) * dti)
        return seg + dAi, y + w * per_head(xi)

    Bo, dto, xo, dAo = (rows_as(dtw, a[:, 0]) for a in (Bm, dt, x, dA))
    seg, y = jax.lax.fori_loop(
        0, q0, earlier, (dAo, weight(Bo, dto) * per_head(xo))
    )
    return y.reshape(B, 1, I), seg.reshape(B, 1, Hm)


def over_state(c: jax.Array, groups: int):
    """A token's B (or C), [NS, G*N], as it multiplies a state laid out
    [NS, N, I]: a list of factors [NS, N, 1], one a group, each for the
    channels that group's heads hold (``group_channels``). A caller
    multiplies the state a group's channels at a time and joins the
    pieces: repeated out to the channels instead, the factor is a
    float32 copy the size of a layer's pool beside the pool's own at 8
    groups (0.54 GB at 257 slots), and seen ``[..., G, I / G]`` the
    state is re-tiled, which is a copy too."""
    c = jnp.swapaxes(c.reshape(c.shape[0], groups, -1), 1, 2)  # [NS, N, G]
    return [c[..., g : g + 1] for g in range(groups)]


def group_channels(inner: int, groups: int):
    """The slice of the I channels each group's heads hold."""
    w = inner // groups
    return [slice(g * w, (g + 1) * w) for g in range(groups)]


def grouped_rms(y: jax.Array, groups: int, eps: float) -> jax.Array:
    """``y`` [..., I] over the root of its mean square, the mean taken
    over one of ``groups`` runs of ``I / groups`` channels at a time
    (all of ``I`` at one group: the plain expression, no reshape)."""
    if groups == 1:
        return y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    g = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(y.shape)


def mamba_mixer(
    cfg: ModelConfig,
    lp: Dict[str, Any],          # one mamba layer's params
    u: jax.Array,                # [B, T, H], normed
    *,
    valid_len: jax.Array,        # [B]
    past: StatePast,
    layer,                       # this layer's index among the mamba layers
    pending: bool,
    use_pallas: bool = False,
    kernel_mesh=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One Mamba-2 mixer over a chunk (``ModelConfig.mamba_*``):

        [z | xBC] = u W_in ;  dt = u W_dt
        xBC = silu(conv1d(xBC) + b)      causal, depthwise, K taps
        [x | B | C] = xBC
        dt = softplus(dt + dt_bias),  A = -exp(a_log)   a head
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T ;  y_t = S_t C_t + D x_t
        out = (RMSNorm(y * silu(z)) * w_norm) W_out

    The norm's mean is taken over a GROUP's ``I / mamba_groups``
    channels at a time (all of ``I`` at one group). The recurrence and
    the gate norm in float32. Tokens past a row's
    ``valid_len`` get ``dt`` 0, so the state after the chunk is the
    state after ``valid_len`` tokens. ``pending`` False (prefill): the
    chunked scan from the row's state (gathered from its slot) to the
    state after the chunk, returned as "final". ``pending`` True (a
    decode step, a verify chunk): the state is read in place and not
    advanced (``ssd_pending``); the tokens' dt, dA, x, B are returned
    and ``kvcache.write_kv`` commits the accepted ones."""
    Bsz, T = u.shape[:2]
    I, Cd, Hm = cfg.mamba_inner, cfg.mamba_conv_dim, cfg.mamba_heads
    P, G, N = cfg.mamba_head_dim, cfg.mamba_groups, cfg.mamba_state
    K = cfg.mamba_conv
    f32 = jnp.float32
    zx = u @ _w(lp, "w_in", u.dtype)
    z, xbc = zx[..., :I], zx[..., I:]
    dt = u @ _w(lp, "w_dt", u.dtype)
    if past.window is not None:
        # a fused window's step: ONE token, the columns before it read
        # where they lie; it leaves its own column alone
        assert T == 1 and pending, (T, pending)
        ext = xbc
        c = window_taps(
            past.conv, layer, cfg.num_state_layers, past.window[-1], xbc,
            lp["w_conv"],
        ).reshape(Bsz, T, Cd)
    else:
        ext = jnp.concatenate(
            [past.conv[layer].astype(xbc.dtype), xbc], axis=1
        )
        c = causal_taps(ext, lp["w_conv"], T)                 # taps [Cd, K]
    xbc = jax.nn.silu(c + lp["b_conv"].astype(f32))           # [B, T, Cd] f32
    x, Bm, Cm = xbc[..., :I], xbc[..., I : I + G * N], xbc[..., I + G * N :]
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
    dt = jnp.where(live[..., None], dt, 0.0)
    dA = dt * -jnp.exp(lp["a_log"].astype(f32))
    out: Dict[str, jax.Array] = {}
    if pending:
        out["ssm_conv"] = ext
        cur = {"ssm_dt": dt, "ssm_dA": dA, "ssm_x": x.astype(u.dtype),
               "ssm_B": Bm.astype(u.dtype)}
        out.update(cur)
        window = None
        if past.window is not None:
            # the window's earlier tokens are read where they lie; the
            # step's own token stays out of them
            *bufs, q0 = past.window
            window = (*bufs, cfg.num_state_layers)
        y = ssd_pending(
            cfg, past.ssm, layer, past.slots, past.fresh,
            x, dt, dA, Bm, Cm, 0 if window is None else q0, window=window,
            use_pallas=use_pallas, kernel_mesh=kernel_mesh,
        )
    else:
        S0 = past.ssm[layer][past.slots].astype(f32)          # [B, N, I]
        S0 = jnp.where(past.fresh[:, None, None], 0.0, S0)
        y, S = ssd_chunked(
            x.reshape(Bsz, T, Hm, P), dt, dA,
            Bm.reshape(Bsz, T, G, N), Cm.reshape(Bsz, T, G, N),
            S0.reshape(Bsz, N, Hm, P), cfg.mamba_chunk,
        )
        y = y.reshape(Bsz, T, I)
        out["ssm_final"] = S.reshape(Bsz, N, I)
        out["ssm_conv"] = columns_after(ext, valid_len, K - 1)
    y = y + per_channel(lp["d_skip"].astype(f32), P) * x
    y = grouped_rms(y * jax.nn.silu(z.astype(f32)), G, cfg.norm_eps)
    y = (y * lp["gate_norm"].astype(f32)).astype(u.dtype)
    return y @ _w(lp, "w_out", u.dtype), out


# ---------------------------------------------------------------------------
# Mamba-1 and the gated memory unit
# ---------------------------------------------------------------------------


def mamba1_decay(a_log: jax.Array) -> jax.Array:
    """``A = -exp(a_log)`` in float32, ``[.., N, I]`` as the state lies."""
    return -jnp.exp(a_log.astype(jnp.float32))


def selective_scan(
    x: jax.Array,    # [B, T, I] float32
    dt: jax.Array,   # [B, T, I] float32, 0 past a row's valid_len
    A: jax.Array,    # [N, I]    float32 (< 0)
    Bm: jax.Array,   # [B, T, N] float32: every channel shares it
    Cm: jax.Array,   # [B, T, N] float32
    S0: jax.Array,   # [B, N, I] float32: the state before the chunk
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Mamba-1's recurrence ``S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t)
    B_t^T``, ``y_t = S_t C_t`` over T tokens, ``chunk`` tokens at a
    time: inside a chunk an associative scan of the pairs ``(decay,
    input)`` (``[B, chunk, N, I]`` float32 is all it holds: the whole
    ``[T, N, I]`` of a 1,024-token prompt would be 335 MB a row a
    layer), between chunks the carried state. The decay is a value a
    channel AND a state column, so there is no ``C B^T`` form over
    heads (``ssd_chunked``'s). Returns ``(y [B, T, I], S_T)``. A token
    with ``dt`` 0 neither decays nor feeds the state."""
    B, T, I = x.shape
    N = A.shape[0]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, Bm, Cm = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (x, dt, Bm, Cm)
        )
    nc = (T + pad) // Q

    def split(a):  # [B, nc*Q, W] -> [nc, B, Q, W]
        return jnp.moveaxis(a.reshape(B, nc, Q, a.shape[-1]), 1, 0)

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def step(S, c):
        x, dt, Bm, Cm = c
        decay = jnp.exp(dt[:, :, None, :] * A)                # [B, Q, N, I]
        fed = (dt * x)[:, :, None, :] * Bm[..., None]
        decay, fed = jax.lax.associative_scan(combine, (decay, fed), axis=1)
        states = decay * S[:, None] + fed
        y = jnp.sum(states * Cm[..., None], axis=2)           # [B, Q, I]
        return states[:, -1], y

    S, y = jax.lax.scan(step, S0, tuple(split(a) for a in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, nc * Q, I)
    return y[:, :T], S


def mamba1_mixer(
    cfg: ModelConfig,
    lp: Dict[str, Any],          # one mamba1 layer's params
    u: jax.Array,                # [B, T, H], normed
    *,
    valid_len: jax.Array,        # [B]
    past: StatePast,
    layer,                       # this layer's index among the mamba1 layers
    pending: bool,
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """One Mamba-1 mixer over a chunk (``ModelConfig.mamba1_*``):

        [x | z] = u W_in
        x = silu(conv1d(x) + b)          causal, depthwise, K taps
        [r | B | C] = x W_x ;  dt = softplus(r W_dt + dt_bias)   a channel
        A = -exp(a_log)                  a channel and a state column
        S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t) B_t^T ;  y_t = S_t C_t + D x_t
        out = (y * silu(z)) W_out

    Returns ``(out, what commits the state, y)``: ``y`` (with the ``D
    x`` term, before the gate) is what a gated memory unit reads when
    this is the model's ``memory_layer``. The recurrence in float32.
    Tokens past a row's ``valid_len`` get ``dt`` 0. Three forms, as
    ``mamba_mixer``'s: ``pending`` False (prefill): ``selective_scan``
    from the row's slot to the state after the chunk, "final".
    ``pending`` True (a decode step, a verify chunk): the state is
    gathered from the slot and stepped a token at a time WITHOUT being
    written; the tokens' ``dt``, ``x`` and ``B`` (and the layer's ``A``)
    are returned and ``kvcache.write_kv`` commits the accepted ones.
    Inside a fused window (``past.window`` set) the step's state comes
    from ``past.running``, the rows' state after the window's earlier
    tokens as the scan carries it in float32, and the state after this
    token goes back as "S": the pool is read once a window, not once a
    step, and a decay a channel a column of every earlier token (an
    ``exp`` a state element a token: 0.8 G a step at 128 rows) is never
    formed. The commit still runs from the pool and the tokens."""
    Bsz, T = u.shape[:2]
    I, N, K = cfg.mamba1_inner, cfg.mamba1_state, cfg.mamba1_conv
    R = cfg.mamba1_dt_rank
    f32 = jnp.float32
    xz = u @ _w(lp, "w_in", u.dtype)
    x, z = xz[..., :I], xz[..., I:]
    windowed = past.window is not None
    if windowed:
        # a fused window's step: ONE token, the columns before it read
        # where they lie; it leaves its own column alone
        assert T == 1 and pending, (T, pending)
        ext = x
        c = window_taps(
            past.conv, layer, cfg.num_state_layers, past.window[-1], x,
            lp["w_conv"],
        ).reshape(Bsz, T, I)
    else:
        ext = jnp.concatenate([past.conv[layer].astype(x.dtype), x], axis=1)
        c = causal_taps(ext, lp["w_conv"], T)                 # taps [I, K]
    x = jax.nn.silu(c + lp["b_conv"].astype(f32))             # [B, T, I] f32
    xa = x.astype(u.dtype)
    rbc = xa @ _w(lp, "w_x", u.dtype)
    Bm, Cm = rbc[..., R : R + N], rbc[..., R + N :]
    dt = rbc[..., :R] @ _w(lp, "w_dt", u.dtype)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
    dt = jnp.where(live[..., None], dt, 0.0)
    A = mamba1_decay(lp["a_log"])                             # [N, I]
    out: Dict[str, jax.Array] = {}

    def before():  # the rows' committed state, float32; 0 for a fresh row
        S0 = past.ssm[layer][past.slots].astype(f32)          # [B, N, I]
        return jnp.where(past.fresh[:, None, None], 0.0, S0)

    if pending:
        lowering.record_mamba1("window" if windowed else "pending")
        # the state advances by what the commit will read: the tokens'
        # x and B as the buffers keep them
        Bc = Bm.astype(u.dtype)
        out.update({
            "ssm_conv": ext, "ssm_dt": dt, "ssm_x": xa, "ssm_B": Bc,
            "ssm_A": A,
        })
        with jax.named_scope("mamba1_state_step"):
            def token(S, t):
                dt_t, x_t, B_t, C_t = t
                S = jnp.exp(dt_t[:, None, :] * A) * S + (
                    (dt_t * x_t)[:, None, :] * B_t[:, :, None]
                )
                return S, jnp.sum(S * C_t[:, :, None], axis=1)

            S = past.running[layer] if windowed else before()
            tokens = tuple(
                jnp.moveaxis(a.astype(f32), 1, 0)
                for a in (dt, xa, Bc, Cm)
            )
            if T == 1:
                S, y = token(S, tuple(a[0] for a in tokens))
                y = y[:, None]
            else:
                S, y = jax.lax.scan(token, S, tokens)
                y = jnp.moveaxis(y, 0, 1)
            if windowed:
                out["ssm_S"] = S
    else:
        lowering.record_mamba1("chunked")
        with jax.named_scope("mamba1_scan"):
            y, S = selective_scan(
                x, dt, A, Bm.astype(f32), Cm.astype(f32), before(),
                cfg.mamba1_chunk,
            )
        out["ssm_final"] = S
        out["ssm_conv"] = columns_after(ext, valid_len, K - 1)
    y = y + lp["d_skip"].astype(f32) * x
    gated = (y * jax.nn.silu(z.astype(f32))).astype(u.dtype)
    return gated @ _w(lp, "w_out", u.dtype), out, y.astype(u.dtype)


def running_state(cfg: ModelConfig, past: Optional[StatePast]):
    """What a fused window's scan carries of the state layers' state
    beside their tokens (``StatePast.running``): for Mamba-1 layers the
    rows' states ``[L, B, N, I]`` in float32, gathered from their slots
    ONCE a window (0 for a fresh row); None for every other kind, whose
    steps read the committed state where it lies."""
    if cfg.state_kind != "mamba1" or past is None:
        return None
    S = past.ssm[:, past.slots].astype(jnp.float32)
    return jnp.where(past.fresh[None, :, None, None], 0.0, S)


def memory_unit(lp: Dict[str, Any], u: jax.Array, m: jax.Array) -> jax.Array:
    """A gated memory unit: ``(m * silu(u W_1)) W_2``, ``m`` the
    ``memory_layer``'s scan output for the same tokens."""
    gate = jax.nn.silu((u @ _w(lp, "w_in", u.dtype)).astype(jnp.float32))
    return (m.astype(jnp.float32) * gate).astype(u.dtype) @ _w(
        lp, "w_out", u.dtype
    )


# ---------------------------------------------------------------------------
# Kimi Delta Attention (a gated delta rule)
# ---------------------------------------------------------------------------


def pending_buffers(cfg: ModelConfig, act) -> Tuple[Tuple[str, int, Any], ...]:
    """``(name, width, dtype)`` of what a state layer's token leaves for
    a chunk whose accepted length is decided later (``MixedChunk.ssm``'s
    keys beside "conv"; the fused window's buffers; ``StatePast.window``
    in this order), by the model's ``state_kind``. A verify chunk stacks
    them over its layers ([L, B, T, width]); a fused window carries one
    ``window_buffer`` of each, step-major, into which the RUNNER puts a
    step's token after the step (``window_put``): a mixer reads the
    earlier tokens there and never its own."""
    f32 = jnp.float32
    if cfg.state_kind == "kda":
        return (("g", cfg.kda_inner, f32), ("k", cfg.kda_inner, act),
                ("u", cfg.kda_inner, act))
    if cfg.state_kind == "mamba1":
        return (("dt", cfg.mamba1_inner, f32), ("x", cfg.mamba1_inner, act),
                ("B", cfg.mamba1_state, act))
    return (
        ("dt", cfg.mamba_heads, f32), ("dA", cfg.mamba_heads, f32),
        ("x", cfg.mamba_inner, act),
        ("B", cfg.mamba_groups * cfg.mamba_state, act),
    )


def _pair_products(rows, ks: jax.Array, Ga: jax.Array, G: jax.Array):
    """For each ``a`` of ``rows``: ``out[b, t, i, h] = sum_d a[b, t, h, d]
    ks[b, i, h, d] exp(min(Ga[b, t, h, d] - G[b, i, h, d], 0))``: every
    decay formed PAIRWISE (at most 1 for i <= t, which is all a caller
    keeps), never a quotient of cumulative decays: the log-decay has no
    lower bound. The decayed keys are formed once for all of ``rows``."""
    seg = Ga[:, :, None] - G[:, None, :]                  # [B, T, W, H, dk]
    decayed = ks[:, None, :] * jnp.exp(jnp.minimum(seg, 0.0))
    return [jnp.sum(a[:, :, None] * decayed, axis=-1) for a in rows]


def _unit_lower_solve(A: jax.Array, rhs: jax.Array) -> jax.Array:
    """``(I + A)^-1 rhs`` for strictly lower ``A`` [B, t, i, H] and
    ``rhs`` [B, t, H, dv]: one forward substitution a head."""
    T = A.shape[1]
    if T == 1:
        return rhs
    M = jnp.moveaxis(A, 3, 1) + jnp.eye(T, dtype=A.dtype)  # [B, H, t, i]
    U = jax.scipy.linalg.solve_triangular(
        M, jnp.moveaxis(rhs, 2, 1), lower=True, unit_diagonal=True
    )
    return jnp.moveaxis(U, 1, 2)


def kda_chunked(
    q: jax.Array,     # [B, T, H, dk] float32 (normed, scaled)
    k: jax.Array,     # [B, T, H, dk] float32 (normed)
    v: jax.Array,     # [B, T, H, dv] float32
    g: jax.Array,     # [B, T, H, dk] float32 log-decay (<= 0; 0 past valid_len)
    beta: jax.Array,  # [B, T, H]     float32 (0 past valid_len)
    S0: jax.Array,    # [B, dk, H, dv] float32: the state before the chunk
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
    + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` over T tokens in chunks of
    ``chunk``: with ``G`` the log-decay summed inside the chunk,

        A[t, i] = beta_t sum_d k_t k_i exp(G_t - G_i)          i < t
        U = (I + A)^-1 Diag(beta) (V - (K * exp G) S_0)
        o_t = (q_t * exp G_t)^T S_0 + sum_{i<=t} (q_t . k_i)_decayed u_i
        S = Diag(exp G_Q) S_0 + sum_i (k_i * exp(G_Q - G_i)) u_i^T

    Returns ``(o [B, T, H, dv], S_T)``. A token with ``g`` 0 and ``beta``
    0 neither decays nor feeds the state (its ``u`` is 0), so ``S_T`` is
    the state after a row's valid tokens when its padding has both 0."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    nc = (T + pad) // Q

    def split(a):  # [B, nc*Q, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(a.reshape((B, nc, Q) + a.shape[2:]), 1, 0)

    t_i = jnp.arange(Q)
    before = (t_i[None, :] < t_i[:, None])[None, :, :, None]    # i < t
    upto = (t_i[None, :] <= t_i[:, None])[None, :, :, None]     # i <= t

    def step(S, c):
        q, k, v, g, beta = c
        G = jnp.cumsum(g, axis=1)                               # [B, Q, H, dk]
        eG = jnp.exp(G)
        kk, qk = _pair_products((k, q), k, G, G)
        A = jnp.where(before, kk, 0.0) * beta[:, :, None, :]
        kS = jnp.einsum("bthk,bkhv->bthv", k * eG, S, precision=_HI)
        U = _unit_lower_solve(A, beta[..., None] * (v - kS))
        qk = jnp.where(upto, qk, 0.0)
        o = jnp.einsum("bthk,bkhv->bthv", q * eG, S, precision=_HI) + (
            jnp.einsum("btih,bihv->bthv", qk, U, precision=_HI)
        )
        left = k * jnp.exp(G[:, -1:] - G)                       # [B, Q, H, dk]
        S = jnp.moveaxis(eG[:, -1], 1, 2)[..., None] * S + jnp.einsum(
            "bihk,bihv->bkhv", left, U, precision=_HI
        )
        return S, o

    with jax.named_scope("kda_chunk"):
        S, o = jax.lax.scan(
            step, S0, (split(q), split(k), split(v), split(g), split(beta))
        )
    o = jnp.moveaxis(o, 0, 1).reshape(B, nc * Q, H, dv)
    return o[:, :T], S


def kda_state_read(
    cfg: ModelConfig,
    pool: jax.Array,   # [L_k, NS, dk, I]: every kda layer's slots,
    layer,             # and this layer's index: read in place
    slots: jax.Array,  # [B] int32
    c: jax.Array,      # [B, T, I] float32: a row a token, a head's dk
    *,
    use_pallas: bool = False,
    kernel_mesh=None,
) -> jax.Array:
    """``out[b, t, h, :] = S_h^T c[b, t, h, :]`` [B, T, I] float32 with
    ``S`` the row's committed state: ``ops/pallas_ssm.ssm_state_read``'s
    body with a HEAD a group (a head's state is the block's
    ``[dk, dv]`` lane range) where the caller runs its kernels, no mesh
    shards the call and the shapes pass its static gate; a gather of the
    rows' slots and one product otherwise.
    ``ops/lowering.kda_state_read_counts()`` says which a process
    traced."""
    B, T, I = c.shape
    H = cfg.kda_heads
    with jax.named_scope("kda_state_read"):
        if (
            use_pallas and kernel_mesh is None
            and pallas_ssm.state_read_supported(pool, c, H)
        ):
            return pallas_ssm.ssm_state_read(
                pool, layer, slots, c, groups=H,
                counted=lowering.KDA_STATE_READ,
            )
        if use_pallas:
            lowering.record_reference(lowering.KDA_STATE_READ)
        S = pool[layer][slots].astype(jnp.float32)            # [B, dk, I]
        return jnp.einsum(
            "bthk,bkhv->bthv", c.reshape(B, T, H, -1),
            S.reshape(B, S.shape[1], H, -1), precision=_HI,
        ).reshape(B, T, I)


def _kda_window_step(
    cfg: ModelConfig, pool, layer, slots, fresh, q, v, beta, g, k, window,
    q0, *, use_pallas: bool, kernel_mesh,
) -> Tuple[jax.Array, jax.Array]:
    """``kda_pending`` for a fused window's step ``q0``: ``(o, u)`` [B, 1,
    I] of the step's own token (``q, v, g, k`` [B, 1, I], ``beta`` [B, 1,
    H]) over the committed state and the window's tokens ``i < q0``:

        u = beta (v - (k * exp G)^T S_0 - sum_{i<q0} kk_i u_i)
        o = (q * exp G)^T S_0 + sum_{i<q0} qk_i u_i + (q . k) u

    ``kk_i, qk_i = sum_d (k | q) k_i exp(min(seg_i, 0))`` a head, every
    decay pairwise: ``seg_i = g + sum_{i<j<q0} g_j``, summed from the
    step backwards (never a difference or a quotient of cumulative
    decays), and ``G`` the sum over all of them, the decay since the
    committed state. Float32 throughout. The loop runs ``q0`` times, a
    slab of each buffer an iteration, each read once and none past the
    step."""
    B, _, I = q.shape
    H = cfg.kda_heads
    f32 = jnp.float32
    gw, kw, uw, layers = window

    def heads(a):
        return a.reshape(a.shape[:-1] + (H, -1))

    # the token axis stays on everything of the step's own token: a
    # [B, 1, I] made by a reshape is laid a row a tile (``window_taps``)
    qh, kh, vh = heads(q), heads(k), heads(v)                 # [B, 1, H, d]
    rows = rows_as(gw, jnp.concatenate([kh, qh], axis=1))     # [n, R, 2, H, dk]

    def earlier(j, carry):
        seg, sums = carry
        gi, ki, ui = (
            window_slab(b, q0 - 1 - j, layer, layers, B) for b in (gw, kw, uw)
        )
        decayed = heads(ki.astype(f32) * jnp.exp(jnp.minimum(seg, 0.0)))
        pair = jnp.sum(rows * decayed[:, :, None], axis=-1, keepdims=True)
        return seg + gi, sums + pair * heads(ui.astype(f32))[:, :, None]

    seg, sums = jax.lax.fori_loop(
        0, q0, earlier, (rows_as(gw, g[:, 0]), jnp.zeros_like(rows))
    )
    sums = sums.reshape(kh.shape[:1] + sums.shape[2:])        # [B, 2, H, dv]
    eG = jnp.exp(seg).reshape(kh.shape)                       # [B, 1, H, dk]
    read = kda_state_read(
        cfg, pool, layer, slots,
        jnp.concatenate([qh * eG, kh * eG], axis=1).reshape(B, 2, I),
        use_pallas=use_pallas, kernel_mesh=kernel_mesh,
    )
    read = heads(jnp.where(fresh[:, None, None], 0.0, read))  # [B, 2, H, dv]
    u = beta[..., None] * (vh - read[:, 1:] - sums[:, :1])
    o = read[:, :1] + sums[:, 1:] + (
        jnp.sum(qh * kh, axis=-1, keepdims=True) * u
    )
    return o.reshape(B, 1, I), u.reshape(B, 1, I)


def kda_pending(
    cfg: ModelConfig,
    pool: jax.Array,   # [L_k, NS, dk, I]
    layer,
    slots: jax.Array,  # [B] int32
    fresh: jax.Array,  # [B] bool
    q: jax.Array,      # [B, T, I] float32: the chunk's own tokens
    v: jax.Array,      # [B, T, I]
    beta: jax.Array,   # [B, T, H]
    gs: jax.Array,     # [B, W, I] float32: the uncommitted tokens' log-
    ks: jax.Array,     # [B, W, I] decays and keys, the chunk's own among
    us: jax.Array,     # [B, W, I] them; ``u`` of the tokens BEFORE the chunk
    q0,                # index among the W of the chunk's first token
    *,
    window=None,       # a fused window's earlier tokens, step-major
    use_pallas: bool = False,
    kernel_mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """``(o, u)`` [B, T, I] of a short chunk whose state is NOT advanced:
    the committed state ``S_0`` is read once, where it lies, for the two
    products ``(q * exp G)^T S_0`` and ``(k * exp G)^T S_0``
    (``kda_state_read``), the uncommitted tokens before the chunk enter
    through their ``(G, k, u)``, and the chunk's own ``u`` come from one
    forward substitution. Tokens after a query are masked out, so
    buffers may hold anything there.

    Inside a fused window (``window`` set) the chunk is ONE token, the
    window's step ``q0``, and ``gs, ks`` hold it ALONE (W = 1; ``us`` is
    not read): the CALLER owns the step's token and never places it
    among the window's. ``window`` = ``(g, k, u, layers)``: the buffers
    as the scan carries them, step-major (``window_buffer``: ``g`` of I
    float32, ``k, u`` of I in the activation dtype). The EARLIER tokens
    are read from them a ``[B, I]`` slab at a time, where they lie
    (``window_slab``), by a loop of ``q0`` turns: rows at and past
    ``q0`` are never read and may hold anything; a slab is converted
    inside the sums that consume it: no float32 copy of a buffer, no
    token placed in one (``_kda_window_step``)."""
    B, T, I = q.shape
    W = gs.shape[1]
    H = cfg.kda_heads

    def heads(a):
        return a.reshape(a.shape[:2] + (H, -1))

    if window is not None:
        assert T == W == 1, (T, W)
        return _kda_window_step(
            cfg, pool, layer, slots, fresh, q, v, beta, gs, ks, window, q0,
            use_pallas=use_pallas, kernel_mesh=kernel_mesh,
        )
    G = heads(jnp.cumsum(gs, axis=1))                         # [B, W, H, dk]
    Gq = jax.lax.dynamic_slice_in_dim(G, q0, T, axis=1)       # [B, T, H, dk]
    kq = jax.lax.dynamic_slice_in_dim(heads(ks), q0, T, axis=1)
    qh, vh, eG = heads(q), heads(v), jnp.exp(Gq)
    read = kda_state_read(
        cfg, pool, layer, slots,
        jnp.concatenate([qh * eG, kq * eG], axis=1).reshape(B, 2 * T, I),
        use_pallas=use_pallas, kernel_mesh=kernel_mesh,
    )
    read = heads(jnp.where(fresh[:, None, None], 0.0, read))
    qS, kS = read[:, :T], read[:, T:]
    kk, qk = _pair_products((kq, qh), heads(ks), Gq, G)       # [B, T, W, H]
    at = jnp.arange(W, dtype=jnp.int32)
    earlier = (at < q0)[None, None, :, None]
    uh = heads(us.astype(jnp.float32))
    r = vh - kS - jnp.einsum(
        "btih,bihv->bthv", jnp.where(earlier, kk, 0.0), uh, precision=_HI
    )
    t_i = jnp.arange(T)
    before = (t_i[None, :] < t_i[:, None])[None, :, :, None]
    upto = (t_i[None, :] <= t_i[:, None])[None, :, :, None]
    A = jnp.where(
        before, jax.lax.dynamic_slice_in_dim(kk, q0, T, axis=2), 0.0
    ) * beta[:, :, None, :]
    U = _unit_lower_solve(A, beta[..., None] * r)             # [B, T, H, dv]
    o = qS + jnp.einsum(
        "btih,bihv->bthv", jnp.where(earlier, qk, 0.0), uh, precision=_HI
    ) + jnp.einsum(
        "btih,bihv->bthv",
        jnp.where(upto, jax.lax.dynamic_slice_in_dim(qk, q0, T, axis=2), 0.0),
        U, precision=_HI,
    )
    return o.reshape(B, T, I), U.reshape(B, T, I)


def l2_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_mixer(
    cfg: ModelConfig,
    lp: Dict[str, Any],          # one kda layer's params
    x: jax.Array,                # [B, T, H], normed
    *,
    valid_len: jax.Array,        # [B]
    past: StatePast,
    layer,                       # this layer's index among the kda layers
    pending: bool,
    use_pallas: bool = False,
    kernel_mesh=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One Kimi Delta Attention mixer over a chunk (``ModelConfig.kda_*``):

        [q | k | v] = silu(conv1d(x W_qkv))    causal, depthwise, K taps
        q = l2norm(q) / sqrt(dk) ;  k = l2norm(k)            a head
        beta = kda_beta_scale * sigmoid(x W_beta)            a head
        g = -exp(a_log) * softplus(x W_fa W_fb + dt_bias)    a CHANNEL
        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
        out = (RMSNorm_dk(o) * w_norm * sigmoid(x W_ga W_gb + b_g)) W_out

    The recurrence, the norm and the gate in float32. Tokens past a
    row's ``valid_len`` get ``g`` 0 and ``beta`` 0, so the state after the
    chunk is the state after ``valid_len`` tokens. ``pending`` False
    (prefill): the chunk form from the row's state (gathered from its
    slot) to the state after the chunk, returned as "final". ``pending``
    True (a decode step, a verify chunk, a fused window's step): the
    state is read in place and not advanced (``kda_pending``); the
    tokens' ``g``, ``k`` and solved ``u`` are returned and
    ``kvcache.write_kv`` commits the accepted ones."""
    Bsz, T = x.shape[:2]
    I, Hk, dk = cfg.kda_inner, cfg.kda_heads, cfg.kda_head_dim
    K = cfg.kda_conv
    f32 = jnp.float32
    lowering.record_kda("pending" if pending else "chunked")
    with jax.named_scope("kda_conv"):
        qkv = x @ _w(lp, "w_qkv", x.dtype)
        if past.window is not None:
            # a fused window's step: ONE token, the columns before it
            # read where they lie; it leaves its own column alone
            assert T == 1 and pending, (T, pending)
            ext = qkv
            c = window_taps(
                past.conv, layer, cfg.num_state_layers, past.window[-1],
                qkv, lp["w_conv"],
            )
        else:
            ext = jnp.concatenate(
                [past.conv[layer].astype(qkv.dtype), qkv], axis=1
            )
            c = causal_taps(ext, lp["w_conv"], T)
        # [B, T, 3 Hk, dk] float32: a head's channels an axis of their own
        qkv = jax.nn.silu(c).reshape(Bsz, T, 3 * Hk, dk)

    def heads(a):
        return a.reshape(Bsz, T, Hk, dk)

    q = l2_norm(qkv[:, :, :Hk]) * dk ** -0.5
    k = l2_norm(qkv[:, :, Hk : 2 * Hk])
    v = qkv[:, :, 2 * Hk :].reshape(Bsz, T, I)
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
    beta = cfg.kda_beta_scale * jax.nn.sigmoid(
        (x @ _w(lp, "w_beta", x.dtype)).astype(f32)
    )
    beta = jnp.where(live[..., None], beta, 0.0)              # [B, T, Hk]
    f = (x @ _w(lp, "w_fa", x.dtype)) @ _w(lp, "w_fb", x.dtype)
    g = jax.nn.softplus(f.astype(f32) + lp["dt_bias"].astype(f32))
    g = -g * per_channel(jnp.exp(lp["a_log"].astype(f32)), dk)
    g = jnp.where(live[..., None], g, 0.0)                    # [B, T, I]
    out: Dict[str, jax.Array] = {}
    if pending:
        out["ssm_conv"] = ext
        kf = k.reshape(Bsz, T, I)
        q0, window = 0, None
        if past.window is not None:
            # the window's earlier tokens are read where they lie; the
            # step's own token stays out of them
            *bufs, q0 = past.window
            window = (*bufs, cfg.num_state_layers)
        o, u = kda_pending(
            cfg, past.ssm, layer, past.slots, past.fresh,
            q.reshape(Bsz, T, I), v, beta, g, kf, jnp.zeros_like(kf), q0,
            window=window, use_pallas=use_pallas, kernel_mesh=kernel_mesh,
        )
        out.update(
            ssm_g=g, ssm_k=kf.astype(x.dtype), ssm_u=u.astype(x.dtype)
        )
        o = heads(o)
    else:
        S0 = past.ssm[layer][past.slots].astype(f32)          # [B, dk, I]
        S0 = jnp.where(past.fresh[:, None, None], 0.0, S0)
        o, S = kda_chunked(
            q, k, heads(v), heads(g), beta,
            S0.reshape(Bsz, dk, Hk, dk), cfg.kda_chunk,
        )
        out["ssm_final"] = S.reshape(Bsz, dk, I)
        out["ssm_conv"] = columns_after(ext, valid_len, K - 1)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * lp["o_norm"].astype(f32)
    gate = (x @ _w(lp, "w_ga", x.dtype)) @ _w(lp, "w_gb", x.dtype)
    gate = jax.nn.sigmoid(gate.astype(f32) + lp["b_g"].astype(f32))
    y = (o.reshape(Bsz, T, I) * gate).astype(x.dtype)
    return y @ _w(lp, "w_out", x.dtype), out


def layer_apply(
    cfg: ModelConfig,
    lp: Dict[str, Any],          # one layer's params (leaves without L axis)
    h: jax.Array,                # [B, T, H]
    *,
    positions: jax.Array,        # [B, T]
    valid_len: jax.Array,        # [B]
    window: jax.Array,           # scalar int32
    theta: jax.Array,            # scalar fp32 RoPE base
    # paged past: the WHOLE stacked pool [L, NP, PS, KVH*Dh] (and, in
    # int8 KV mode, the stacked per-token dequant scales [L, NP, PS])
    # plus this block's index into it. Never a per-layer slice: every
    # reader indexes [layer, page] itself (ops/attention.py)
    k_pages: Optional[jax.Array] = None,
    v_pages: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,  # scalar int32
    page_table: Optional[jax.Array] = None,
    past_len: Optional[jax.Array] = None,
    use_pallas: bool = False,
    ring_mesh=None,
    wk_l: Optional[jax.Array] = None,   # this layer's fused-decode
    wv_l: Optional[jax.Array] = None,   # window buffer [B, W, KVH*Dh]
    win_len: Optional[jax.Array] = None,
    ep_mesh=None,  # Mesh with "expert" axis > 1 => shard_map EP MLP
    pfx_groups: Optional[tuple] = None,  # shared-prefix decode groups
    #                                      (ops/attention.py)
    kernel_mesh=None,  # Mesh: Pallas calls shard_map over its "model" axis
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decoder block of a homogeneous model (attention, then its
    MLP). Shared by the scanned ``forward`` and the pipeline-parallel
    stage loop (parallel/pipeline.py). Returns
    ``(h, (k_chunk, v_chunk))``. Each half is one part of the step
    (``lowering.PARTS``): the norms and the residual add belong to the
    half they surround. The residual lives HERE, as the two plain adds
    ``h = resid + ...`` over one lane ``[B, T, H]``: a stream of several
    lanes (``ModelConfig.hc_mult``) is the mixed walk's
    (``_mixed_trunk``'s ``residual``) and ``init_params`` refuses it for
    this scan."""
    with lowering.part("mixer"):
        resid = h
        x = block_norm(cfg, h, lp, "attn_norm")
        with jax.named_scope("attn_mixer"):
            attn, (k, v) = attention_mixer(
                cfg, lp, x,
                positions=positions, valid_len=valid_len,
                window=window, theta=theta,
                k_pages=k_pages, v_pages=v_pages,
                k_scale=k_scale, v_scale=v_scale, layer=layer,
                page_table=page_table, past_len=past_len,
                use_pallas=use_pallas, ring_mesh=ring_mesh,
                wk_l=wk_l, wv_l=wv_l, win_len=win_len,
                pfx_groups=pfx_groups,
                kernel_mesh=kernel_mesh,
            )
        if cfg.post_norms:
            attn = rms_norm(
                attn, lp["post_attn_norm"], cfg.norm_eps,
                cfg.norm_zero_centered,
            )
        h = resid + attn
    with lowering.part("ffn"):
        resid = h
        x = block_norm(cfg, h, lp, "mlp_norm")
        with jax.named_scope("moe_ffn" if "router" in lp else "dense_ffn"):
            x = _mlp(
                cfg, lp, x, ep_mesh=ep_mesh, use_pallas=use_pallas,
                kernel_mesh=kernel_mesh,
            )
        if cfg.post_norms:
            x = rms_norm(
                x, lp["post_mlp_norm"], cfg.norm_eps, cfg.norm_zero_centered
            )
        h = resid + x
    return h, (k, v)


# ---------------------------------------------------------------------------
# Layers of several kinds
# ---------------------------------------------------------------------------


def hc_coefficients(cfg: ModelConfig, hp: Dict[str, Any], X):
    """A sublayer's hyper-connection coefficients for every token of the
    stream ``X`` (n lanes ``[B, T, C]``), in float32 whatever the
    stream's dtype: ``(H_pre [n], H_post [n], H_res [n][n])`` as lists of
    vectors ``[B, T, 1]``, ready to be multiplied into a lane.

    Shapes that matter on a TPU. The projection ``x phi`` reads each lane
    once, as ``[n^2 + 2 n, C] @ [N, C]^T`` summed over the lanes (N =
    B * T; ``phi`` is kept output-major, ``[n^2 + 2 n, n C]``: 24
    columns would be padded to 128 lanes), beside the pass that takes
    ``mean(x^2)``, and leaves ``[n^2 + 2 n, N]``: the TOKEN axis minor,
    so that every coefficient is a lane-dense vector ``[N]`` (an
    ``[N, n, n]`` array pads each 4 x 4 matrix to an (8, 128) tile). The
    Sinkhorn runs on sixteen such vectors held apart: its sums are adds
    of whole vectors and its divisions one reciprocal a row or column,
    all elementwise over one shape (sliced out of one ``[n, n, N]``
    array the twenty passes compiled to eighty small programs a
    sublayer, held apart to forty)."""
    n = len(X)
    B, T, C = X[0].shape
    with jax.named_scope("hc_coeff"):
        xs = [x.reshape(B * T, C) for x in X]
        ms = sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1) for x in xs
        ) * (1.0 / (n * C))
        r = jax.lax.rsqrt(ms + cfg.norm_eps)                # [N]
        # the norm's scale is folded into phi: the division may follow
        # the product. Operands in the stream's dtype, float32 out
        phi = hp["phi"].astype(xs[0].dtype)   # lane j: columns j C ..
        m = sum(
            jax.lax.dot_general(
                phi[:, j * C : (j + 1) * C], xs[j], (((1,), (1,)), ((), ())),
                precision=_HI, preferred_element_type=jnp.float32,
            )
            for j in range(n)
        ) * r                                               # [n^2 + 2n, N]
        a, b = hp["alpha"], hp["b"]
        pre = [jax.nn.sigmoid(a[0] * m[j] + b[j]) for j in range(n)]
        post = [
            2.0 * jax.nn.sigmoid(a[1] * m[n + j] + b[n + j]) for j in range(n)
        ]
    with jax.named_scope("hc_sinkhorn"):
        at = lambda i, j: 2 * n + i * n + j        # mat() is row-major
        M = [
            [
                jnp.exp(jnp.clip(
                    a[2] * m[at(i, j)] + b[at(i, j)],
                    -cfg.hc_res_clamp, cfg.hc_res_clamp,
                ))
                for j in range(n)
            ]
            for i in range(n)
        ]
        for _ in range(cfg.hc_sinkhorn_iters):
            inv = [
                1.0 / (sum(M[i][j] for i in range(n)) + cfg.hc_eps)
                for j in range(n)
            ]                                       # a column's sum
            M = [[M[i][j] * inv[j] for j in range(n)] for i in range(n)]
            inv = [1.0 / (sum(M[i]) + cfg.hc_eps) for i in range(n)]
            M = [[M[i][j] * inv[i] for j in range(n)] for i in range(n)]

        def col(v):                     # [N] token-minor -> [B, T, 1]
            return v.reshape(B, T, 1)

        return (
            [col(v) for v in pre], [col(v) for v in post],
            [[col(v) for v in row] for row in M],
        )


def hc_sublayer(cfg: ModelConfig, hp: Dict[str, Any], X, f):
    """One sublayer ``f`` (a mixer or an FFN with its norm) of a model
    whose residual stream is ``n`` lanes a token (``ModelConfig.hc_mult``;
    mHC): with ``hc_coefficients``' ``H_pre``, ``H_post``, ``H_res``,

        u = sum_j H_pre[j] X[j] ;  y = f(u)
        X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

    ``X``: a tuple of the n lanes, each ``[B, T, C]`` in the activation
    dtype; ``hp``: the sublayer's ``phi [n^2 + 2 n, n C]``,
    ``b [n^2 + 2 n]`` and ``alpha [3]`` (pre, post, res). The lanes are n
    ARRAYS and the mix n^2 multiply-adds over [N, C] slabs: one array
    ``[B, T, n, C]`` sliced and stacked again (or mixed by a batched
    4 x 4 ``dot_general``, whose operands would be padded to MXU tiles)
    took 2.0 ms a sublayer at 4,096 tokens on a v5e where the tuple takes
    0.56 (PERF.md section 6, PR 54). Sums in float32, lanes stored in the
    stream's dtype.

    ``u`` and ``y`` each cross an ``optimization_barrier``: the read and
    the write stay device programs of their OWN, every instruction of
    which is under an ``hc_`` scope, and no part of them rides in the
    fusion of ``f``'s norm or of its last product, where a trace could
    not tell the stream's seconds from the product's (PERF.md section 6,
    PR 54: what the barriers cost; tests/perfbench/test_aot_xing_v5e.py
    holds the compiled programs to it)."""
    n, dt = len(X), X[0].dtype
    pre, post, res = hc_coefficients(cfg, hp, X)
    with jax.named_scope("hc_read"):
        u = sum(
            pre[j] * X[j].astype(jnp.float32) for j in range(n)
        ).astype(dt)
    y = jax.lax.optimization_barrier(f(jax.lax.optimization_barrier(u)))
    with jax.named_scope("hc_write"):
        y = y.astype(jnp.float32)
        return tuple(
            sum(
                (res[i][j] * X[j].astype(jnp.float32) for j in range(n)),
                post[i] * y,
            ).astype(dt)
            for i in range(n)
        )


_MIXER_STACK = {
    "attention": "attn", "swa": "swa", "conv": "conv", "mamba": "mamba",
    "mla": "mla", "kda": "kda", "mamba1": "mamba1", "gmu": "gmu",
    "cross": "cross",
}


def _check_mixed(cfg: ModelConfig) -> None:
    """What the mixed walk does not implement it refuses."""
    if len(cfg.mixers) != cfg.num_layers:
        raise ValueError(
            f"{cfg.name}: layer_types has {len(cfg.mixers)} entries for "
            f"{cfg.num_layers} layers"
        )
    unknown = set(cfg.mixers) - set(_MIXER_STACK) - {"none"}
    if unknown:
        raise ValueError(
            f"{cfg.name}: unknown layer kinds {sorted(unknown)} (the walk "
            f"has {sorted(_MIXER_STACK)})"
        )
    if "moe" in cfg.ffns and not cfg.moe_experts:
        raise ValueError(f"{cfg.name}: a routed block needs moe_experts")
    if cfg.moe_first_expert + cfg.experts_held > cfg.moe_experts:
        raise ValueError(
            f"{cfg.name}: experts {cfg.moe_first_expert}.."
            f"{cfg.moe_first_expert + cfg.experts_held} are not among the "
            f"router's {cfg.moe_experts}"
        )
    if cfg.num_conv_layers and cfg.conv_kernel < 2:
        raise ValueError(f"{cfg.name}: conv layers need conv_kernel >= 2")
    if cfg.num_mamba_layers and (
        cfg.mamba_conv < 2 or cfg.mamba_heads % cfg.mamba_groups
        or min(cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state) < 1
    ):
        raise ValueError(
            f"{cfg.name}: mamba layers need mamba_conv >= 2, heads, a "
            "head_dim and a state size, and heads a multiple of groups"
        )
    state_kinds = [k for k in ("mamba", "kda", "mamba1") if k in cfg.mixers]
    if len(state_kinds) > 1:
        # one slot pool, one description of a state layer
        # (``ModelConfig.state_kind``)
        raise NotImplementedError(
            f"{cfg.name}: state layers of two kinds in one model "
            f"({' beside '.join(state_kinds)}: a slot pool of another shape)"
        )
    if cfg.num_mamba1_layers and (
        cfg.mamba1_conv < 2 or min(
            cfg.mamba1_inner, cfg.mamba1_state, cfg.mamba1_dt_rank,
            cfg.mamba1_chunk,
        ) < 1
    ):
        raise ValueError(
            f"{cfg.name}: mamba1 layers need mamba1_conv >= 2, a "
            "mamba1_inner, a mamba1_state, a mamba1_dt_rank and a "
            "mamba1_chunk"
        )
    # a value that travels DOWN the stack inside one forward comes from
    # ONE layer of the right kind, before every layer that reads it
    for reader, field, at, kind in (
        ("gmu", "memory_layer", cfg.memory_layer, "mamba1"),
        ("cross", "kv_source_layer", cfg.kv_source_layer, "attention"),
    ):
        if reader not in cfg.mixers:
            continue
        if not (
            0 <= at < cfg.mixers.index(reader) and cfg.mixers[at] == kind
        ):
            raise ValueError(
                f"{cfg.name}: {reader} layers read layer {field}={at}, "
                f"which has to be a {kind} layer before the first of them"
            )
    if cfg.block_norm not in ("rmsnorm", "layernorm"):
        raise ValueError(
            f"{cfg.name}: block_norm {cfg.block_norm!r} (\"rmsnorm\" | "
            "\"layernorm\")"
        )
    if cfg.attn_differential and (
        cfg.num_heads % 2 or cfg.num_kv_heads % 2
        or cfg.window_num_heads or cfg.num_latent_layers
        or cfg.position_embedding != "nope" or cfg.qk_norm or cfg.attn_gate
        or cfg.block_length > 1
    ):
        raise NotImplementedError(
            f"{cfg.name}: differential attention (attn_differential) "
            "pairs an even number of query and KV heads of NoPE GQA "
            "layers; with a rotary embedding, QK-norm, an output gate "
            "(attn_gate), query heads a layer kind (window_num_heads), "
            "latent (mla) layers or a mask by blocks it is not built"
        )
    if cfg.num_kda_layers:
        if cfg.kda_conv < 2 or min(
            cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank, cfg.kda_chunk
        ) < 1:
            raise ValueError(
                f"{cfg.name}: kda layers need kda_conv >= 2, kda_heads, a "
                "kda_head_dim, a kda_rank and a kda_chunk"
            )
    if cfg.num_window_layers and cfg.sliding_window < 1:
        raise ValueError(f"{cfg.name}: swa layers need a sliding_window")
    if cfg.attn_gate not in ("", "channel", "head"):
        raise ValueError(
            f"{cfg.name}: attn_gate {cfg.attn_gate!r} (\"channel\" | "
            "\"head\" | \"\": none)"
        )
    for mixer in ("attention", "swa"):
        heads, rot = cfg.heads_of(mixer), cfg.rotary_dim_of(mixer)
        if heads % max(cfg.num_kv_heads, 1):
            raise ValueError(
                f"{cfg.name}: {heads} query heads of a {mixer} layer are "
                f"no multiple of {cfg.num_kv_heads} KV heads"
            )
        if rot % 2 or rot > cfg.head_dim:
            raise ValueError(
                f"{cfg.name}: a rotary part of {rot} elements of a head "
                f"of {cfg.head_dim} (even, at most the head)"
            )
    if cfg.window_num_heads and not cfg.num_window_layers:
        raise ValueError(
            f"{cfg.name}: window_num_heads is a swa layer's and the "
            "model lists none"
        )
    if cfg.rotary_dim and (
        cfg.num_latent_layers or cfg.position_embedding == "nope"
    ):
        raise NotImplementedError(
            f"{cfg.name}: a rotary part narrower than the head "
            "(rotary_dim) on latent (mla) layers, whose rotary part is "
            "qk_rope_head_dim, or with no rotary embedding at all (nope)"
        )
    if cfg.moe_shared_gate and not (
        cfg.moe_shared_intermediate_size and cfg.moe_gated
    ):
        raise ValueError(
            f"{cfg.name}: a shared expert's gate (moe_shared_gate) needs "
            "a shared expert of three matrices"
        )
    if cfg.num_latent_layers:
        if cfg.num_attn_layers or cfg.num_window_layers:
            # one pool, one page width (``ModelConfig.page_width``)
            raise NotImplementedError(
                f"{cfg.name}: latent (mla) layers beside attention layers "
                "that keep K/V (a pool of another page width)"
            )
        if min(cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
               cfg.v_head_dim) < 1 or cfg.qk_rope_head_dim < 2 or (
            cfg.qk_rope_head_dim % 2
        ):
            raise ValueError(
                f"{cfg.name}: mla layers need q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, v_head_dim and an even qk_rope_head_dim"
            )
        if cfg.index_topk and (
            min(cfg.index_n_heads, cfg.index_head_dim) < 1
            or cfg.index_head_dim < cfg.qk_rope_head_dim
        ):
            raise ValueError(
                f"{cfg.name}: an indexer (index_topk) needs index_n_heads "
                "and an index_head_dim of at least qk_rope_head_dim"
            )
        if not cfg.rope_interleave or cfg.position_embedding != "rope":
            raise NotImplementedError(
                f"{cfg.name}: mla layers turn their rotary part in "
                "interleaved pairs (rope_interleave): half-split pairs "
                "and no rotary embedding at all (nope) are not built"
            )
        if cfg.rope_scaling_factor and cfg.index_topk:
            raise NotImplementedError(
                f"{cfg.name}: YaRN (rope_scaling_factor) on mla layers "
                "with an indexer: the index key's rotary part is plain"
            )
    if cfg.hc_mult > 1 and min(cfg.hc_sinkhorn_iters, cfg.hc_eps) <= 0:
        raise ValueError(
            f"{cfg.name}: a residual stream of several lanes (hc_mult) "
            "needs hc_sinkhorn_iters >= 1 and hc_eps > 0"
        )
    # supported in the walk: a window by the layer's kind ("swa", its
    # K/V a pool of its own) and a rotary embedding a kind (YaRN on the
    # full layers, plain ``local_rope_theta`` on the window layers). A
    # window by ``sliding_pattern`` belongs to the one scan of a
    # homogeneous model
    unsupported = {
        "sliding windows by sliding_pattern (list the layers' kinds instead)":
            cfg.sliding_pattern != "none",
        "post norms": cfg.post_norms,
        "zero-centered norms": cfg.norm_zero_centered,
        "attention sinks": cfg.attention_sink,
        "expert and router biases (moe_bias)": cfg.moe_bias,
        "attention projection biases (attn_bias) on latent (mla) layers":
            cfg.attn_bias and cfg.num_latent_layers > 0,
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise NotImplementedError(
            f"{cfg.name}: layers of several kinds with {', '.join(bad)}"
        )


def layer_groups(cfg: ModelConfig) -> List[Tuple[int, int, int]]:
    """The config's list of layers cut into ``(first layer, period,
    repeats)`` groups, taken greedily from the front: at each layer the
    period whose pattern of (mixer, FFN) kinds repeats over the most
    layers (the shortest on a tie); a layer that starts no repeat is a
    group of one. The walk scans a group's repeats and unrolls its
    period, so the program's size follows the number of groups and
    periods, not the number of layers."""
    kinds = list(zip(cfg.mixers, cfg.ffns))
    L = len(kinds)
    groups, i = [], 0
    while i < L:
        best = (1, 1)  # (period, repeats)
        for p in range(1, (L - i) // 2 + 1):
            r = 1
            while kinds[i + r * p : i + (r + 1) * p] == kinds[i : i + p]:
                r += 1
            if r >= 2 and p * r > best[0] * best[1]:
                best = (p, r)
        groups.append((i, best[0], best[1]))
        i += best[0] * best[1]
    return groups


# ``mamba_mixer``'s and ``kda_mixer``'s outputs as the walk stacks them (MixedChunk.ssm's
# keys, prefixed)
_SSM_KEYS = (
    "ssm_conv", "ssm_final", "ssm_dt", "ssm_dA", "ssm_x", "ssm_B",
    "ssm_g", "ssm_k", "ssm_u", "ssm_A", "ssm_S",
)


def _index_in_kind(kinds) -> List[int]:
    seen: Dict[str, int] = {}
    out = []
    for k in kinds:
        out.append(seen.get(k, 0))
        seen[k] = out[-1] + 1
    return out


def _mixed_trunk(
    cfg: ModelConfig, params: Params, h: jax.Array, *,
    positions, valid_len, conv_state, k_pages, v_pages, k_scale, v_scale,
    page_table, past_len, window_past, use_pallas, ep_mesh,
    pfx_groups, kernel_mesh, state_past=None, ssm_pending=False,
    window_pool=None,
):
    """The walk over a config's own list of layers, parameters stacked
    per kind. Every stack (and the page pool, the window buffers, the
    conv state) is a CONSTANT of each group's scan and the layer is an
    index into it, as the pool is in the homogeneous scan: a stack
    among the scan's xs would have to be sliced out per group first,
    which copies it. Returns ``(h, k, v, conv, route, ssm)``: K/V
    stacked over the attention layers, ``g_ext`` over the conv layers,
    expert row counts over the routed layers (None for a kind with no
    layer), and what commits the mamba layers' state (``MixedChunk``).
    K/V of the "swa" layers follows that of the full layers in the
    stack (and in the fused window's buffers): ``kvcache.write_kv``
    splits it by the pools' depths. ``window_pool`` = (wk_pages,
    wv_pages, wtable): the window layers' pool and each row's pages IN
    it (``kvcache.window_table``).
    """
    _check_mixed(cfg)
    stacks = params["layers"]
    lane = h[0] if cfg.hc_mult > 1 else h     # the lanes of a stream
    B, T = lane.shape[:2]
    K1 = cfg.conv_state_len
    r = cfg.residual_multiplier
    if cfg.num_state_layers and state_past is None:
        # no cache: every row starts a sequence, in the garbage slot
        state_past = StatePast(
            ssm=jnp.zeros(
                (cfg.num_state_layers, 1, cfg.state_rows, cfg.state_inner),
                lane.dtype,
            ),
            slots=jnp.zeros((B,), jnp.int32),
            fresh=jnp.ones((B,), bool),
            conv=jnp.zeros(
                (cfg.num_state_layers, B, cfg.state_conv_len,
                 cfg.state_conv_dim), lane.dtype,
            ),
        )
    if cfg.num_conv_layers and conv_state is None:
        conv_state = jnp.zeros(
            (cfg.num_conv_layers, B, K1, cfg.hidden_size), lane.dtype
        )
    win_len = None if window_past is None else window_past[2]
    La = cfg.num_attn_layers
    # per attention kind: (window, theta, YaRN?, pool, table, where its
    # layers start in the stacked K/V and the fused window's buffers).
    # A full layer's window is Python's 0, not an array: a constant made
    # while tracing is a tracer, and ``chunk_attention`` hands the flash
    # body a window it can read BEFORE tracing as a static bound
    attn_kind = {
        "attention": (
            0, jnp.float32(cfg.rope_theta),
            bool(cfg.rope_scaling_factor), k_pages, v_pages, page_table, 0,
        ),
    }
    if cfg.num_window_layers:
        wk_pages, wv_pages, wtable = window_pool or (None, None, None)
        attn_kind["swa"] = (
            jnp.int32(cfg.sliding_window),
            jnp.float32(cfg.local_rope_theta or cfg.rope_theta),
            False, wk_pages, wv_pages, wtable, La,
        )
    mixers, ffns = cfg.mixers, cfg.ffns
    mixer_at, ffn_at = _index_in_kind(mixers), _index_in_kind(ffns)
    # what travels DOWN the stack inside this forward: ``memory_layer``'s
    # scan output "m" and ``kv_source_layer``'s chunk K/V "kv". Their
    # layers are groups of one (``layer_groups``), so both are in hand,
    # constants of the readers' scan, when it begins
    shared: Dict[str, Any] = {}

    def take(stack, idx):
        return jax.tree_util.tree_map(lambda a: a[idx], stack)

    def scaled(y):
        return y if r == 1.0 else y * jnp.asarray(r, y.dtype)

    def residual(h, stack, idx, prefix, f):
        """``h`` after the sublayer ``f``. THE place the residual lives:
        the plain add ``h + f(h)`` over ``[B, T, H]``, or, for a stream
        of several lanes (a tuple of n such arrays:
        ``ModelConfig.hc_mult``), ``hc_sublayer`` under the sublayer's
        own ``hc_*`` leaves. The
        branch is taken in Python: a model with one lane traces the
        program it always did."""
        if cfg.hc_mult == 1:
            return h + scaled(f(h))
        with jax.named_scope("hc_coeff"):   # the slices are its reads
            hp = {
                k[len(prefix):]: v[idx] for k, v in stack.items()
                if k.startswith(prefix)
            }
        return hc_sublayer(cfg, hp, h, lambda u: scaled(f(u)))

    def block(h, mixer, m_idx, ffn, f_idx, depth):
        """One block: its mixer, then its FFN, each under its own norm
        and its own pass through the residual (``residual``: the norm
        and the add, or the lanes' read and write, belong to the half
        they surround); "none" for the one a block of ONE sublayer
        lacks. ``depth``: the block's place in the model."""
        out = {}
        if mixer != "none":
            with lowering.part("mixer"):
                h = residual(
                    h, stacks[_MIXER_STACK[mixer]], m_idx, "hc_mix_",
                    lambda u: mix(u, mixer, m_idx, out, depth),
                )
        if ffn != "none":
            with lowering.part("ffn"):
                h = residual(
                    h, stacks[ffn], f_idx, "hc_ffn_",
                    lambda u: feed(u, ffn, f_idx, out),
                )
        return h, out

    def mix(h, mixer, m_idx, out, depth):
        lp = take(stacks[_MIXER_STACK[mixer]], m_idx)
        x = block_norm(cfg, h, lp, "attn_norm")
        if mixer == "conv":
            with jax.named_scope("conv_mixer"):
                y, out["conv"] = conv_mixer(cfg, lp, x, conv_state[m_idx])
        elif mixer == "mla":
            with jax.named_scope("mla_mixer"):
                # a layer with an indexer: its index keys ride in V's
                # place (the pool ``v_pages``, the window's buffers, the
                # chunk's stack)
                y, out["k"], ik = mla_mixer(
                    cfg, lp, x, positions=positions, valid_len=valid_len,
                    pages=k_pages, layer=m_idx, page_table=page_table,
                    past_len=past_len,
                    win_rows=None if window_past is None
                    else window_past[0][m_idx],
                    win_len=win_len, use_pallas=use_pallas,
                    index_pages=v_pages,
                    win_index=None if window_past is None or (
                        window_past[1] is None
                    ) else window_past[1][m_idx],
                )
                if ik is not None:
                    out["v"] = ik
        elif mixer == "mamba":
            with jax.named_scope("mamba_mixer"):
                y, ssm = mamba_mixer(
                    cfg, lp, x, valid_len=valid_len, past=state_past,
                    layer=m_idx, pending=ssm_pending,
                    use_pallas=use_pallas, kernel_mesh=kernel_mesh,
                )
                out.update(ssm)
        elif mixer == "kda":
            with jax.named_scope("kda_mixer"):
                y, ssm = kda_mixer(
                    cfg, lp, x, valid_len=valid_len, past=state_past,
                    layer=m_idx, pending=ssm_pending,
                    use_pallas=use_pallas, kernel_mesh=kernel_mesh,
                )
                out.update(ssm)
        elif mixer == "mamba1":
            with jax.named_scope("mamba1_mixer"):
                y, ssm, out["m"] = mamba1_mixer(
                    cfg, lp, x, valid_len=valid_len, past=state_past,
                    layer=m_idx, pending=ssm_pending,
                )
                out.update(ssm)
        elif mixer == "gmu":
            with jax.named_scope("memory_unit"):
                y = memory_unit(lp, x, shared["m"])
        else:
            # a "cross" layer is a full layer's reader: that layer's
            # pool, index, window buffers and chunk K/V, a query of its own
            cross = mixer == "cross"
            window, theta, yarn, kp, vp, table, at = attn_kind[
                "attention" if cross else mixer
            ]
            swa = mixer == "swa"
            scope = "attn_cross" if cross else (
                "attn_window" if swa else "attn_full"
            ) if cfg.num_window_layers else "attn_mixer"
            key = ("wk", "wv") if swa else ("k", "v")
            at_pool = mixer_at[cfg.kv_source_layer] if cross else m_idx
            with jax.named_scope(scope):
                y, kv = attention_mixer(
                    cfg, lp, x,
                    positions=positions, valid_len=valid_len,
                    window=window, theta=theta,
                    k_pages=kp, v_pages=vp,
                    k_scale=None if swa else k_scale,
                    v_scale=None if swa else v_scale, layer=at_pool,
                    page_table=table, past_len=past_len,
                    use_pallas=use_pallas,
                    wk_l=None if window_past is None
                    else window_past[0][at + at_pool],
                    wv_l=None if window_past is None
                    else window_past[1][at + at_pool],
                    win_len=win_len,
                    # a shared prefix's carry reads the full pool's pages
                    pfx_groups=None if swa else pfx_groups,
                    kernel_mesh=kernel_mesh, yarn=yarn,
                    live_window=cfg.sliding_window if swa else 0,
                    rotary_dim=cfg.rotary_dim_of(mixer),
                    kv=shared["kv"] if cross else None, depth=depth,
                )
                if not cross:     # a reader keeps nothing
                    out[key[0]], out[key[1]] = kv
        return y

    def feed(h, ffn, f_idx, out):
        experts = {
            k: v for k, v in stacks[ffn].items() if k.startswith("we_")
        }
        fp = take(
            {k: v for k, v in stacks[ffn].items() if k not in experts},
            f_idx,
        )
        x = block_norm(cfg, h, fp, "mlp_norm")
        if ffn == "moe":
            with jax.named_scope("moe_ffn"):
                # the experts stay whole stacks: the layer is an index
                # into them, never a slice (ops/moe.py)
                y, out["route"] = _mlp(
                    cfg, fp, x, ep_mesh=ep_mesh, use_pallas=use_pallas,
                    return_counts=True, expert_stacks=(experts, f_idx),
                    kernel_mesh=kernel_mesh,
                )
        else:
            with jax.named_scope("dense_ffn"):
                y = _mlp(cfg, fp, x)
        return y

    outs: Dict[str, list] = {
        k: [] for k in ("k", "v", "wk", "wv", "conv", "route") + _SSM_KEYS
    }
    for first, period, repeats in layer_groups(cfg):
        span = range(first, first + period)
        per_mixer = {m: [mixers[l] for l in span].count(m) for m in set(mixers)}
        per_ffn = {f: [ffns[l] for l in span].count(f) for f in set(ffns)}

        def body(h, rep, span=span, per_mixer=per_mixer, per_ffn=per_ffn):
            ys: Dict[str, list] = {k: [] for k in outs}
            for l in span:
                h, out = block(
                    h,
                    mixers[l], mixer_at[l] + rep * per_mixer[mixers[l]],
                    ffns[l], ffn_at[l] + rep * per_ffn[ffns[l]],
                    l + rep * len(span),
                )
                m = out.pop("m", None)
                if l == cfg.memory_layer:
                    shared["m"] = m
                if l == cfg.kv_source_layer:
                    shared["kv"] = (out["k"], out["v"])
                for k, val in out.items():
                    ys[k].append(val)
            with lowering.part("cache"):  # gathered for the commit
                return h, {k: jnp.stack(v) for k, v in ys.items() if v}

        if repeats == 1:
            h, ys = body(h, 0)
        else:
            h, ys = jax.lax.scan(
                body, h, jnp.arange(repeats, dtype=jnp.int32)
            )
            # [repeats, in a period, ...] -> [layers of the kind, ...]
            ys = {
                k: v.reshape((-1,) + v.shape[2:]) for k, v in ys.items()
            }
        for k, v in ys.items():
            outs[k].append(v)
    with lowering.part("cache"):
        cat = {
            k: (jnp.concatenate(v) if len(v) > 1 else v[0]) if v else None
            for k, v in outs.items()
        }
        ssm = {k[4:]: cat[k] for k in _SSM_KEYS if cat[k] is not None}
        if cat["wk"] is not None:
            # the window layers' K/V after the full layers'
            for full, win in (("k", "wk"), ("v", "wv")):
                cat[full] = cat[win] if cat[full] is None else (
                    jnp.concatenate([cat[full], cat[win]])
                )
    return h, cat["k"], cat["v"], cat["conv"], cat["route"], ssm or None


def rope_thetas(cfg: ModelConfig) -> jax.Array:
    """Per-layer RoPE base frequencies [L] (local layers may differ)."""
    return jnp.asarray(
        [
            (
                cfg.local_rope_theta
                if (w > 0 and cfg.local_rope_theta)
                else cfg.rope_theta
            )
            for w in cfg.window_array()
        ],
        jnp.float32,
    )


@lowering.part("embed")
def embed_tokens(cfg: ModelConfig, params: Params, ids: jax.Array):
    h = params["embed"][ids]  # [B, T, H] gather
    if cfg.embed_scale:
        h = (h.astype(jnp.float32) * (cfg.hidden_size ** 0.5)).astype(h.dtype)
    if cfg.embedding_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    if cfg.hc_mult > 1:
        # a stream of several lanes starts as so many copies of the
        # token's embedding: a tuple of n arrays [B, T, H]
        return (h,) * cfg.hc_mult
    return h


@lowering.part("head")
def head_apply(
    cfg: ModelConfig, params: Params, h: jax.Array, valid_len: jax.Array,
    logit_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """final norm + lm/embedding head. h: [B, T, H] (or the lanes of a
    stream, a tuple of n such arrays: ``ModelConfig.hc_mult``).

    Returns ``(out, h_normed)`` — the head output plus the post-final-norm
    hidden states (the ``hidden`` of the forward contract).

    ``logit_positions`` ([B] int32) runs the LM head on that ONE
    position per row and returns logits ``[B, 1, V]``: prefill samples
    only from the last valid position, and the full ``[B, T, V]`` tensor
    (8 x 512 x 151,936 in bf16 then f32 is ~3.7 GB at Qwen3's vocab)
    is the largest transient of the whole program."""
    if cfg.hc_mult > 1:
        # a stream of several lanes ends as their sum
        h = sum(x.astype(jnp.float32) for x in h).astype(h[0].dtype)
    T = h.shape[1]
    h = block_norm(cfg, h, params, "final_norm")
    if cfg.head == "embedding":
        if cfg.pooling == "last":
            # Qwen3-Embedding: the final valid token's hidden state
            last = jnp.maximum(valid_len - 1, 0)
            pooled = jnp.take_along_axis(
                h.astype(jnp.float32), last[:, None, None], axis=1
            )[:, 0]
        else:
            mask = (
                jnp.arange(T)[None, :] < valid_len[:, None]
            ).astype(jnp.float32)
            pooled = jnp.sum(h.astype(jnp.float32) * mask[..., None], axis=1)
            pooled = pooled / jnp.maximum(
                mask.sum(axis=1, keepdims=True), 1.0
            )
        emb = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
        )
        return emb, h
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    else:
        lm_head = materialize(lm_head, h.dtype)
    h_head = h
    if logit_positions is not None:
        h_head = jnp.take_along_axis(
            h, logit_positions[:, None, None], axis=1
        )
    logits = h_head @ lm_head.astype(h.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits.astype(jnp.float32), h


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Params,
    ids: jax.Array,                     # [B, T] int32
    positions: jax.Array,               # [B, T] int32 (global positions)
    valid_len: jax.Array,               # [B] int32 — tokens of chunk that are real
    paged_past: Optional[Tuple[jax.Array, ...]] = None,
    # paged_past: (k_pages, v_pages, page_table), or with an int8 KV
    # cache (k_pages, v_pages, k_scale, v_scale, page_table) — pages
    # [L, NP, PS, KVH*Dh] (L the ATTENTION layers: every layer of a
    # homogeneous model; FUSED trailing axis, engine/kvcache.py),
    # per-token scales [L, NP, PS], table [B, MP]. The stacks are
    # CONSTANTS of the layer scan, which carries the layer's index:
    # attention DMAs pool[layer, page] in place (Pallas) or gathers
    # [layer, page_table] one layer at a time (XLA fallback). A pool
    # among the scan's xs would reach the kernel as a per-layer slice,
    # which XLA copies out in full before a custom call; the full
    # [L, B, CTX, ...] gather is never materialized either.
    past_len: Optional[jax.Array] = None,  # [B] int32 — valid past tokens
    use_pallas: bool = False,
    ring_mesh=None,  # Mesh with "seq" axis > 1 => ring-attention prefill
    # fused-decode window buffer: (win_k [L, B, W, KVH*Dh] fused, win_v,
    # win_len scalar) — K/V of window tokens not yet in the page pool
    # (runner.decode_multi writes pages once per window, not per step)
    window_past: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    ep_mesh=None,  # Mesh with "expert" axis > 1 => shard_map EP MLP
    # shared-prefix decode (Hydragen-style carry injection, see
    # ops/attention.py): tuple of (pages [Pp_g], pfx_len [B]) groups —
    # the job-shared pages at member rows' table heads + per-row
    # prefix token counts (0 = row not in that group)
    pfx_groups: Optional[tuple] = None,
    # [B] int32: LM-head logits for this one position per row only
    # ([B, 1, V] instead of [B, T, V]) — see head_apply
    logit_positions: Optional[jax.Array] = None,
    # Mesh whose "model" axis the Pallas calls are shard_mapped over
    # (tensor parallelism; ops/lowering.shard_over_model)
    kernel_mesh=None,
    # [L_conv, B, K-1, H]: each conv layer's state before the chunk, for
    # a model that has such layers (None: every row starts a sequence)
    conv_state: Optional[jax.Array] = None,
    # a mamba model's slot pool and each row's place in it (None: every
    # row starts a sequence), and whether the chunk's accepted length
    # is decided later (``mamba_mixer``; None: a chunk of one token)
    state_past: Optional[StatePast] = None,
    ssm_pending: Optional[bool] = None,
    # (wk_pages, wv_pages [L_win, NP_w, PS, KD], wtable [B, MP]): the
    # "swa" layers' pool and each row's pages in it, with ``paged_past``
    # for a model that has such layers (engine/kvcache.py)
    window_pool: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array, Tuple[Any, jax.Array]]:
    """Run the trunk over a chunk.

    Returns ``(logits_or_emb, final_hidden, (k_chunk, v_chunk))`` where the
    chunk K/V are stacked ``[L_attn, B, T, KVH, Dh]`` over the ATTENTION
    layers (every layer of a homogeneous model; post-RoPE, ready for
    cache scatter by the runner). For a model with layers of several
    kinds ``k_chunk`` is a ``MixedChunk``: K, and with it what commits
    the conv state and what counts the routing.
    """
    h = embed_tokens(cfg, params, ids)

    k_pages = v_pages = k_scale = v_scale = page_table = None
    if paged_past is not None:
        if len(paged_past) == 5:  # int8 KV
            k_pages, v_pages, k_scale, v_scale, page_table = paged_past
        else:
            k_pages, v_pages, page_table = paged_past

    if not cfg.homogeneous:
        if ring_mesh is not None:
            raise NotImplementedError(
                f"{cfg.name}: ring attention with layers of several kinds"
            )
        h, k_all, v_all, conv, route, ssm = _mixed_trunk(
            cfg, params, h,
            positions=positions, valid_len=valid_len, conv_state=conv_state,
            k_pages=k_pages, v_pages=v_pages,
            k_scale=k_scale, v_scale=v_scale,
            page_table=page_table, past_len=past_len,
            window_past=window_past, use_pallas=use_pallas,
            ep_mesh=ep_mesh,
            pfx_groups=pfx_groups, kernel_mesh=kernel_mesh,
            state_past=state_past,
            ssm_pending=(
                ids.shape[1] == 1 if ssm_pending is None else ssm_pending
            ),
            window_pool=window_pool,
        )
        out, h = head_apply(cfg, params, h, valid_len, logit_positions)
        chunk = MixedChunk(k=k_all, conv=conv, route=route, ssm=ssm)
        return out, h, (chunk, v_all)

    windows = jnp.asarray(cfg.window_array(), jnp.int32)  # [L]
    thetas = rope_thetas(cfg)

    win_len = None if window_past is None else window_past[2]
    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    xs = (params["layers"], windows, thetas, layers)
    if window_past is not None:
        xs += (window_past[0], window_past[1])

    def layer_step(h, xs_l):
        lp, window, theta, layer = xs_l[:4]
        wk_l, wv_l = xs_l[4:] if window_past is not None else (None, None)
        return layer_apply(
            cfg, lp, h,
            positions=positions, valid_len=valid_len,
            window=window, theta=theta,
            k_pages=k_pages, v_pages=v_pages,
            k_scale=k_scale, v_scale=v_scale, layer=layer,
            page_table=page_table, past_len=past_len,
            use_pallas=use_pallas, ring_mesh=ring_mesh,
            wk_l=wk_l, wv_l=wv_l, win_len=win_len,
            ep_mesh=ep_mesh,
            pfx_groups=pfx_groups, kernel_mesh=kernel_mesh,
        )

    h, (k_all, v_all) = jax.lax.scan(layer_step, h, xs)

    out, h = head_apply(cfg, params, h, valid_len, logit_positions)
    return out, h, (k_all, v_all)


def num_params(params: Params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)))
