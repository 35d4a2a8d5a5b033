"""Pipeline parallelism: stage-sharded layers + GPipe microbatch loop.

The reference has no parallelism at all (SURVEY §2.3 "PP: not in
reference; optional"); this is the TPU-native implementation for models
whose layer stack exceeds TP+EP memory on a slice. Design:

- The params pytree keeps its stacked ``[L, ...]`` layer axis; under PP
  that axis is sharded over the ``pipe`` mesh axis (``pp_param_shardings``)
  so each device holds a contiguous stage of ``L/pp`` layers — no
  re-packing, the same checkpoint layout serves TP, EP and PP.
- ``pipeline_forward`` runs the classic GPipe schedule inside a
  ``shard_map`` that is *manual only over ``pipe``* (``axis_names={"pipe"}``):
  microbatch activations hop stage-to-stage via ``lax.ppermute`` over ICI
  while every other mesh axis (data/model/expert) stays in GSPMD auto mode,
  so PP composes with DP/TP/EP without hand-written collectives.
- The bubble is the standard (pp-1)/(M+pp-1) fraction; callers pick the
  microbatch count M (default: pp) to trade bubble against per-step
  matmul size (MXU utilization).
- Embedding lookup and the lm/embedding head run outside the pipeline
  (replicated/TP-sharded as usual, see parallel/sharding.py) — they are
  cheap relative to the trunk and this keeps stage boundaries uniform.

Returns the same ``(out, hidden, (k_all, v_all))`` contract as
``models.transformer.forward`` so the runner can scatter K/V into the
paged cache; under PP the cache's layer axis should be sharded over
``pipe`` too (``pp_cache_sharding``), keeping each layer's pages resident
on the stage that produces and consumes them.

Decode runs ``pipeline_decode``: a stage-sequential schedule where the
activation hops stage-to-stage via ``ppermute`` and each device computes
ONLY its own ``L/pp`` layers (``lax.cond``-gated, so inactive stages do
no matmuls and read no weights). Per-device weight/cache residency and
traffic are 1/pp of the stack — the point of PP (models whose layers
exceed TP+EP memory). The (pp-1)/pp decode bubble is inherent to a
single in-flight batch; overlapping multiple decode batches across
stages is a possible follow-up.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.configs import ModelConfig
from ..models import transformer
from .sharding import param_shardings


def pp_param_shardings(params: Any, mesh: Mesh) -> Any:
    """TP/EP rules with the stacked layer axis additionally sharded over
    ``pipe`` (layers subtree only; embed/head/final_norm keep their
    top-level rules)."""
    base = param_shardings(params, mesh)

    def add_pipe(path, sh: NamedSharding):
        names = [p.key for p in path if hasattr(p, "key")]
        if "layers" not in names:
            return sh
        spec = list(sh.spec) if len(sh.spec) else []
        if not spec:
            spec = [None]
        spec[0] = "pipe"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(
        add_pipe, base, is_leaf=lambda x: isinstance(x, NamedSharding)
    )


def pp_cache_sharding(
    mesh: Mesh, kv_heads: "int | None" = None
) -> NamedSharding:
    """KV pages [L, NP, PS, KVH*Dh]: layers over ``pipe``, the fused
    KV-head-major trailing axis over ``model`` in whole-KV-head blocks
    (matches pp_param_shardings / cache_shardings)."""
    from .sharding import check_tp_divides_kv_heads

    check_tp_divides_kv_heads(mesh, kv_heads)
    return NamedSharding(mesh, P("pipe", None, None, "model"))


def pipeline_forward(
    cfg: ModelConfig,
    params: Any,
    ids: jax.Array,        # [B, T] int32
    positions: jax.Array,  # [B, T] int32
    valid_len: jax.Array,  # [B] int32
    mesh: Mesh,
    *,
    n_microbatches: Optional[int] = None,
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, jax.Array]]:
    """GPipe-scheduled trunk forward (prefill; no KV past).

    ``B`` must divide into ``n_microbatches`` (default ``pp``) and ``L``
    into ``pp``.
    """
    S = int(mesh.shape["pipe"])
    B, T = ids.shape
    L, H = cfg.num_layers, cfg.hidden_size
    M = n_microbatches or min(S, B)
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    if L % S:
        raise ValueError(f"layers {L} not divisible by pipe size {S}")
    mb = B // M
    Lb = L // S
    KVH, Dh = cfg.num_kv_heads, cfg.head_dim

    h = transformer.embed_tokens(cfg, params, ids)
    h0 = h.reshape(M, mb, T, H)
    pos_s = positions.reshape(M, mb, T)
    val_s = valid_len.reshape(M, mb)
    windows = jnp.asarray(cfg.window_array(), jnp.int32)
    thetas = transformer.rope_thetas(cfg)

    def stage(layers_local, windows_l, thetas_l, h0, pos_s, val_s):
        s = jax.lax.axis_index("pipe")
        last = S - 1
        buf = jnp.zeros((mb, T, H), h0.dtype)
        out = jnp.zeros((M, mb, T, H), h0.dtype)
        k_out = jnp.zeros((M, Lb, mb, T, KVH, Dh), h0.dtype)
        v_out = jnp.zeros_like(k_out)
        fwd = [(i, i + 1) for i in range(S - 1)]

        def layer_body(carry, xs_l):
            # positions/valid ride the carry: closure-captured
            # device-varying values are miscompiled by lax.scan under
            # partial-manual shard_map (jax 0.9), explicit operands are not
            hh, p, vln = carry
            lp, w, th = xs_l
            hh, kv = transformer.layer_apply(
                cfg, lp, hh,
                positions=p, valid_len=vln,
                window=w, theta=th, use_pallas=use_pallas,
            )
            return (hh, p, vln), kv

        for t in range(M + S - 1):
            m = t - s                      # microbatch index at this stage
            mi = jnp.clip(m, 0, M - 1)
            active = (m >= 0) & (m < M)
            x_in = jnp.where(s == 0, h0[mi], buf)
            (y, _, _), (k_l, v_l) = jax.lax.scan(
                layer_body,
                (x_in, pos_s[mi], val_s[mi]),
                (layers_local, windows_l, thetas_l),
            )
            out = out.at[mi].set(
                jnp.where(active & (s == last), y, out[mi])
            )
            k_out = k_out.at[mi].set(jnp.where(active, k_l, k_out[mi]))
            v_out = v_out.at[mi].set(jnp.where(active, v_l, v_out[mi]))
            if S > 1 and t < M + S - 2:
                buf = jax.lax.ppermute(y, "pipe", fwd)
        # replicate the last stage's outputs (zeros elsewhere => psum)
        out = jax.lax.psum(
            jnp.where(s == last, out, jnp.zeros_like(out)), "pipe"
        )
        return out, k_out, v_out

    fn = jax.shard_map(
        stage,
        mesh=mesh,
        in_specs=(P("pipe"), P("pipe"), P("pipe"), P(), P(), P()),
        out_specs=(P(), P(None, "pipe"), P(None, "pipe")),
        axis_names={"pipe"},
    )
    out, k_all, v_all = fn(
        params["layers"], windows, thetas, h0, pos_s, val_s
    )

    h_final = out.reshape(B, T, H)
    # [M, L, mb, T, KVH, Dh] -> [L, B, T, KVH, Dh]
    k_all = k_all.transpose(1, 0, 2, 3, 4, 5).reshape(L, B, T, KVH, Dh)
    v_all = v_all.transpose(1, 0, 2, 3, 4, 5).reshape(L, B, T, KVH, Dh)

    head_out, h_final = transformer.head_apply(cfg, params, h_final, valid_len)
    return head_out, h_final, (k_all, v_all)


def pipeline_decode(
    cfg: ModelConfig,
    params: Any,
    ids: jax.Array,          # [B, T] int32 (decode: T == 1)
    positions: jax.Array,    # [B, T] int32
    valid_len: jax.Array,    # [B] int32
    k_pages: jax.Array,      # [L, NP, PS, KVH*Dh] (layer axis pipe-sharded)
    v_pages: jax.Array,
    page_table: jax.Array,   # [B, MP] int32
    past_len: jax.Array,     # [B] int32
    mesh: Mesh,
    *,
    use_pallas: bool = False,
    window_past: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, jax.Array]]:
    """Stage-local decode step under ``pipe > 1``.

    The activation hops stages over ICI (``ppermute``); stage ``s`` runs
    its local layer slice only on iteration ``t == s`` (``lax.cond``), so
    each device touches exactly its own ``L/pp`` layers' weights and KV
    pages per decode step — per-device memory AND weight traffic are
    1/pp of the stack, unlike the GSPMD fallback which gathered every
    stage's weights everywhere. Same return contract as
    ``transformer.forward``.
    """
    S = int(mesh.shape["pipe"])
    B, T = ids.shape
    L, H = cfg.num_layers, cfg.hidden_size
    if L % S:
        raise ValueError(f"layers {L} not divisible by pipe size {S}")
    Lb = L // S
    KVH, Dh = cfg.num_kv_heads, cfg.head_dim

    h0 = transformer.embed_tokens(cfg, params, ids)  # [B, T, H]
    windows = jnp.asarray(cfg.window_array(), jnp.int32)
    thetas = transformer.rope_thetas(cfg)
    win_len = None if window_past is None else window_past[2]

    def stage(layers_local, windows_l, thetas_l, kp_local, vp_local,
              wk_local, wv_local, h0):
        s = jax.lax.axis_index("pipe")
        last = S - 1
        fwd = [(i, i + 1) for i in range(S - 1)]

        def layer_body(carry, xs_l):
            hh = carry
            lp, w, th, layer, wk_l, wv_l = xs_l
            # the stage-local pool [Lb, NP, PS, KD] is a constant of the
            # stage's layer scan and ``layer`` the index into it, as in
            # transformer.forward: no reader gets a per-layer slice
            hh, kv = transformer.layer_apply(
                cfg, lp, hh,
                positions=positions, valid_len=valid_len,
                window=w, theta=th,
                k_pages=kp_local, v_pages=vp_local, layer=layer,
                page_table=page_table, past_len=past_len,
                use_pallas=use_pallas,
                wk_l=wk_l, wv_l=wv_l, win_len=win_len,
            )
            return hh, kv

        def run_stage(x):
            return jax.lax.scan(
                layer_body, x,
                (layers_local, windows_l, thetas_l,
                 jnp.arange(Lb, dtype=jnp.int32), wk_local, wv_local),
            )

        k_out = jnp.zeros((Lb, B, T, KVH, Dh), h0.dtype)
        v_out = jnp.zeros_like(k_out)
        # the carry becomes pipe-varying after the first stage's layers;
        # mark it varying from the start so scan carry types line up
        buf = jax.lax.pcast(h0, ("pipe",), to="varying")
        y = buf
        for t in range(S):
            active = s == t
            y, (k_l, v_l) = jax.lax.cond(
                active,
                run_stage,
                lambda x: (
                    x,
                    jax.lax.pcast(
                        (jnp.zeros((Lb, B, T, KVH, Dh), h0.dtype),
                         jnp.zeros((Lb, B, T, KVH, Dh), h0.dtype)),
                        ("pipe",),
                        to="varying",
                    ),
                ),
                buf,
            )
            k_out = jnp.where(active, k_l, k_out)
            v_out = jnp.where(active, v_l, v_out)
            if S > 1 and t < S - 1:
                buf = jax.lax.ppermute(y, "pipe", fwd)
        # the full-trunk output lives on the last stage; zeros elsewhere
        out = jax.lax.psum(
            jnp.where(s == last, y, jnp.zeros_like(y)), "pipe"
        )
        return out, k_out, v_out

    if window_past is not None:
        wk_all, wv_all = window_past[0], window_past[1]
    else:  # zero-width dummy keeps the scan xs structure static;
        # attention ignores W == 0 windows (fused [.., KVH*Dh] layout,
        # matching runner._window_scan)
        wk_all = jnp.zeros((L, B, 0, KVH * Dh), h0.dtype)
        wv_all = jnp.zeros((L, B, 0, KVH * Dh), h0.dtype)
        win_len = jnp.asarray(0, jnp.int32)

    fn = jax.shard_map(
        stage,
        mesh=mesh,
        in_specs=(
            P("pipe"), P("pipe"), P("pipe"), P("pipe"), P("pipe"),
            P("pipe"), P("pipe"), P(),
        ),
        out_specs=(P(), P("pipe"), P("pipe")),
        axis_names={"pipe"},
    )
    h_final, k_all, v_all = fn(
        params["layers"], windows, thetas, k_pages, v_pages,
        wk_all, wv_all, h0,
    )
    head_out, h_final = transformer.head_apply(
        cfg, params, h_final, valid_len
    )
    return head_out, h_final, (k_all, v_all)
