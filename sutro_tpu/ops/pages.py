"""Pure functions of a page pool's arrays: which of a row's pages a
window still sees, and one layer's pages gathered through a page table.
The attention paths (ops/attention.py) and the engine's cache
(engine/kvcache.py, which names the pools and re-exports these) both
read them here; nothing in this module knows a model or an engine."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def window_span_pages(window: int, in_flight: int, page_size: int) -> int:
    """The most window pages one sequence holds at once: its last
    ``window`` positions and the ``in_flight`` tokens dispatched past
    what the host has seen committed, however they lie on pages."""
    return (window + in_flight + page_size - 2) // page_size + 1


def first_live_page(past_len, window: int, page_size: int):
    """The slot of a row's table that holds the oldest position a query
    at ``past_len`` (numpy or jax, any shape) sees through ``window``:
    what every reader of a window pool starts at, and what the host
    releases behind."""
    return (past_len - window + 1).clip(0) // page_size


def gather_pages(
    k_pages: jax.Array,  # [L, NP, PS, KVH*Dh] — the stacked pool
    v_pages: jax.Array,
    layer: jax.Array,  # scalar int32 — the layer to read
    page_table: jax.Array,  # [B, MP] int32
    k_scale: "jax.Array | None" = None,  # [L, NP, PS] (int8 KV mode)
    v_scale: "jax.Array | None" = None,
    out_dtype=None,  # dequant target (compute dtype); None => float32
) -> Tuple[jax.Array, jax.Array]:
    """Every row's pages of one layer, ``[B * MP, PS, KD]`` x2 in the
    pool's own fused layout, as ONE gather on ``[layer, page_table]`` of
    the stack (never a slice of the layer's pool followed by a gather:
    the slice would be a copy of it).
    With int8 KV scales the gathered pages are dequantized here, INTO
    the caller's compute dtype — a float32 view would quadruple the
    gathered context's bytes and promote the whole XLA attention to
    f32, doubling the HBM traffic the int8 cache exists to halve."""
    L, NP, PS, KD = k_pages.shape
    # rows of the stack seen flat, [L * NP, PS, KD] (a bitcast): ONE
    # index on the major axis. Indexed as [layer, pages] the TPU
    # compiler re-lays the whole pool out for the gather (layer axis
    # moved inward) and back for the next write: two copies of the pool
    # a program, and their bytes among its temporaries
    pages = layer * NP + page_table.reshape(-1)
    k = k_pages.reshape(L * NP, PS, KD)[pages]  # [B*MP, PS, KD]
    v = v_pages.reshape(L * NP, PS, KD)[pages]
    if k_scale is not None:
        dt = out_dtype or jnp.float32
        ks = k_scale.reshape(L * NP, PS)[pages]
        vs = v_scale.reshape(L * NP, PS)[pages]
        k = (k.astype(jnp.float32) * ks[..., None]).astype(dt)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(dt)
    return k, v


def gather_kv_layer(
    k_pages: jax.Array,  # [L, NP, PS, KVH*Dh] — the stacked pool
    v_pages: jax.Array,
    layer: jax.Array,  # scalar int32 — the layer to read
    page_table: jax.Array,  # [B, MP] int32
    kv_heads: int,
    k_scale: "jax.Array | None" = None,  # [L, NP, PS] (int8 KV mode)
    v_scale: "jax.Array | None" = None,
    out_dtype=None,  # dequant target (compute dtype); None => float32
) -> Tuple[jax.Array, jax.Array]:
    """Per-layer page gather: [B, MP] table -> ([B, CTX, KVH, Dh]) x2,
    CTX = MP * PS (``gather_pages``, head-split). Used inside the layer
    scan so only one layer's context view is ever live. This view
    serves a chunk over a paged past (T > 1: chunked prefill, verify
    forwards); one decode step reads its pages in place (the Pallas
    paged kernel) or keeps the gathered pages fused
    (``ops/attention.paged_decode_xla``)."""
    PS, KD = k_pages.shape[2:]
    B, MP = page_table.shape
    k, v = gather_pages(
        k_pages, v_pages, layer, page_table, k_scale, v_scale, out_dtype
    )
    return (
        k.reshape(B, MP * PS, kv_heads, KD // kv_heads),
        v.reshape(B, MP * PS, kv_heads, KD // kv_heads),
    )


