"""Paged KV cache.

TPU-native replacement for the server-side KV management the reference
delegates to its remote fleet (SURVEY §2.3 row 1: "continuous-batching
scheduler ... paged-KV decode attention"). Layout:

- ``k_pages`` / ``v_pages``: ``[L, NP, PS, KVH*Dh]`` device arrays, L the
  model's ATTENTION layers (``ModelConfig.num_attn_layers``: every layer
  of a homogeneous model; a conv layer has no K/V). Page 0
  is a reserved garbage page — padding tokens scatter there, so the write
  path needs no masks or dynamic shapes. The KV-head and head-dim axes are
  stored FUSED as one trailing axis: the Pallas decode kernel's
  block-diagonal score/value matmuls contract over exactly that axis, and
  Mosaic supports collapsing leading dims of a fetched page but not
  merging (KVH, Dh) into the lane dim in-kernel — so the pool carries the
  kernel-native layout and the small per-step tensors reshape outside.
- ``page_table``: host-side ``numpy`` ``[B, MP]`` int32, passed into each
  jitted step as a device argument. Pages are allocated/freed by a
  host-side free list (allocation is control-plane work; the device only
  ever sees dense int32 tables).

- ``conv``: ``[NP, L_conv * (K-1) * H]``, for a model with conv layers
  (models/transformer.py ``conv_mixer``): a second kind of per-sequence
  state, kept PER PAGE, page-major and flat (a row a page: reads and
  writes are a gather and a scatter of whole rows on the major axis,
  and no axis of 2 or 8 is padded up to a tile). Row ``p`` holds, layer
  by layer, the state after the
  last token its sequence wrote into page p; a sequence at position
  ``start`` reads the page that holds position ``start - 1`` (zeros at
  ``start`` 0). Whatever shares, moves, hibernates or frees a page
  thereby carries the state with it: a whole-page prefix hit restores
  the state exactly, a released page needs no reset (a new sequence
  starts from zeros, and writes a page's state before it reads it).

- ``ssm`` ``[L_m, NS, N, I]`` and ``ssm_conv`` ``[NS, L_m * (K-1) * Cd]``,
  for a model with layers that keep a MATRIX state (Mamba-2's
  ``mamba_mixer`` or a delta rule's ``kda_mixer``; ``ModelConfig.
  state_kind`` / ``state_rows`` N / ``state_inner`` I / ``state_conv_dim``
  Cd are the one description both read): a THIRD kind of
  per-sequence state, too large to keep a page (a matrix a head: tens
  of MB a sequence), kept a SLOT a live sequence. Slot 0 is the garbage
  slot, and page 0, the garbage page, leads to it and is never
  re-pointed. ``state_slot`` ``[NP]`` int32 maps a page to a slot, on the
  device: a sequence at position ``start`` finds its slot through the
  page that holds position ``start - 1`` (its first page at ``start``
  0), as ``read_conv_state`` finds its page, and ``write_kv`` re-points
  the page that holds the chunk's last accepted token. The host
  (``StateSlots``) only hands a slot to a sequence's first page and
  takes it back with the row; a page's entry is always written before
  it is read, so a freed page or slot needs no reset. The state axis N
  is MAJOR and the channels I = heads x head_dim minor: a decode step
  reduces every slot against its row's C over N with no cross-lane
  work, reading the pool where it lies; no forward writes it.

- ``wk_pages`` / ``wv_pages``: ``[L_win, NP_w, PS, KVH*Dh]``, for a
  model whose attention layers are of two kinds (``layer_types`` with
  "swa" layers: a sliding window of ``W`` positions): K/V a POOL A
  KIND. A window layer can see a row's last W positions and nothing
  older, so its pool holds those and no more: about W / PS pages a
  sequence whatever its length, where the full layers' pool holds
  the whole context. There is ONE space of page ids (the full pool's,
  what the allocators and every page table speak) and a device map
  ``window_page`` ``[NP]`` int32 from a page id to the window pool's
  page that holds the same positions of the window layers (0: none;
  page 0 of the window pool is its garbage page). Every program looks
  its rows' window pages up itself (``window_table``), as it finds a
  state slot. The host (``WindowPages``, owned by the runner) binds a
  page id to a window page before the dispatch that writes it and
  takes the window page back once its last position is older than
  the row's committed length less W: from then on no query reads it
  (every reader starts at the page of position ``past_len - W + 1``:
  ops/attention.py ``live_pages``, the paged kernel's ``first_page``).
  A token written through an unbound page id lands on the garbage
  page: a prefill binds only what the window still holds at its end.
  With ``NP_w == NP`` the map is the identity and nothing is ever
  bound or released (the trivial setting: a runner given its pool's
  size, a mesh).

- a model of LATENT layers (``ModelConfig.num_latent_layers``:
  models/transformer.py ``mla_mixer``) keeps ONE pool and no V pool:
  ``k_pages`` ``[L_mla, NP, PS, page_width]`` holds, a token a row, the
  layer's normed latent values followed by the rotated key all heads
  share, and ``v_pages`` is None. Every head reads that row for both
  products (ops/attention.py ``latent_attention``), so nothing else of
  a token is kept. ``ModelConfig.page_width`` is THE place that says how
  wide a page's rows are (``num_kv_heads * head_dim`` for every other
  model): the pools' shapes, a page's bytes (the runner's
  ``_page_bytes_per_device``, ``_pool_margin_pages``) and the tier
  payloads read it there. The page table, the allocators and the
  garbage page are the same; int8 K/V (``kv_quantize``), a mesh and
  the tiers' payloads refuse such a pool by name. Under ``use_pallas``
  the paged decode kernel fetches such a page ONCE for both products
  (``paged_decode_attention(v_pages=None)``) and the in-place write
  lands one slab a segment (``pallas_kv.row_write_pallas``).

- latent layers with an INDEXER (``ModelConfig.index_topk``: learned
  sparse attention, ops/sparse_attention.py) keep a SECOND row a token:
  the index key, ``ik_pages`` ``[L_mla, NP, PS, index_head_dim]``, a pool
  of its own width on the SAME page table, allocators and garbage page.
  ``ModelConfig.pool_row_widths`` is the place that says what a token
  of a layer keeps in each pool. A chunk's index keys ride where V would
  (``forward``'s ``(k, v)`` pair, the fused window's second buffer,
  ``paged_past``'s second slot), so every caller that commits a chunk's
  K/V by ``write_kv(cache, k, v, ...)`` commits them too: a second call
  of the one-pool write.

``write_kv`` lands a chunk's K/V into pages (Pallas in-place RMW kernel
on TPU, XLA scatter fallback elsewhere); ``gather_kv_layer`` produces one
layer's contiguous ``[B, CTX, KVH, Dh]`` view for a chunk's attention
over a paged past by ONE gather on ``[layer, page_table]`` of the
stacked pool (``gather_pages``; a decode step keeps what that returns
fused: ops/attention.py). No reader is ever handed a per-layer
``[NP, PS, KD]`` slice: the stack is a constant of the layer scan and
the layer is an index (models/transformer.py). Both are pure functions over pytrees,
jitted as part of the runner's step functions.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.configs import ModelConfig
from ..models.transformer import (
    MixedChunk, StatePast, chunk_tokens, columns_after, group_channels,
    over_state, per_channel,
)
from ..ops.lowering import part
from .config import EngineConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k_pages: jax.Array  # [L, NP, PS, KVH*Dh] — bf16, or int8 quantized
    # [L, NP, PS, KVH*Dh]; None for a model of latent layers, whose
    # ``k_pages`` rows [.., page_width] serve both products
    v_pages: "jax.Array | None"
    # int8 KV mode (EngineConfig.kv_quantize): per-TOKEN dequant scales,
    # amax/127 over the fused KD axis. Per-token (not per-page) so a
    # decode append quantizes exactly once — no page rescale, no
    # clipping against a stale amax. Overhead: 4 bytes per token per
    # layer vs KD int8 bytes (<1% at KD=1024).
    k_scale: "jax.Array | None" = None  # [L, NP, PS] f32
    v_scale: "jax.Array | None" = None
    # conv layers' per-sequence state, per page (module docstring)
    conv: "jax.Array | None" = None     # [NP, L_conv * (K-1) * H]
    # mamba layers' per-sequence state, per SLOT, and the page -> slot map
    ssm: "jax.Array | None" = None         # [L_m, NS, N, I]
    ssm_conv: "jax.Array | None" = None    # [NS, L_m * (K-1) * Cd]
    state_slot: "jax.Array | None" = None  # [NP] int32
    # window attention layers' K/V, a pool of their own, and the page id
    # -> window page map (module docstring)
    wk_pages: "jax.Array | None" = None    # [L_win, NP_w, PS, KVH*Dh]
    wv_pages: "jax.Array | None" = None
    window_page: "jax.Array | None" = None  # [NP] int32
    # latent layers' index keys (an indexer: module docstring), beside
    # ``k_pages``' latent rows on the same page table
    ik_pages: "jax.Array | None" = None    # [L_mla, NP, PS, index_head_dim]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def num_window_pages(self) -> int:
        """Pages of the window pool, its garbage page included (0 for a
        model with one pool)."""
        return 0 if self.wk_pages is None else self.wk_pages.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def num_state_slots(self) -> int:
        """Slots of the state pool, the garbage slot included (0 for a
        model that keeps no matrix state)."""
        return 0 if self.ssm is None else self.ssm.shape[1]


def alloc_cache(
    mcfg: ModelConfig, ecfg: EngineConfig, num_pages: int,
    dtype: jnp.dtype = jnp.bfloat16,
    sharding: "jax.sharding.NamedSharding | None" = None,
    window_pages: "int | None" = None,
) -> KVCache:
    """Zeroed page pools; for a model with window attention layers a
    pool a kind, the window layers' of ``window_pages`` pages (None: as
    many as the full pool, under the identity map: nothing to bind or
    release); for a model with mamba layers also the state
    pools: beside the garbage slot a slot a row of the decode batch, and
    never more than there are pages, since a sequence holds at least
    one (``default_state_slots``). With ``sharding`` (parallel/sharding.py
    ``cache_shardings``) every pool is allocated sharded — never whole
    on one device first; the int8 per-token scale pools are
    shard-invariant (full-KD amax) and replicate across that mesh."""
    # what a token keeps in each pool: ``ModelConfig.pool_row_widths``
    widths = mcfg.pool_row_widths
    shape = (
        mcfg.num_pool_layers, num_pages, ecfg.kv_page_size, widths[0],
    )
    if mcfg.num_latent_layers and getattr(ecfg, "kv_quantize", None):
        raise NotImplementedError(
            f"{mcfg.name} keeps a latent row a token: the latent pool has "
            "no int8 scale pools (kv_quantize)"
        )
    if mcfg.num_latent_layers and sharding is not None:
        raise NotImplementedError(
            f"{mcfg.name} keeps a latent row a token: every head reads the "
            "whole row, so the latent pool does not shard over a mesh"
        )
    rep = None
    if sharding is not None:
        rep = jax.sharding.NamedSharding(
            sharding.mesh, jax.sharding.PartitionSpec()
        )
    conv = None
    if mcfg.num_conv_layers:
        # in the activation dtype (what the mixer computes g in);
        # replicated under a mesh, like the conv weights
        conv = jnp.zeros(
            (
                num_pages,
                mcfg.num_conv_layers * mcfg.conv_state_len
                * mcfg.hidden_size,
            ),
            jnp.dtype(ecfg.activation_dtype), device=rep,
        )
    state = {}
    if mcfg.num_state_layers:
        if mcfg.state_kind == "kda" and (
            getattr(ecfg, "kv_quantize", None) or sharding is not None
        ):
            raise NotImplementedError(
                f"{mcfg.name} keeps a delta-rule state a slot: "
                + ("int8 K/V beside it (kv_quantize)"
                   if getattr(ecfg, "kv_quantize", None) else
                   "the slot pool under a mesh")
                + " is not built"
            )
        state_slots = default_state_slots(ecfg, num_pages)
        # stored in the activation dtype, updated in float32; replicated
        # under a mesh, like the conv state
        act = jnp.dtype(ecfg.activation_dtype)
        state = dict(
            ssm=jnp.zeros(
                (mcfg.num_state_layers, 1 + state_slots, mcfg.state_rows,
                 mcfg.state_inner), act, device=rep,
            ),
            ssm_conv=jnp.zeros(
                (1 + state_slots, mcfg.num_state_layers
                 * mcfg.state_conv_len * mcfg.state_conv_dim),
                act, device=rep,
            ),
            state_slot=jnp.zeros((num_pages,), jnp.int32, device=rep),
        )
    if mcfg.num_window_layers:
        if getattr(ecfg, "kv_quantize", None):
            raise NotImplementedError(
                f"{mcfg.name} keeps K/V a pool a kind: the window pool "
                "has no int8 scale pools (kv_quantize)"
            )
        same = window_pages is None or window_pages == num_pages
        wshape = (mcfg.num_window_layers,
                  num_pages if same else window_pages) + shape[2:]
        state.update(
            wk_pages=jnp.zeros(wshape, dtype, device=sharding),
            wv_pages=jnp.zeros(wshape, dtype, device=sharding),
            window_page=(
                jnp.arange(num_pages, dtype=jnp.int32, device=rep) if same
                else jnp.zeros((num_pages,), jnp.int32, device=rep)
            ),
        )
    if getattr(ecfg, "kv_quantize", None) == "int8":
        return KVCache(
            conv=conv, **state,
            k_pages=jnp.zeros(shape, jnp.int8, device=sharding),
            v_pages=jnp.zeros(shape, jnp.int8, device=sharding),
            k_scale=jnp.zeros(shape[:3], jnp.float32, device=rep),
            v_scale=jnp.zeros(shape[:3], jnp.float32, device=rep),
        )
    if getattr(ecfg, "kv_quantize", None):
        raise ValueError(
            f"Unknown kv_quantize mode {ecfg.kv_quantize!r} (only 'int8')"
        )
    return KVCache(
        k_pages=jnp.zeros(shape, dtype, device=sharding),
        v_pages=(
            jnp.zeros(shape, dtype, device=sharding)
            if mcfg.pool_has_values else None
        ),
        ik_pages=(
            jnp.zeros(shape[:3] + (widths[1],), dtype)
            if mcfg.index_key_width else None
        ),
        conv=conv, **state,
    )


def default_state_slots(ecfg: EngineConfig, num_pages: int) -> int:
    return max(min(ecfg.decode_batch_size, num_pages - 1), 1)


def state_bytes_per_slot(mcfg: ModelConfig, ecfg: EngineConfig) -> int:
    """Bytes of matrix state one sequence keeps (its slot of both
    pools), from the one description of a state layer
    (``ModelConfig.state_rows`` ...): 0 for a model that keeps none."""
    per_layer = (
        mcfg.state_rows * mcfg.state_inner
        + mcfg.state_conv_len * mcfg.state_conv_dim
    )
    return (
        mcfg.num_state_layers * per_layer
        * jnp.dtype(ecfg.activation_dtype).itemsize
    )


class StateSlots:
    """Host-side allocator of the state pool's slots, beside the
    page free list: slot 0 is the garbage slot; a live sequence holds
    one slot, bound to its FIRST page (``bind`` is idempotent for a page
    that is bound, so a sequence written again from position 0 into the
    same pages keeps its slot). The device finds a row's slot from its
    pages (``KVCache.state_slot``); this class only says which slots
    are free."""

    def __init__(self, slots: int):
        self.total = slots
        self._free: List[int] = list(range(slots, 0, -1))  # pop() -> 1 first
        self._of_page: dict = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.total - len(self._free)

    def slot_of(self, page: int) -> "int | None":
        return self._of_page.get(int(page))

    def bind(self, page: int) -> "Tuple[int, bool]":
        """``(slot, new)`` for the sequence whose first page is
        ``page``; raises MemoryError when none is free."""
        page = int(page)
        slot = self._of_page.get(page)
        if slot is not None:
            return slot, False
        if not self._free:
            raise MemoryError("state pool out of slots")
        slot = self._free.pop()
        self._of_page[page] = slot
        return slot, True

    def release(self, page: int) -> None:
        slot = self._of_page.pop(int(page), None)
        if slot is not None:
            self._free.append(slot)

    def reset(self) -> None:
        self._free = list(range(self.total, 0, -1))
        self._of_page.clear()


def window_table(cache: KVCache, page_table: jax.Array) -> "jax.Array | None":
    """Each row's pages IN THE WINDOW POOL, ``[B, MP]``: the map applied
    to the row's table (0, the window pool's garbage page, for a page id
    the host has bound to none). None for a model with one pool."""
    if cache.window_page is None:
        return None
    return cache.window_page[page_table]


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


class WindowPages:
    """Host-side allocator of the window pool's pages, beside the page
    free list (module docstring): which window page a page id is bound
    to, which are free, and how many the admitted rows may still ask
    for. ``budget`` is admission's: a row reserves the most it will
    hold at once (``window_span_pages``), keyed by its first own page,
    so that a bind between two dispatches cannot find the pool empty.
    ``delta`` hands the device what changed since it was last asked."""

    def __init__(self, window_pages: int, num_pages: int):
        self.total = window_pages - 1          # page 0 is the garbage page
        self._free: List[int] = list(range(self.total, 0, -1))
        self.of_page = np.zeros((num_pages,), np.int32)
        self._reserved: dict = {}
        self._dirty: set = set()
        self.released_total = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.total - len(self._free)

    @property
    def budget_free(self) -> int:
        return self.total - sum(self._reserved.values())

    def set_budget(self, first_page: int, n: int) -> None:
        self._reserved[int(first_page)] = int(n)

    def bind(self, pages) -> None:
        """A window page for each page id of ``pages`` that has none
        (0 excepted). Raises MemoryError when the pool is out."""
        ids = np.unique(np.asarray(pages, np.int64))
        for p in ids[(ids > 0) & (self.of_page[ids] == 0)].tolist():
            if not self._free:
                raise MemoryError("window KV pool out of pages")
            self.of_page[p] = self._free.pop()
            self._dirty.add(p)

    def release(self, pages) -> int:
        """Take back the window pages of ``pages`` (those that have
        one); returns how many."""
        ids = np.unique(np.asarray(pages, np.int64))
        bound = ids[(ids > 0) & (self.of_page[ids] > 0)].tolist()
        for p in bound:
            self._free.append(int(self.of_page[p]))
            self.of_page[p] = 0
            self._dirty.add(p)
        return len(bound)

    def release_row(self, own_pages) -> None:
        """A row's pages go back to the allocator: with them its window
        pages and its reservation."""
        if len(own_pages):
            self._reserved.pop(int(own_pages[0]), None)
            self.release(own_pages)

    def delta(self) -> "Tuple[np.ndarray, np.ndarray] | None":
        """``(page ids, window pages)`` the device has yet to learn."""
        if not self._dirty:
            return None
        ids = np.fromiter(self._dirty, np.int32, len(self._dirty))
        self._dirty.clear()
        return ids, self.of_page[ids]

    def reset(self) -> None:
        self.release(np.nonzero(self.of_page)[0])
        self._reserved.clear()


def _quantize_tokens(x: jax.Array):
    """[..., KD] float -> (int8 values, f32 per-token scales [...])."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


class PageAllocator:
    """Host-side page allocator. Page 0 is reserved as the garbage page.

    Allocation is CONTIGUOUS-FIRST: a slot's reserved pages form one
    ascending run whenever a large-enough hole exists (first-fit over
    the sorted free set), falling back to scattered pages otherwise.
    Contiguous runs let the Pallas decode kernel fetch a row's whole
    context in a few chunked DMAs instead of one DMA per page — the
    dominant decode-attention cost measured in PERF.md. Since slots
    reserve their worst case up front and runs are uniform per job,
    fragmentation stays bounded in practice; correctness never depends
    on contiguity (the kernel and the gather fallback accept any
    table)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free: List[int] = list(range(1, num_pages))  # sorted asc

    def alloc(self, n: int = 1) -> List[int]:
        free = self._free
        if len(free) < n:
            raise MemoryError(
                f"KV cache out of pages (requested {n}, free {len(free)})"
            )
        # first-fit contiguous run over the sorted free list
        run_start = 0
        run_len = 1
        for i in range(1, len(free)):
            if free[i] == free[i - 1] + 1:
                run_len += 1
                if run_len == n:
                    pages = free[run_start : run_start + n]
                    del free[run_start : run_start + n]
                    return pages
            else:
                run_start = i
                run_len = 1
        if n == 1 and free:
            return [free.pop(0)]
        # no hole big enough: scattered fallback (ascending)
        pages = free[:n]
        del free[:n]
        return pages

    def free(self, pages: List[int]) -> None:
        import bisect

        for p in pages:
            if p != 0:
                bisect.insort(self._free, p)

    def reserve(self, pages: List[int]) -> None:
        """Remove SPECIFIC page ids from the free list. The engine-
        lifetime prefix store (engine/prefixstore.py) owns pages in the
        runner's pool across batcher sessions; each new session's fresh
        allocator must take them out of circulation before any
        admission. Atomic: raises KeyError leaving the free list
        untouched if any id (or duplicate) is not currently free."""
        import bisect

        free = self._free
        want = sorted(int(p) for p in pages)
        for a, b in zip(want, want[1:]):
            if a == b:
                raise KeyError(f"duplicate page id {a} in reserve()")
        for p in want:
            i = bisect.bisect_left(free, p)
            if i >= len(free) or free[i] != p:
                raise KeyError(f"page {p} is not free (cannot reserve)")
        drop = set(want)
        self._free = [p for p in free if p not in drop]

    @property
    def free_count(self) -> int:
        return len(self._free)


def pages_needed(length: int, page_size: int) -> int:
    return (length + page_size - 1) // page_size


#: tokens of a row one call of the one-pool write kernel takes
_ROW_WRITE_TOKENS = 2048


def _flat_slots(
    page_table: jax.Array, start: jax.Array, valid_len: jax.Array,
    T: int, PS: int,
) -> jax.Array:
    """[B, T] flat pool positions for a chunk's tokens; padding tokens
    route to garbage page 0. Single copy of the scatter index math for
    the quantized AND unquantized write paths."""
    pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
    page_idx = jnp.take_along_axis(page_table, pos // PS, axis=1)
    return jnp.where(valid, page_idx * PS + pos % PS, 0)


def _scatter_rows(pool: jax.Array, flat: jax.Array, rows: jax.Array):
    """``pool`` [L, NP, PS, ...] with ``rows`` [L, B, T, ...] written at
    the flat positions ``flat`` [B, T] of every layer: ONE index on the
    major axis of the pool seen as [L * NP * PS, ...] (a bitcast). As
    ``[:, flat]`` on [L, NP * PS, ...] the TPU compiler re-lays the
    whole pool out for the scatter (layer axis moved inward) and back:
    two copies of the pool a program."""
    L, NP, PS = pool.shape[:3]
    at = jnp.arange(L, dtype=jnp.int32)[:, None, None] * (NP * PS) + flat[None]
    out = pool.reshape((L * NP * PS,) + pool.shape[3:]).at[at].set(
        rows.astype(pool.dtype)
    )
    return out.reshape(pool.shape)


@part("cache")
def read_conv_state(
    cache: KVCache, page_table: jax.Array, start: jax.Array,
    layers: int, hidden: int,
) -> "jax.Array | None":
    """[L_conv, B, K-1, H]: each row's conv state at position ``start``
    ([B] int32), read from the page that holds position ``start - 1``;
    zeros for a row at ``start`` 0. None for a cache without conv
    state. ``layers`` and ``hidden`` are the model's (the pool's rows
    are flat)."""
    if cache.conv is None:
        return None
    slot = jnp.maximum(start - 1, 0) // cache.page_size
    page = jnp.take_along_axis(page_table, slot[:, None], axis=1)[:, 0]
    state = cache.conv[page].reshape(page.shape[0], layers, -1, hidden)
    state = jnp.where((start > 0)[:, None, None, None], state, 0)
    return state.transpose(1, 0, 2, 3)


@part("cache")
def write_conv_state(
    conv: jax.Array,           # [NP, L_conv * (K-1) * H] — the pool
    g_ext: jax.Array,          # [L_conv, B, K-1+T, H] (MixedChunk.conv)
    page_table: jax.Array,     # [B, MP] int32
    start: jax.Array,          # [B] int32 — global position of chunk token 0
    valid_len: jax.Array,      # [B] int32 — tokens of the chunk that count
    page_size: int,
) -> jax.Array:
    """Commit a chunk's conv state for ANY accepted length: for every
    page the chunk's first ``valid_len`` tokens touch, the state after
    the last of them in that page, ``g_ext[n : n+K-1]`` for n the
    tokens up to there. A row with ``valid_len`` 0 writes the garbage
    page. A gather and a scatter of a few columns a row: no second pass
    over the chunk."""
    Lc, B, W, H = g_ext.shape
    K1 = conv.shape[1] // (Lc * H)
    T, PS = W - K1, page_size
    M = (T + PS - 2) // PS + 1           # pages a chunk of T can touch
    end = start + valid_len
    slot = start[:, None] // PS + jnp.arange(M, dtype=jnp.int32)[None, :]
    n = jnp.minimum((slot + 1) * PS, end[:, None]) - start[:, None]  # [B, M]
    touched = (slot * PS < end[:, None]) & (valid_len[:, None] > 0)
    page = jnp.take_along_axis(
        page_table, jnp.minimum(slot, page_table.shape[1] - 1), axis=1
    )
    page = jnp.where(touched, page, 0)
    cols = jnp.clip(n, 0, T)[..., None] + jnp.arange(K1, dtype=jnp.int32)
    vals = jnp.take_along_axis(
        g_ext[:, :, None], cols[None, ..., None], axis=3
    )                                     # [Lc, B, M, K1, H]
    rows = vals.transpose(1, 2, 0, 3, 4).reshape(B, M, Lc * K1 * H)
    return conv.at[page].set(rows.astype(conv.dtype))


def state_slots_at(
    cache: KVCache, page_table: jax.Array, start: jax.Array
) -> jax.Array:
    """[B] int32: each row's slot of the mamba state pool, through the
    page that holds position ``start - 1`` (the row's first page at
    ``start`` 0, which the host bound)."""
    at = jnp.maximum(start - 1, 0) // cache.page_size
    page = jnp.take_along_axis(page_table, at[:, None], axis=1)[:, 0]
    return cache.state_slot[page]


@part("cache")
def read_state(
    cache: KVCache, page_table: jax.Array, start: jax.Array,
    layers: int, conv_dim: int,
) -> "StatePast | None":
    """A model's matrix state as ``transformer.forward`` reads it, for
    rows at ``start`` ([B] int32): the pool itself, each row's slot, and
    the rows' conv columns (a gather of a few KB a layer; zeros for a
    row at ``start`` 0). None for a cache without such state."""
    if cache.ssm is None:
        return None
    slots = state_slots_at(cache, page_table, start)
    fresh = start <= 0
    conv = cache.ssm_conv[slots].reshape(slots.shape[0], layers, -1, conv_dim)
    conv = jnp.where(fresh[:, None, None, None], 0, conv)
    return StatePast(
        ssm=cache.ssm, slots=slots, fresh=fresh,
        conv=conv.transpose(1, 0, 2, 3),
    )


def _advance_kda(
    ssm: jax.Array,            # [L_k, NS, dk, I]: the pool
    chunk: dict,               # "g" f32, "k", "u", "conv": MixedChunk.ssm
    slots: jax.Array,          # [B] int32 (0: the row does not move)
    fresh: jax.Array,          # [B] bool: the row's state before is 0
    n: jax.Array,              # [B] int32: tokens accepted
    K1: int,
    use_pallas: bool = False,
):
    """``S_n = Diag(exp G_n) S_0 + sum_{i<n} (k_i * exp(G_n - G_i)) u_i^T``
    for each row's slot, from the chunk's tokens (``transformer.
    kda_pending`` solved their ``u``), a layer at a time: ONE ``u`` serves
    every accepted length. Every decay is the pairwise ``exp(G_n - G_i)
    <= 1``. With ``use_pallas`` a row's slot is streamed in and out once,
    in place (``ops/pallas_ssm.kda_state_commit``); else the rows' slots
    are gathered, advanced and scattered back. Returns ``(pool, conv
    columns [L_k, B, K-1, Cd] after the n tokens)``."""
    from ..ops import lowering, pallas_ssm

    f32 = jnp.float32
    L, NS, dk, I = ssm.shape
    B = slots.shape[0]
    H = I // dk
    kernel = use_pallas and pallas_ssm.state_commit_supported(ssm)
    if use_pallas and not kernel:
        lowering.record_reference(lowering.KDA_STATE_COMMIT)

    def layer(pool, l):
        g, k, u, ext = (
            chunk_tokens(chunk[name], l, L, B)
            for name in ("g", "k", "u", "conv")
        )
        W = g.shape[1]
        took = (jnp.arange(W, dtype=jnp.int32)[None, :] < n[:, None])[..., None]
        g = jnp.where(took, g.astype(f32), 0.0)                    # [B, W, I]
        G = jnp.cumsum(g, axis=1)
        total = G[:, -1]                                           # [B, I]
        coef = jnp.where(
            took, jnp.exp(total[:, None] - G) * k.astype(f32), 0.0
        )
        decay = jnp.where(fresh[:, None], 0.0, jnp.exp(total))     # [B, I]
        with jax.named_scope("kda_commit"):
            if kernel:
                pool = pallas_ssm.kda_state_commit(
                    pool, l, slots, decay, coef, u.astype(f32)
                )
            else:
                S = pool[l][slots].astype(f32).reshape(B, dk, H, dk)
                new = jnp.swapaxes(
                    decay.reshape(B, H, dk), 1, 2
                )[..., None] * S + jnp.einsum(
                    "bwhk,bwhv->bkhv", coef.reshape(B, W, H, dk),
                    u.astype(f32).reshape(B, W, H, dk),
                    precision=jax.lax.Precision.HIGHEST,
                )
                pool = pool.at[l, slots].set(
                    new.reshape(B, dk, I).astype(pool.dtype)
                )
        return pool, columns_after(ext, n, K1)

    return jax.lax.scan(layer, ssm, jnp.arange(L, dtype=jnp.int32))


@part("cache")
def write_state(
    cache: KVCache,
    chunk: dict,               # MixedChunk.ssm
    page_table: jax.Array,     # [B, MP] int32
    start: jax.Array,          # [B] int32 — global position of chunk token 0
    valid_len: jax.Array,      # [B] int32 — tokens of the chunk that count
    use_pallas: bool = False,
) -> KVCache:
    """Commit a chunk's matrix state for its first ``valid_len`` tokens
    and point the page that holds the last of them at the row's slot. A
    row with ``valid_len`` 0 keeps its state. Two forms
    (``MixedChunk.ssm``): "final", the state a prefill computed, is
    scattered to the rows' slots; the tokens' own ``dt, dA, x, B`` (a
    decode window, a verify chunk) advance EVERY slot of the pool in one
    elementwise pass, in place: ``S <- decay S + sum_t c_t x_t B_t^T``
    with decay 1 and no tokens for a slot no row advances, so the state
    is neither gathered nor scattered. A delta-rule chunk's ``g, k, u``
    advance the rows' slots a row at a time (``_advance_kda``). A fused
    window hands its tokens over as its scan carried them
    (``transformer.window_buffer``) and each layer's are read from there
    where they lie (``transformer.chunk_tokens``): no transposed copy of
    the window's buffers is made for the commit."""
    slots = state_slots_at(cache, page_table, start)
    moved = valid_len > 0
    slots = jnp.where(moved, slots, 0)
    ssm, B = cache.ssm, slots.shape[0]
    L, NS = ssm.shape[:2]
    f32 = jnp.float32
    ext = chunk["conv"]                # [L, B, K-1+T', Cd], or a window's
    K1 = cache.ssm_conv.shape[1] // (L * ext.shape[-1])
    if "u" in chunk:
        ssm, cols = _advance_kda(
            ssm, chunk, slots, start <= 0, valid_len, K1,
            use_pallas=use_pallas,
        )
    elif "final" in chunk:
        at = jnp.arange(L, dtype=jnp.int32)[:, None] * NS + slots[None]
        ssm = ssm.reshape((L * NS,) + ssm.shape[2:]).at[at].set(
            chunk["final"].astype(ssm.dtype)
        ).reshape(ssm.shape)
        cols = ext                          # the columns after the chunk
    else:
        n = valid_len
        Hm = chunk["dt"].shape[-1]
        I = chunk["x"].shape[-1]
        P = I // Hm
        G = chunk["B"].shape[-1] // ssm.shape[2]
        # slot-major: each slot takes ITS row's tokens, or none
        row = jnp.zeros((NS,), jnp.int32).at[slots].set(
            jnp.arange(B, dtype=jnp.int32)
        )
        live = jnp.zeros((NS,), bool).at[slots].set(moved).at[0].set(False)
        fresh = start <= 0
        after = (n[:, None] + jnp.arange(K1, dtype=jnp.int32))[..., None]

        def layer(pool, l):
            # one layer at a time: the temporaries are a layer's, and
            # the pool is updated where it lies
            dt, dA, x, Bm, ext = (
                chunk_tokens(chunk[name], l, L, B)
                for name in ("dt", "dA", "x", "B", "conv")
            )
            W = dt.shape[1]
            took = jnp.arange(W, dtype=jnp.int32)[None, :] < n[:, None]  # [B, W]
            dt, dA = dt.astype(f32), dA.astype(f32)
            cum = jnp.cumsum(dA, axis=1)                              # [B, W, Hm]
            total = jnp.sum(jnp.where(took[..., None], dA, 0.0), axis=1)
            coef = jnp.where(
                took[..., None],
                jnp.exp(jnp.minimum(total[:, None] - cum, 0.0)) * dt, 0.0,
            )
            decay = jnp.where(fresh[:, None], 0.0, jnp.exp(total))    # [B, Hm]
            cx = per_channel(coef, P) * x.astype(f32)                # [B, W, I]
            decay = jnp.where(
                live[:, None], per_channel(decay, P)[row], 1.0
            )                                                         # [NS, I]
            cx = jnp.where(live[:, None, None], cx[row], 0.0)         # [NS, W, I]
            Bs = Bm[row].astype(f32)                                  # [NS, W, G*N]
            Bw = [over_state(Bs[:, w], G) for w in range(W)]
            for g, ch in enumerate(group_channels(I, G)):
                # a group's channels at a time (its B broadcasts over
                # them), each slice read and written where it lies
                at = (l, 0, 0, ch.start)
                new = jax.lax.dynamic_slice(
                    pool, at, (1, NS, pool.shape[2], ch.stop - ch.start)
                )[0].astype(f32) * decay[:, None, ch]
                for w in range(W):
                    new = new + Bw[w][g] * cx[:, w, None, ch]
                pool = jax.lax.dynamic_update_slice(
                    pool, new.astype(pool.dtype)[None], at
                )
            return pool, jnp.take_along_axis(ext, after, axis=1)

        ssm, cols = jax.lax.scan(
            layer, ssm, jnp.arange(L, dtype=jnp.int32)
        )
    rows = cols.transpose(1, 0, 2, 3).reshape(B, -1)      # [B, L * K1 * Cd]
    ssm_conv = cache.ssm_conv.at[slots].set(rows.astype(cache.ssm_conv.dtype))
    # the page of the last accepted token now leads to the slot
    last = jnp.maximum(start + valid_len - 1, 0) // cache.page_size
    page = jnp.take_along_axis(
        page_table, jnp.minimum(last, page_table.shape[1] - 1)[:, None], axis=1
    )[:, 0]
    # page 0 is the garbage page and leads to the garbage slot, always: a
    # row whose window runs past its reserved pages (its table holds 0
    # there) must not point it at a live slot, or every row that finds
    # its slot through page 0 (an empty batch slot, another row past its
    # pages) would advance that slot, or the row the slot goes to next
    moved = moved & (page != 0)
    state_slot = cache.state_slot.at[jnp.where(moved, page, 0)].set(
        jnp.where(moved, slots, 0)
    )
    return dataclasses.replace(
        cache, ssm=ssm, ssm_conv=ssm_conv, state_slot=state_slot
    )


@part("cache")
def write_kv(
    cache: KVCache,
    k_chunk: "jax.Array | MixedChunk",  # [L, B, T, KVH, Dh] or fused [L, B, T, KD]
    v_chunk: jax.Array,
    page_table: jax.Array,     # [B, MP] int32
    start: jax.Array,          # [B] int32 — global position of chunk token 0
    valid_len: jax.Array,      # [B] int32 — real tokens in chunk
    use_pallas: bool = False,
    kernel_mesh=None,          # mesh whose "model" axis shards KD
) -> KVCache:
    """Scatter a chunk's K/V into pages. Padding positions are routed to
    garbage page 0. With ``use_pallas`` the write is a true in-place DMA
    (ops/pallas_kv.py) instead of an XLA scatter over the full pool;
    under ``kernel_mesh`` each "model" shard writes its own KV heads.

    A ``MixedChunk`` in K's place (a model with conv layers) commits the
    conv state for the same ``valid_len`` beside the K/V; one whose
    ``conv`` is None commits K/V alone (a verify forward, whose accepted
    length is decided later: ``ModelRunner.commit_verified``)."""
    if isinstance(k_chunk, MixedChunk):
        if k_chunk.ssm is not None:
            cache = write_state(
                cache, k_chunk.ssm, page_table, start, valid_len,
                use_pallas=use_pallas and kernel_mesh is None,
            )
        conv = cache.conv
        if k_chunk.conv is not None:
            conv = write_conv_state(
                conv, k_chunk.conv, page_table, start, valid_len,
                cache.page_size,
            )
        if k_chunk.k is not None:  # None: state alone (commit_verified)
            cache = write_kv(
                cache, k_chunk.k, v_chunk, page_table, start, valid_len,
                use_pallas=use_pallas, kernel_mesh=kernel_mesh,
            )
        return dataclasses.replace(cache, conv=conv)
    if cache.wk_pages is not None:
        # a pool a kind: the chunk stacks the full layers' K/V, then the
        # window layers' (transformer._mixed_trunk); each part goes to
        # its own pool, the window layers' through the page id -> window
        # page map (an unbound page id: the garbage page)
        Lf = cache.k_pages.shape[0]
        full = dataclasses.replace(
            cache, wk_pages=None, wv_pages=None, window_page=None
        )
        win = dataclasses.replace(
            full, k_pages=cache.wk_pages, v_pages=cache.wv_pages
        )
        full = write_kv(
            full, k_chunk[:Lf], v_chunk[:Lf], page_table, start, valid_len,
            use_pallas=use_pallas, kernel_mesh=kernel_mesh,
        )
        win = write_kv(
            win, k_chunk[Lf:], v_chunk[Lf:],
            window_table(cache, page_table), start, valid_len,
            use_pallas=use_pallas, kernel_mesh=kernel_mesh,
        )
        return dataclasses.replace(
            cache, k_pages=full.k_pages, v_pages=full.v_pages,
            wk_pages=win.k_pages, wv_pages=win.v_pages,
        )
    if k_chunk.ndim == 4:  # already fused (decode window buffers)
        L, B, T, KD = k_chunk.shape
    else:
        L, B, T, KVH, Dh = k_chunk.shape
        KD = KVH * Dh
    PS = cache.page_size
    NP = cache.num_pages
    if cache.v_pages is None:
        # a latent pool: one row a token into ONE pool (it refuses a
        # mesh at construction, so there is no shard to write)
        if use_pallas:
            from ..ops import pallas_kv

            # the kernel keeps a row's whole run in VMEM (and a float32
            # copy of it for the roll): 2,048 rows of 640 at a time fit
            def land(pool, rows):
                for at in range(0, T, _ROW_WRITE_TOKENS):
                    part = rows[:, :, at:at + _ROW_WRITE_TOKENS]
                    pool = pallas_kv.row_write_pallas(
                        pool, part.astype(pool.dtype),
                        page_table.astype(jnp.int32),
                        (start + at).astype(jnp.int32),
                        jnp.clip(
                            valid_len - at, 0, part.shape[2]
                        ).astype(jnp.int32),
                    )
                return pool
        else:
            flat = _flat_slots(page_table, start, valid_len, T, PS)

            def land(pool, rows):
                return _scatter_rows(pool, flat, rows)

        if cache.ik_pages is None:
            return dataclasses.replace(
                cache, k_pages=land(cache.k_pages, k_chunk)
            )
        # an indexer's keys ride in V's place: the same write, a pool of
        # its own width
        return dataclasses.replace(
            cache, k_pages=land(cache.k_pages, k_chunk),
            ik_pages=land(cache.ik_pages, v_chunk),
        )
    if cache.quantized:
        # int8 KV: quantize per token, then the SAME flat scatter as
        # the unquantized fallback below (shared index helper), plus
        # the scale scatter. The in-place Pallas write kernel is
        # bf16-only — the XLA path serves the quantized cache.
        if use_pallas:
            from ..ops import lowering

            lowering.record_reference("kv_write")
        kq, ks = _quantize_tokens(k_chunk.reshape(L, B, T, KD))
        vq, vs = _quantize_tokens(v_chunk.reshape(L, B, T, KD))
        flat = _flat_slots(page_table, start, valid_len, T, PS)
        return dataclasses.replace(
            cache,
            k_pages=_scatter_rows(cache.k_pages, flat, kq),
            v_pages=_scatter_rows(cache.v_pages, flat, vq),
            k_scale=_scatter_rows(cache.k_scale, flat, ks),
            v_scale=_scatter_rows(cache.v_scale, flat, vs),
        )
    if use_pallas:
        from jax.sharding import PartitionSpec as P

        from ..ops.lowering import shard_over_model
        from ..ops.pallas_kv import kv_write_pallas

        kd = P(None, None, None, "model")
        k_pages, v_pages = shard_over_model(
            kernel_mesh,
            kv_write_pallas,
            dict(
                k_pages=cache.k_pages,
                v_pages=cache.v_pages,
                k_new=k_chunk.reshape(L, B, T, KD).astype(
                    cache.k_pages.dtype
                ),
                v_new=v_chunk.reshape(L, B, T, KD).astype(
                    cache.v_pages.dtype
                ),
                page_table=page_table.astype(jnp.int32),
                start=start.astype(jnp.int32),
                valid_len=valid_len.astype(jnp.int32),
            ),
            dict(
                k_pages=kd, v_pages=kd, k_new=kd, v_new=kd,
                page_table=P(), start=P(), valid_len=P(),
            ),
            (kd, kd),
        )
        return dataclasses.replace(cache, k_pages=k_pages, v_pages=v_pages)

    flat = _flat_slots(page_table, start, valid_len, T, PS)          # [B, T]
    return dataclasses.replace(
        cache,
        k_pages=_scatter_rows(
            cache.k_pages, flat, k_chunk.reshape(L, B, T, KD)
        ),
        v_pages=_scatter_rows(
            cache.v_pages, flat, v_chunk.reshape(L, B, T, KD)
        ),
    )


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


def make_page_table(rows: List[List[int]], max_pages: int) -> np.ndarray:
    """Pad per-slot page lists to a dense [B, MP] int32 table (garbage page 0)."""
    out = np.zeros((len(rows), max_pages), np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out
