"""Paged KV cache: ONE description of a model's pools.

TPU-native replacement for the server-side KV management the reference
delegates to its remote fleet (SURVEY §2.3 row 1: "continuous-batching
scheduler ... paged-KV decode attention").

**What a pool is.** A device array of ``KVCache`` with one axis that a
host allocator hands out entry by entry. ``cache_layout`` builds THE
description (``CacheLayout``) from the ``ModelConfig`` properties that
say what a layer keeps (``pool_row_widths``, ``page_width``,
``num_pool_layers``, ``num_window_layers``, ``num_conv_layers``,
``state_rows`` ...): for every array its field name, what indexes it,
its shape and dtype and whether it is sharded over ``"model"`` on its
last axis or replicated; and from those, once, a page's and a slot's
bytes on one device, what a row needs of each pool before it is admitted
(``RowPools``) and what the pages support (``CacheLayout.refuses``). It
is three parts: what a layout cannot hold is refused by name
(``_refuse_layout``), every array (``pool_arrays``), the answers
(``_support``). ``alloc_cache`` is a loop over it. Nobody else
multiplies widths: the runner's byte arithmetic, ``device_info``,
``sutro engine info``, the tier payloads' checks and the scheduler's
fallbacks read the description; who asks for bytes alone reads
``pool_bytes``, which refuses nothing.

**What indexes an array.**

- a PAGE (``PAGE``): ``k_pages`` / ``v_pages`` ``[L, NP, PS, width]``,
  the per-token rows of the layers that see the whole context. Page 0 is
  a reserved garbage page: padding tokens scatter there, so the write
  path needs no masks or dynamic shapes. The KV-head and head-dim axes
  are stored FUSED as one trailing axis: the Pallas decode kernel's
  block-diagonal products contract over exactly that axis, and Mosaic
  collapses leading dims of a fetched page but does not merge (KVH, Dh)
  into the lane dim in-kernel. A model of LATENT layers keeps one row a
  token for both products and no ``v_pages``; under an indexer a second
  row a token, ``ik_pages``, of its own width on the same page table (it
  rides where V would: ``write_kv``'s second operand). ``k_scale`` /
  ``v_scale`` are int8 K/V's per-token scales, shard-invariant (full-KD
  amax) and so replicated. ``conv`` ``[NP, ...]`` is a conv layer's
  state kept PER PAGE, page-major and flat: row ``p`` holds the state
  after the last token its sequence wrote into page p, so whatever
  shares, moves or frees a page carries the state with it and a freed
  page needs no reset (a page's state is written before it is read).
  Page ids are handed out by the page free list (``PageAllocator`` or
  the native runtime); ``page_table`` is a host ``[B, MP]`` int32 passed
  into each jitted step.
- a WINDOW PAGE (``WINDOW_PAGE``): ``wk_pages`` / ``wv_pages``, the
  K/V of layers that see a sliding window, a pool of their own that
  holds a row's last W positions and no more. There is ONE space of page
  ids (the full pool's) and a device map ``window_page`` ``[NP]``
  (``WINDOW_OF_PAGE``) from a page id to the window page that holds the
  same positions (0: none, the window pool's garbage page). The host
  (``WindowPages``) binds a page id before the dispatch that writes it
  and takes the window page back once its last position is older than
  the row's COMMITTED length less W: no reader starts before the page of
  ``past_len - W + 1`` (``ops/pages.first_live_page``). A token written
  through an unbound page id lands on the garbage page. With as many
  window pages as pages the map is the identity and nothing is bound or
  released (the trivial setting: a runner given its pool's size, a
  mesh).
- a SLOT (``SLOT``): ``ssm`` ``[L, NS, N, I]`` and ``ssm_conv``, a
  MATRIX state a sequence (Mamba-2's or a delta rule's:
  ``ModelConfig.state_kind``), too large to keep a page. Slot 0 is the
  garbage slot; ``state_slot`` ``[NP]`` (``SLOT_OF_PAGE``) maps a page to
  a slot on the device: a sequence at ``start`` finds its slot through
  the page that holds ``start - 1``, and ``write_state`` re-points the
  page of the chunk's last accepted token. The host (``StateSlots``)
  hands a slot to a sequence's first page and takes it back with the
  row; an entry is written before it is read, so nothing is reset. The
  state axis N is major and the channels minor: a decode step reduces
  every slot against its row's C with no cross-lane work.

**How a family adds a kind of state.** It says what a layer keeps in
``models/configs.py``, computes it in ``models/transformer.py`` and its
kernel, and here: a field of ``KVCache``, its ``PoolArray`` in
``pool_arrays`` (any refusal about layout in ``_refuse_layout``), its
commit in ``write_kv``, and, where the host must hand entries out, an
allocator behind ``RowPools``' verbs and its answers in ``_support``.
Not the scheduler, not the runner's bytes, not the tier code.

``write_kv`` lands a chunk's K/V into pages (Pallas in-place RMW kernel
on TPU, XLA scatter fallback elsewhere). The readers' gathers are pure
functions of arrays and live in ``ops/pages.py`` (re-exported here). No
reader is ever handed a per-layer ``[NP, PS, KD]`` slice: the stack is a
constant of the layer scan and the layer is an index
(models/transformer.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..models.configs import ModelConfig
from ..models.transformer import (
    MixedChunk, StatePast, chunk_tokens, columns_after, group_channels,
    over_state, per_channel,
)
from ..ops.lowering import part
from ..ops.pages import (  # noqa: F401  (re-exported: tests, perfbench)
    first_live_page, gather_kv_layer, gather_pages, window_span_pages,
)
from .config import EngineConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """The pools (module docstring; ``cache_layout`` says each array's
    shape). None: the model keeps no such array."""

    k_pages: jax.Array
    v_pages: "jax.Array | None" = None
    # int8 KV mode (EngineConfig.kv_quantize): per-TOKEN dequant scales,
    # amax/127 over the fused KD axis. Per-token (not per-page) so a
    # decode append quantizes exactly once — no page rescale, no
    # clipping against a stale amax. Overhead: 4 bytes per token per
    # layer vs KD int8 bytes (<1% at KD=1024).
    k_scale: "jax.Array | None" = None
    v_scale: "jax.Array | None" = None
    conv: "jax.Array | None" = None
    ssm: "jax.Array | None" = None
    ssm_conv: "jax.Array | None" = None
    state_slot: "jax.Array | None" = None
    wk_pages: "jax.Array | None" = None
    wv_pages: "jax.Array | None" = None
    window_page: "jax.Array | None" = None
    ik_pages: "jax.Array | None" = None

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


#: what indexes an array of the cache (module docstring): the entries a
#: host allocator hands out, and the two device maps from a page id
PAGE, WINDOW_PAGE, SLOT = "page", "window_page", "slot"
SLOT_OF_PAGE, WINDOW_OF_PAGE = "slot_of_page", "window_of_page"


@dataclasses.dataclass(frozen=True)
class PoolArray:
    """One array of ``KVCache``."""

    name: str                # the field
    index: str               # PAGE | WINDOW_PAGE | SLOT, or a map's name
    axis: int                # the axis the index runs over
    shape: Tuple[int, ...]
    dtype: Any
    sharded: bool = False    # over "model" on its last axis; else replicated
    #: values of a row in use (a latent row is padded to whole lane
    #: tiles: ``ModelConfig.page_width``); 0: all of them
    used: int = 0
    identity: bool = False   # a map born as the identity, not zeros


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """THE description of a model's pools (``cache_layout``)."""

    arrays: Tuple[PoolArray, ...]
    #: the sharding the sharded arrays carry (parallel/ says HOW a pool
    #: is partitioned: ``cache_shardings``, ``pp_cache_sharding``); None
    #: on one device
    sharding: Any = None
    page_size: int = 0
    num_pages: int = 0
    #: pages of the window pool, its garbage page included (0: one pool)
    window_pages: int = 0
    #: slots of the state pool beside the garbage slot (0: no such state)
    state_slots: int = 0
    #: a window layer's positions, and the most window pages a row holds
    #: at once (``row_window_span``)
    window: int = 0
    window_span: int = 0
    #: positions a block of the model holds (``ModelConfig.block_length``;
    #: 1: a causal model): a block's K/V is committed whole
    block_length: int = 1
    #: a forward leaves per-sequence state beside K/V (conv or matrix):
    #: a verify dispatch's caller commits it at the accepted length
    has_state: bool = False
    state_kind: Optional[str] = None
    #: question -> why not (``refuses``)
    refusals: Mapping[str, str] = dataclasses.field(default_factory=dict)

    @property
    def binds_window(self) -> bool:
        """The window pool has a size of its own: page ids are bound to
        window pages and released (False at the trivial setting)."""
        return 0 < self.window_pages != self.num_pages

    def array(self, name: str) -> Optional[PoolArray]:
        return next((a for a in self.arrays if a.name == name), None)

    def _device_shape(self, a: PoolArray) -> Tuple[int, ...]:
        if a.sharded and self.sharding is not None:
            return tuple(self.sharding.shard_shape(a.shape))
        return a.shape

    def entry_bytes(self, name: str, used: bool = False) -> int:
        """Bytes of ONE entry (page, window page, slot) of array ``name``
        as it sits on one device; ``used``: of the values in use alone.
        0 for a model that keeps no such array. THE place a page's bytes
        are multiplied out."""
        a = self.array(name)
        if a is None:
            return 0
        n = int(np.prod(self._device_shape(a))) // a.shape[a.axis]
        if used and a.used:
            n = n // a.shape[-1] * a.used
        return n * np.dtype(a.dtype).itemsize

    def bytes_per(self, index: str) -> int:
        """One entry of every array ``index`` indexes, on one device."""
        return sum(
            self.entry_bytes(a.name) for a in self.arrays if a.index == index
        )

    @property
    def page_bytes(self) -> int:
        """One page of the full pool on one device: K and V of every
        layer that sees the whole context (or a latent row, and an index
        key), int8 scales, the page's conv state."""
        return self.bytes_per(PAGE)

    @property
    def window_page_bytes(self) -> int:
        return self.bytes_per(WINDOW_PAGE)

    @property
    def slot_bytes(self) -> int:
        return self.bytes_per(SLOT)

    @property
    def margin_row_bytes(self) -> int:
        """One layer's rows of one page of ``k_pages`` on one device:
        what ``runner._pool_margin_pages`` counts its chunk in."""
        a = self.array("k_pages")
        return int(np.prod(self._device_shape(a)[2:])) * np.dtype(
            a.dtype
        ).itemsize

    def refuses(self, what: str) -> Optional[str]:
        """None where the pages support ``what``, else why not:

        - ``"share"``: rows SHARE a prefix's pages (a job's shared
          prefix, the prefix store). Not a model that keeps its state a
          slot a sequence (no page holds the state at the end of the
          shared pages; only the row that wrote them has it), nor one
          whose window pages are bound and released (a shared page's
          window page would go with the first row to slide past it; at
          the trivial setting a page id carries both kinds and prefixes
          work), nor a latent pool (every later row would take the
          absorbed form over pages no test or measurement has held yet),
          nor a model that generates by blocks (a row would be cut at a
          page's edge inside blocks whose K/V a later block's queries
          never saw committed that way). The answer is the reason label
          of ``sutro_state_fallback_prefill_tokens_total``.
        - ``"tiers"``: pages MOVE to the tiers and a row hibernates. The
          tiers' payload is a K and a V of one width a page: no slot, no
          second pool (window layers at ANY setting), no latent row. The
          answer is that counter's label too.
        - ``"native"``: the C++ host core admits (native/runtime.cpp).
        - ``"read_pages"`` / ``"write_pages"``: a payload of K and V of
          one width holds / restores this model's page (the runner's
          tier verbs raise the answer)."""
        return self.refusals.get(what)

    def alloc(self) -> KVCache:
        """Zeroed pools. With a sharding every pool is born sharded or
        replicated over that mesh, never whole on one device first."""
        rep = None
        if self.sharding is not None:
            rep = jax.sharding.NamedSharding(
                self.sharding.mesh, jax.sharding.PartitionSpec()
            )

        def born(a: PoolArray) -> jax.Array:
            device = self.sharding if a.sharded else rep
            if a.identity:
                return jnp.arange(a.shape[0], dtype=a.dtype, device=device)
            return jnp.zeros(a.shape, a.dtype, device=device)

        return KVCache(**{a.name: born(a) for a in self.arrays})


def default_state_slots(ecfg: EngineConfig, num_pages: int) -> int:
    return max(min(ecfg.decode_batch_size, num_pages - 1), 1)


def row_window_span(mcfg: ModelConfig, ecfg: EngineConfig) -> int:
    """The most window pages a row holds at once: its window and the
    tokens in flight (the fused windows dispatched past what the host
    has seen committed, or a verify chunk's inputs), never more than a
    row's table. 0 for a model with no window layers."""
    if not mcfg.num_window_layers:
        return 0
    in_flight = max(
        (ecfg.decode_lookahead + 1) * ecfg.decode_multi_step,
        ecfg.constrain_fastforward + 1,
    )
    return min(
        ecfg.max_pages_per_seq,
        window_span_pages(mcfg.sliding_window, in_flight, ecfg.kv_page_size),
    )


def _refuse_layout(mcfg: ModelConfig, ecfg: EngineConfig, sharding) -> None:
    """What a layout cannot hold is refused here, once, by name."""
    quant = ecfg.kv_quantize
    latent = mcfg.num_latent_layers > 0
    if latent and quant:
        raise NotImplementedError(
            f"{mcfg.name} keeps a latent row a token: the latent pool has "
            "no int8 scale pools (kv_quantize)"
        )
    if latent and sharding is not None:
        raise NotImplementedError(
            f"{mcfg.name} keeps a latent row a token: every head reads the "
            "whole row, so the latent pool does not shard over a mesh"
        )
    if (
        mcfg.num_state_layers and mcfg.state_kind == "kda"
        and (quant or sharding is not None)
    ):
        raise NotImplementedError(
            f"{mcfg.name} keeps a delta-rule state a slot: "
            + ("int8 K/V beside it (kv_quantize)" if quant else
               "the slot pool under a mesh")
            + " is not built"
        )
    if mcfg.state_kind == "mamba1" and sharding is not None:
        raise NotImplementedError(
            f"{mcfg.name} keeps a Mamba-1 state a slot beside differential "
            "heads read as pairs: the slot pool and the pairs under a mesh "
            "are not built"
        )
    if mcfg.num_window_layers and quant:
        raise NotImplementedError(
            f"{mcfg.name} keeps K/V a pool a kind: the window pool "
            "has no int8 scale pools (kv_quantize)"
        )
    if quant and quant != "int8":
        raise ValueError(
            f"Unknown kv_quantize mode {quant!r} (only 'int8')"
        )


def pool_arrays(
    mcfg: ModelConfig, ecfg: EngineConfig, num_pages: int = 2,
    dtype: jnp.dtype = jnp.bfloat16, window_pages: "int | None" = None,
) -> Tuple[PoolArray, ...]:
    """Every array of ``mcfg``'s ``KVCache`` at ``num_pages`` pages (and
    ``window_pages`` of the window layers' pool; None: as many, under
    the identity map). It refuses nothing, and an entry's bytes do not
    depend on the sizes: a reader of bytes alone (``pool_bytes``) leaves
    them out."""
    PS = ecfg.kv_page_size
    # what a token keeps in each pool: ``ModelConfig.pool_row_widths``
    widths = mcfg.pool_row_widths
    act = jnp.dtype(ecfg.activation_dtype)
    kv = jnp.dtype(jnp.int8 if ecfg.kv_quantize else dtype)
    pool = (mcfg.num_pool_layers, num_pages, PS)
    arrays = [PoolArray(
        "k_pages", PAGE, 1, pool + (widths[0],), kv, sharded=True,
        used=mcfg.latent_width if mcfg.num_latent_layers else 0,
    )]
    if mcfg.pool_has_values:
        arrays.append(PoolArray(
            "v_pages", PAGE, 1, pool + (widths[0],), kv, sharded=True
        ))
    if ecfg.kv_quantize:
        f32 = jnp.dtype(jnp.float32)
        arrays += [
            PoolArray("k_scale", PAGE, 1, pool, f32),
            PoolArray("v_scale", PAGE, 1, pool, f32),
        ]
    if mcfg.index_key_width:
        arrays.append(PoolArray(
            "ik_pages", PAGE, 1, pool + (widths[1],), jnp.dtype(dtype)
        ))
    if mcfg.num_conv_layers:
        # in the activation dtype (what the mixer computes g in);
        # replicated under a mesh, like the conv weights
        arrays.append(PoolArray("conv", PAGE, 0, (
            num_pages,
            mcfg.num_conv_layers * mcfg.conv_state_len * mcfg.hidden_size,
        ), act))
    if mcfg.num_state_layers:
        # beside the garbage slot a slot a row of the decode batch, and
        # never more than there are pages, since a sequence holds at
        # least one. Stored in the activation dtype, updated in float32;
        # replicated under a mesh, like the conv state
        L, NS = mcfg.num_state_layers, 1 + default_state_slots(ecfg, num_pages)
        arrays += [
            PoolArray("ssm", SLOT, 1, (
                L, NS, mcfg.state_rows, mcfg.state_inner,
            ), act),
            PoolArray("ssm_conv", SLOT, 0, (
                NS, L * mcfg.state_conv_len * mcfg.state_conv_dim,
            ), act),
            PoolArray(
                "state_slot", SLOT_OF_PAGE, 0, (num_pages,),
                jnp.dtype(jnp.int32),
            ),
        ]
    if mcfg.num_window_layers:
        same = window_pages is None or window_pages == num_pages
        wshape = (
            mcfg.num_window_layers, num_pages if same else window_pages,
            PS, widths[0],
        )
        arrays += [
            PoolArray("wk_pages", WINDOW_PAGE, 1, wshape, kv, sharded=True),
            PoolArray("wv_pages", WINDOW_PAGE, 1, wshape, kv, sharded=True),
            PoolArray(
                "window_page", WINDOW_OF_PAGE, 0, (num_pages,),
                jnp.dtype(jnp.int32), identity=same,
            ),
        ]
    return tuple(arrays)


def pool_bytes(
    mcfg: ModelConfig, ecfg: EngineConfig,
    dtype: jnp.dtype = jnp.bfloat16, sharding=None,
) -> CacheLayout:
    """The description's byte functions alone (``entry_bytes``,
    ``page_bytes``, ``slot_bytes`` ...): no sizes, no answers, and
    nothing refused. For who only asks what an entry weighs."""
    return CacheLayout(
        arrays=pool_arrays(mcfg, ecfg, dtype=dtype), sharding=sharding
    )


def _support(mcfg: ModelConfig, bound: bool) -> Mapping[str, str]:
    """What the pages do NOT support, question -> why
    (``CacheLayout.refuses``); ``bound``: the window pool has a size of
    its own. The first reason that applies: a model of several kinds
    reports ONE."""
    slots = mcfg.num_state_layers > 0
    windows = mcfg.num_window_layers > 0
    latent = mcfg.num_latent_layers > 0
    blocks = mcfg.block_length > 1
    ladders = {
        "share": (
            (slots, "prefix_without_state_snapshot"),
            (bound, "prefix_without_window_pages"),
            (latent, "prefix_on_latent_pool"),
            (blocks, "prefix_on_block_model"),
        ),
        "tiers": (
            (slots, "hibernate_without_slot_state"),
            (latent, "hibernate_on_latent_pool"),
            (blocks, "hibernate_on_block_model"),
            (windows, "hibernate_without_window_pages"),
        ),
        "native": (
            (blocks, "the C++ core counts one token a row a step, and a "
                     "window of a block model yields whole blocks"),
        ),
        "read_pages": (
            (latent, "pages of a latent pool do not move to the tiers (the "
                     "payload is a K and a V of one width)"),
            # a page id's window page may be gone, and the tiers' payload
            # has no place for a second pool: the caller prefills again
            (windows, "pages of a model that keeps K/V a pool a kind do "
                      "not move to the tiers (no window pages in the "
                      "payload)"),
        ),
        "write_pages": (
            (latent, "a K/V page payload cannot restore the rows of a "
                     "latent pool"),
            (windows, "pages cannot restore the window pages of a model "
                      "that keeps K/V a pool a kind"),
            # a slot's state is in no page: the caller prefills again
            (slots, "pages cannot restore the state of a model that keeps "
                    "it a slot a sequence"),
        ),
    }
    found = {
        what: next((why for applies, why in ladder if applies), None)
        for what, ladder in ladders.items()
    }
    return {what: why for what, why in found.items() if why}


def cache_layout(
    mcfg: ModelConfig, ecfg: EngineConfig, num_pages: int,
    dtype: jnp.dtype = jnp.bfloat16,
    sharding: "jax.sharding.NamedSharding | None" = None,
    window_pages: "int | None" = None,
) -> CacheLayout:
    """The description of ``mcfg``'s pools at ``num_pages`` pages: what
    a layout cannot hold refused by name (``_refuse_layout``), every
    array (``pool_arrays``), and what the pages support (``_support``).
    ``sharding`` is what the K/V pools carry (the rest replicate over
    its mesh)."""
    _refuse_layout(mcfg, ecfg, sharding)
    arrays = pool_arrays(mcfg, ecfg, num_pages, dtype, window_pages)
    shape = {a.name: a.shape for a in arrays}
    slots, windows = "ssm" in shape, "wk_pages" in shape
    in_window = shape["wk_pages"][1] if windows else 0
    return CacheLayout(
        arrays=arrays, sharding=sharding, page_size=ecfg.kv_page_size,
        num_pages=num_pages, window_pages=in_window,
        state_slots=shape["ssm"][1] - 1 if slots else 0,
        window=mcfg.sliding_window if windows else 0,
        window_span=row_window_span(mcfg, ecfg),
        block_length=mcfg.block_length,
        has_state=bool(mcfg.num_conv_layers or slots),
        state_kind=mcfg.state_kind if slots else None,
        refusals=_support(mcfg, bound=0 < in_window != num_pages),
    )


#: what the scheduler takes for a runner that offers no description (a
#: test's stub): a single K/V pool that supports everything
ONE_POOL = CacheLayout(arrays=())


def alloc_cache(
    mcfg: ModelConfig, ecfg: EngineConfig, num_pages: int,
    dtype: jnp.dtype = jnp.bfloat16,
    sharding: "jax.sharding.NamedSharding | None" = None,
    window_pages: "int | None" = None,
) -> KVCache:
    """Zeroed pools of ``cache_layout``'s description."""
    return cache_layout(
        mcfg, ecfg, num_pages, dtype, sharding, window_pages
    ).alloc()


def state_bytes_per_slot(mcfg: ModelConfig, ecfg: EngineConfig) -> int:
    """Bytes of matrix state one sequence keeps (its slot of both
    pools): 0 for a model that keeps none."""
    return pool_bytes(mcfg, ecfg).slot_bytes


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


def _tables(page_tables) -> np.ndarray:
    return np.asarray(page_tables).reshape(-1, np.shape(page_tables)[-1])


class RowPools:
    """ONE admission interface over what a row needs of each pool beside
    the page free list: the host allocators a layout calls for
    (``StateSlots``, ``WindowPages``; the next kind of state adds its own
    here) behind the verbs the scheduler calls for every model, in the
    order admission checks (state slot, window budget, then pages):
    ``reset`` at a session's start, ``room`` for a row, ``bind`` and
    ``release`` with its pages, ``slide`` behind the committed lengths.
    For a model with one pool each returns at once. The runner owns it,
    calls ``bind_fresh`` / ``bind_written`` before its own dispatches,
    and is told what the device has to learn (``tell_slots(pairs)``,
    ``tell_window(ids, window pages)``: one small dispatch each)."""

    def __init__(
        self, layout: CacheLayout,
        tell_slots: Optional[Callable] = None,
        tell_window: Optional[Callable] = None,
    ):
        self.layout = layout
        # the host's side of the state pool: which slots are free. None
        # for a model that keeps no such state
        self.slots = (
            StateSlots(layout.state_slots) if layout.state_slots else None
        )
        # the host's side of the window pool; None for a model with one
        # pool and at the trivial setting
        self.window = (
            WindowPages(layout.window_pages, layout.num_pages)
            if layout.binds_window else None
        )
        self._tell_slots, self._tell_window = tell_slots, tell_window
        self._unbound: list = []  # (page, slot) the device has yet to learn

    # -- the scheduler's verbs ------------------------------------------

    def reset(self) -> None:
        """A new session's pages are all free: every slot and window
        page is too."""
        if self.slots is not None:
            self.slots.reset()
            self._unbound.clear()
            self._note_slots()
        if self.window is not None:
            self.window.reset()
            self._flush_window()

    def room(
        self, total_tokens: int, chunked: bool,
        batch_slot_free: Optional[Callable[[], bool]] = None,
    ) -> Optional[int]:
        """Is there room for a row of ``total_tokens`` (prompt and new;
        its prefill ``chunked`` or not) beside the pages it will ask
        for? None: admission waits, as it waits for pages; else what
        ``bind`` reserves for it. A wait for a state slot while the
        batch has a free slot is counted, where the caller counts
        (``batch_slot_free`` given: the scheduler's latched flag) and
        the switch is still on."""
        if self.slots is not None and self.slots.free_count < 1:
            if (
                telemetry.ENABLED and batch_slot_free is not None
                and batch_slot_free()
            ):
                telemetry.STATE_SLOT_WAITS_TOTAL.inc(1.0)
            return None
        if self.window is None:
            return 0
        # admission waits for pages of BOTH kinds: the row reserves the
        # most window pages it will hold at once. A row whose prefill
        # runs in chunks over a paged past holds, at a chunk's dispatch,
        # the window before the chunk and the window at its end
        need = min(
            -(-total_tokens // self.layout.page_size),
            self.layout.window_span * (2 if chunked else 1),
        )
        return None if need > self.window.budget_free else need

    def bind(self, table, own_pages, room: int) -> None:
        """The row admitted through ``table`` ([MP]) with ``own_pages``
        takes what ``room`` promised: a state slot for its first page
        (the device is told with the row's prefill) and its window
        budget."""
        if self.slots is not None:
            self.bind_first([table[0]], flush=False)
        if room and len(own_pages):
            self.window.set_budget(own_pages[0], room)

    def release(self, own_pages) -> None:
        """With a row's pages goes its state slot (bound to the first;
        the device is not told: a page's entry is written before it is
        read), and its window pages and their reservation."""
        if self.slots is not None and len(own_pages):
            first = int(own_pages[0])
            self.slots.release(first)
            self._unbound = [u for u in self._unbound if u[0] != first]
            self._note_slots()
        if self.window is not None:
            self.window.release_row(own_pages)

    def release_behind(self, page_tables, committed) -> int:
        """Take back the window pages whose last position is older than
        ``committed[b] - window``: no query at ``committed[b]`` or later
        sees them, and every dispatch in flight was given a length of
        at least that. The caller passes COMMITTED lengths (never a
        projection over tokens in flight). The device learns with the
        next bind; it never reads such a page meanwhile. Returns the
        pages released."""
        pool = self.window
        if pool is None:
            return 0
        tables = _tables(page_tables)
        first = first_live_page(
            np.asarray(committed, np.int64).reshape(-1, 1),
            self.layout.window, self.layout.page_size,
        )
        ids = tables[np.arange(tables.shape[1])[None, :] < first]
        n = pool.release(ids[pool.of_page[ids] > 0])
        if n:
            pool.released_total += n
            if telemetry.ENABLED:
                telemetry.KV_WINDOW_PAGES_RELEASED_TOTAL.inc(float(n))
        return n

    def slide(self, table, past_len, active, count: bool = False) -> bool:
        """Give back what has slid out behind each ``active`` row's
        COMMITTED length (``past_len`` is what the host has accepted:
        tokens of windows in flight are not in it, so a page one of them
        still reads is never released), and where the caller counts note
        how much of its K/V a window layer holds. False where nothing
        slides."""
        if self.window is None:
            return False
        rows = np.asarray(active, np.int64)
        self.release_behind(table[rows], past_len[rows])
        if count and telemetry.ENABLED:
            PS = self.layout.page_size
            telemetry.KV_WINDOW_PAGES_HELD_TOTAL.inc(float(self.window.in_use))
            telemetry.KV_WINDOW_PAGES_WHOLE_TOTAL.inc(
                float((-(-past_len[rows].astype(np.int64) // PS)).sum())
            )
        return True

    # -- the runner's, before a dispatch ---------------------------------

    def bind_first(self, first_pages, flush: bool = True) -> None:
        """Give each sequence that starts at the head of one of
        ``first_pages`` a slot of the state pool (one that is bound
        keeps its slot). The device learns of new bindings in ONE small
        dispatch, at once or (``flush`` False: admission, a row at a
        time) before the next prefill, which is the first program to
        read them. Raises MemoryError when the pool has no free slot:
        admission asks ``room`` first."""
        if self.slots is None:
            return
        for p in first_pages:
            if int(p) > 0:
                slot, new = self.slots.bind(p)
                if new:
                    self._unbound.append((int(p), slot))
        self._note_slots()
        if flush and self._unbound:
            pairs, self._unbound = self._unbound, []
            self._tell_slots(pairs)

    def bind_fresh(self, page_tables, starts) -> None:
        """The prefill entry points' own ask: rows that start a sequence
        get a slot if whoever admitted them bound none."""
        if self.slots is not None:
            self.bind_first([
                t[0] for t, st in zip(_tables(page_tables), starts)
                if int(st) == 0
            ])

    def bind_written(self, page_tables, starts, lens) -> None:
        """Before a dispatch that writes ``lens[b]`` tokens from
        position ``starts[b]`` through ``page_tables[b]``: bind a window
        page to each page id that holds one of those tokens the window
        at the chunk's end still sees (the others land on the garbage
        page), and tell the device what changed since it was last told,
        releases included, in ONE small dispatch."""
        if self.window is None:
            return
        PS, W = self.layout.page_size, self.layout.window
        tables = _tables(page_tables)
        st = np.asarray(starts, np.int64).reshape(-1, 1)
        n = np.asarray(lens, np.int64).reshape(-1, 1)
        j = np.arange(tables.shape[1])[None, :]
        keep = (
            (j >= np.maximum(st, st + n - W + 1) // PS)
            & (j <= (st + n - 1) // PS) & (n > 0)
        )
        self.window.bind(tables[keep])
        self._flush_window()

    def _flush_window(self) -> None:
        """Tell the device the window bindings that changed."""
        delta = self.window.delta()
        if delta is None:
            return
        self._tell_window(*delta)
        if telemetry.ENABLED:
            pool = self.window
            telemetry.KV_PAGES.set(float(pool.in_use), "window", "used")
            telemetry.KV_PAGES.set(float(pool.free_count), "window", "free")

    def _note_slots(self) -> None:
        if telemetry.ENABLED:
            telemetry.STATE_SLOTS.set(float(self.slots.in_use), "in_use")
            telemetry.STATE_SLOTS.set(float(self.slots.total), "total")


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


def _advance_mamba1(
    ssm: jax.Array,            # [L, NS, N, I]: the pool
    chunk: dict,               # "dt" f32, "x", "B", "A", "conv": MixedChunk.ssm
    slots: jax.Array,          # [B] int32 (0: the row does not move)
    fresh: jax.Array,          # [B] bool: the row's state before is 0
    n: jax.Array,              # [B] int32: tokens accepted
    K1: int,
):
    """``S_n = exp(A t_n) * S_0 + sum_{i<n} exp(A (t_n - t_i)) * B_i (dt_i
    x_i)^T`` for each row's slot, ``t_i`` the step sizes summed up to and
    with token i: ONE pass serves every accepted length (a token not
    taken has ``dt`` 0: it neither decays nor feeds). The decay is a
    value a channel AND a state column, so each token's is formed
    (``W`` exponentials a state element, once a chunk), every one at
    most 1. The rows' slots are gathered, advanced and scattered back,
    a layer at a time. Returns ``(pool, conv columns [L, B, K-1, I]
    after the n tokens)``."""
    f32 = jnp.float32
    L = ssm.shape[0]
    B = slots.shape[0]

    def layer(pool, l):
        dt, x, Bm, ext = (
            chunk_tokens(chunk[name], l, L, B)
            for name in ("dt", "x", "B", "conv")
        )
        A = chunk["A"][l]                                          # [N, I]
        W = dt.shape[1]
        took = (jnp.arange(W, dtype=jnp.int32)[None, :] < n[:, None])[..., None]
        dt = jnp.where(took, dt.astype(f32), 0.0)                  # [B, W, I]
        t = jnp.cumsum(dt, axis=1)
        left = t[:, -1:] - t                                       # [B, W, I]
        fed = dt * x.astype(f32)
        with jax.named_scope("mamba1_commit"):
            S = pool[l][slots].astype(f32)                         # [B, N, I]
            S = jnp.where(fresh[:, None, None], 0.0, S)
            new = jnp.exp(t[:, -1, None, :] * A) * S
            for w in range(W):
                new = new + jnp.exp(left[:, w, None, :] * A) * (
                    Bm[:, w, :, None].astype(f32) * fed[:, w, None, :]
                )
            pool = pool.at[l, slots].set(new.astype(pool.dtype))
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
    elif "A" in chunk:      # a Mamba-1 chunk's tokens (a prefill hands "final")
        ssm, cols = _advance_mamba1(
            ssm, chunk, slots, start <= 0, valid_len, K1
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


def make_page_table(rows: List[List[int]], max_pages: int) -> np.ndarray:
    """Pad per-slot page lists to a dense [B, MP] int32 table (garbage page 0)."""
    out = np.zeros((len(rows), max_pages), np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out
