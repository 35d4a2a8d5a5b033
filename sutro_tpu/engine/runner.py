"""Model runner: compiled prefill / decode / embed steps.

The device-side half of the engine (SURVEY §7.1 ``runner.py``). The
reference's equivalent is the remote fleet's decode loop, visible only
through its progress stream (/root/reference/sutro/sdk.py:331-367); here it
is three jitted functions over static shapes:

- ``prefill(ids[1,T])``: full causal attention over one (bucketed) prompt,
  K/V scattered into the paged cache, returns last-position logits.
  Buckets are powers of two, so at most log2(max_ctx) compilations.
- ``decode(ids[B,1])``: one token for every slot in the fixed-size decode
  batch; past gathered from pages, new K/V scattered back, sampling fused
  in (with optional constrained-decoding vocab masks).
- ``embed(ids[B,T])``: trunk + pooled head (last-token for Qwen3-Embedding).

Host-side state (slots, page tables, FSM states) lives in
engine/scheduler.py; this module is stateless apart from params + cache.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..models import transformer
from ..models.configs import ModelConfig
from ..models.transformer import MixedChunk
from . import faults
from .config import EngineConfig
from .kvcache import (
    KVCache, RowPools, cache_layout, default_state_slots, first_live_page,
    read_conv_state, read_state, window_span_pages, window_table, write_kv,
)
from ..ops.lowering import part
from ..ops.sampling import (
    NEG_INF, cumulative_logprob, sample, sample_with_confidence, transfer,
    unpack_mask,
)


def next_bucket(n: int, lo: int = 16, hi: int = 1 << 20) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return b


#: share of the device's memory limit kept free of weights and KV pool
#: for what the compiled programs allocate while they run (activations,
#: logits, XLA scratch, and the gathered-page attention of the chunked
#: prefills and verify forwards whose heads ops/pallas_chunk.py does not
#: take: the others read their pages in place). At qwen3-4b on a 16 GB
#: v5e the largest program temporaries measured ~2 GB (PERF.md
#: "Bring-up"); 20% is 3.1 GB.
HBM_RESERVE_FRACTION = 0.2


def _pool_margin_pages(max_pages_per_seq: int, page_bytes: int) -> int:
    """Pages a memory-bound pool leaves to the device beside the
    reserve. The decode kernel's chunked fetch read up to a chunk (the
    largest divisor of the table under 1 MiB) past a row's last page,
    and the pool kept that many pages less one out of the allocators'
    hands. Nothing reads past a row's pages now and the pool no longer
    holds them; the count a pool that fills the device hands out stays
    what it was (579 pages for qwen3-4b on one v5e), so that admission
    is unchanged. Handing them out is ROADMAP 1.3's to measure."""
    chunk = min(max_pages_per_seq, max(1, (1 << 20) // max(page_bytes, 1)))
    while chunk > 1 and max_pages_per_seq % chunk:
        chunk -= 1
    return max(chunk, 1) - 1


def resolve_pallas(
    ecfg: EngineConfig, mesh: Optional[jax.sharding.Mesh] = None
) -> Tuple[bool, str]:
    """``(run the Pallas kernels?, why)`` for this config on this
    backend and mesh. The kernels are shard_mapped over the ``model``
    axis only (ops/lowering.shard_over_model) — XLA cannot partition a
    Mosaic call itself — so a mesh that shards anything else selects
    the XLA path, and says so; asking for the kernels there is an
    error at construction, not a surprise at the first compile."""
    other = (
        {a: n for a, n in mesh.shape.items() if a != "model" and n > 1}
        if mesh is not None
        else {}
    )
    if ecfg.use_pallas:
        if other:
            raise ValueError(
                f"use_pallas=True cannot run on a mesh with {other}: the "
                "Pallas kernels are partitioned over the 'model' axis only"
            )
        return True, "use_pallas=True in the engine config"
    if ecfg.use_pallas is not None:
        return False, "use_pallas=False in the engine config"
    backend = jax.default_backend()
    if backend != "tpu":
        return False, f"auto: backend is {backend} (the kernels are TPU-only)"
    if other:
        return False, (
            f"auto: mesh shards {other}; the kernels are partitioned over "
            "'model' only, so this mesh takes the XLA path"
        )
    return True, "auto: backend is tpu"


def device_report(ecfg: Optional[EngineConfig] = None) -> dict:
    """What this process would run an engine on — the facts to read
    before trusting any number from it. ONE helper: `sutro engine info`
    and chip_smoke.py print exactly this. Touches the backend (and
    builds the native helpers on demand, as the engine would)."""
    import jaxlib

    from ..ops import lowering
    from ..parallel.mesh import auto_mesh
    from .config import enable_compile_cache, load_engine_config
    from .constrain import cpp as native_fsm
    from . import native_runtime

    ecfg = ecfg or load_engine_config()
    devs = jax.devices()
    dp, pp, sp, ep, tp = ecfg.resolved_mesh(len(devs))
    mesh = auto_mesh(ecfg) if dp * pp * sp * ep * tp > 1 else None
    use_pallas, why = resolve_pallas(ecfg, mesh)
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None  # a CPU-only installation
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "bytes_limit": (devs[0].memory_stats() or {}).get("bytes_limit"),
        "mesh": {"dp": dp, "pp": pp, "sp": sp, "ep": ep, "tp": tp},
        "mesh_devices": dp * pp * sp * ep * tp,
        "use_pallas": use_pallas,
        "pallas_reason": why,
        # what this process has traced so far (ops/lowering.py): each
        # kernel by path, and beside them the XLA decode attention, the
        # routed experts' grouped product and the combine behind it (a
        # routed model's alone) and a Mamba-2 layer's read of its
        # committed state in a decode step
        "kernel_paths": lowering.snapshot(),
        # the paged decode kernel's traces by the rows a grid step takes
        "paged_decode_rows_per_step": lowering.paged_decode_rows_per_step(),
        # its calls under a row's selection (a latent layer's indexer)
        "paged_decode_forms": lowering.paged_decode_forms(),
        "paged_decode_xla": lowering.xla_decode_count(),
        # a chunk of several tokens over a paged past: the kernel that
        # reads the pages in place, or the gather (``reference``)
        "paged_chunk": lowering.paged_chunk_counts(),
        # the attention kernels' traces by the call's query heads (a
        # model whose window layers have 72 and whose full layers 48):
        # Pallas or XLA each, and the gate that sent a call to XLA
        "kernel_heads": lowering.kernel_heads_counts(),
        # the flash body's GQA calls by heads and form: the tile, the
        # walk (a static window's blocks, the causal half, or a dynamic
        # window's) and what the MXU is fed
        "flash_prefill": lowering.flash_prefill_counts(),
        "grouped_matmul": lowering.grouped_matmul_counts(),
        "moe_combine": lowering.moe_combine_counts(),
        "ssm_state_read": lowering.ssm_state_read_counts(),
        # a delta-rule (kda) layer: its traces by form, its two products
        # against the committed state and its commit, each by path
        "kda": lowering.kda_counts(),
        "kda_state_read": lowering.kda_state_read_counts(),
        "kda_state_commit": lowering.kda_state_commit_counts(),
        # a Mamba-1 layer's traces by form: the prefill's chunked scan,
        # a step from the slot, a fused window's step from its carry
        "mamba1": lowering.mamba1_counts(),
        # a latent layer's attention by form, whichever path computed it
        # (``kernel_paths`` above says kernel or XLA form)
        "latent_attention": lowering.latent_counts(),
        # under an indexer's selection: by how it was applied
        "sparse_attention": lowering.sparse_attention_counts(),
        "compile_cache_dir": enable_compile_cache(),
        "native_runtime": native_runtime.is_available(),
        "native_fsm": native_fsm.is_available(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


class ModelRunner:
    def __init__(
        self,
        mcfg: ModelConfig,
        ecfg: EngineConfig,
        params: Optional[Any] = None,
        *,
        num_pages: Optional[int] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        shardings: Optional[Any] = None,
        window_pages: Optional[int] = None,
    ):
        self.mcfg = mcfg
        self.ecfg = ecfg
        from .config import enable_compile_cache

        enable_compile_cache()
        dtype = jnp.dtype(ecfg.param_dtype)
        if ecfg.quantize not in (None, "int8"):
            raise ValueError(
                f"Unknown quantize mode {ecfg.quantize!r} (only 'int8')"
            )
        # Mesh: explicit > engine-config-resolved > single-device (None).
        # Resolved BEFORE any array exists so random weights and the KV
        # pool are born sharded: a model that needs four chips never
        # sits whole on the first.
        if mesh is None:
            from ..parallel.mesh import auto_mesh

            dp, pp, sp, ep, tp = ecfg.resolved_mesh(jax.device_count())
            if dp * pp * sp * ep * tp > 1:
                mesh = auto_mesh(ecfg)
        self.mesh = mesh
        #: devices this runner computes on — the per-chip divisor
        self.n_devices = int(mesh.size) if mesh is not None else 1
        # before any weight is built: a combination that cannot run
        # raises here
        self.use_pallas, self.pallas_reason = resolve_pallas(ecfg, mesh)
        #: mesh the per-shard attention calls are shard_mapped over
        #: ("model" axis): the Pallas kernels, and the XLA decode path
        #: (ops/attention.paged_decode_xla), whose products over the
        #: fused KV axis GSPMD would answer with an all-gather of the
        #: context. None on one device, where they are called bare, and
        #: on a mesh that shards more than "model", which GSPMD
        #: partitions whole
        model_only = mesh is not None and all(
            n > 1 if a == "model" else n == 1 for a, n in mesh.shape.items()
        )
        self.kernel_mesh = mesh if self.use_pallas or model_only else None
        if (
            mesh is not None
            and getattr(ecfg, "kv_quantize", None)
            and int(mesh.shape.get("pipe", 1)) > 1
        ):
            # the pipeline decode path (parallel/pipeline.py) carries
            # bare k/v page pools, no scale pools — quantized KV under
            # pp stays unsupported. Under dp/tp/sp/ep it IS supported:
            # per-token scales are computed over the FULL fused KD axis
            # (a cross-shard reduce under GSPMD), so they are
            # shard-invariant and the scale pools simply replicate.
            import warnings

            warnings.warn(
                "kv_quantize is not supported under pipeline "
                "parallelism; ignoring it for this pp mesh"
            )
            import dataclasses as _dc

            ecfg = self.ecfg = _dc.replace(ecfg, kv_quantize=None)
        # ring-attention sequence parallelism for prefill when the mesh
        # carries a non-trivial "seq" axis (SURVEY §5.7 TPU plan)
        self.sp = int(mesh.shape.get("seq", 1)) if mesh is not None else 1
        # GPipe pipeline stages when the mesh carries a "pipe" axis
        self.pp = int(mesh.shape.get("pipe", 1)) if mesh is not None else 1
        if not mcfg.homogeneous and (
            self.sp > 1 or self.pp > 1 or ecfg.quantize
        ):
            # the ring and pipeline wrappers drive ``layer_apply`` over
            # one stack, and quantize_params names that stack's leaves
            raise NotImplementedError(
                f"{mcfg.name} has layers of several kinds: not under "
                "sequence or pipeline parallelism, nor quantize"
            )
        # how the K/V pools are partitioned (parallel/ says), and with it
        # what the pools hold at any size (kvcache.cache_layout): a layout
        # that cannot be built (a latent pool under a mesh, int8 K/V
        # beside a window pool ...) raises here, by name
        self._cache_sharding = None
        if mesh is not None and self.pp > 1:
            from ..parallel.pipeline import pp_cache_sharding

            self._cache_sharding = pp_cache_sharding(mesh, mcfg.num_kv_heads)
        elif mesh is not None:
            from ..parallel.sharding import cache_shardings

            self._cache_sharding = cache_shardings(mesh, mcfg.num_kv_heads)
        # pages the allocators may hand out at the worst case (every slot
        # at full context; page 0 is the garbage page)
        worst_case = 1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq
        # (an entry's bytes are the same at any size: ``_pages_that_fit``
        # reads them here, before the pools have one)
        self._sized = cache_layout(
            mcfg, ecfg, worst_case, dtype, self._cache_sharding
        )
        self._margin_pages = _pool_margin_pages(
            ecfg.max_pages_per_seq, self._sized.margin_row_bytes
        ) if self.use_pallas else 0
        if mesh is not None and mcfg.hc_mult > 1:
            # the sharding rules and the pipeline's stages know one lane
            # [B, T, H] (parallel/pipeline.py sends it between stages)
            raise NotImplementedError(
                f"{mcfg.name} keeps a residual stream of {mcfg.hc_mult} "
                "lanes (hc_mult): it runs on one chip, not under a mesh "
                "or pipeline stages"
            )
        if mesh is not None and mcfg.experts_held != mcfg.moe_experts:
            # the share IS one chip's part of a layer; under a mesh the
            # sharding rules would split the held stack again and the
            # EP path would take its shard's experts for the router's
            raise NotImplementedError(
                f"{mcfg.name} holds a share of each layer's experts "
                "(moe_experts_held): it runs on one chip, not under a mesh"
            )
        Bk = mcfg.block_length
        if Bk > 1:
            # generation by blocks: what is not built is refused by name
            if mesh is not None:
                raise NotImplementedError(
                    f"{mcfg.name} generates by blocks of {Bk} "
                    "(block_length): it runs on one chip, not under a mesh "
                    "or pipeline stages"
                )
            if ecfg.quantize or getattr(ecfg, "kv_quantize", None):
                raise NotImplementedError(
                    f"{mcfg.name} generates by blocks (block_length): not "
                    "with quantize or kv_quantize"
                )
            if getattr(ecfg, "interactive_slots", 0):
                raise NotImplementedError(
                    f"{mcfg.name} generates by blocks (block_length): not "
                    "with interactive_slots > 0 (the chat path's stream a "
                    "token and its preemption by hibernation are not "
                    "carried)"
                )
            if Bk & (Bk - 1) or not 0 <= mcfg.mask_token_id < mcfg.vocab_size:
                raise ValueError(
                    f"{mcfg.name}: block_length {Bk} must be a power of two "
                    f"and mask_token_id {mcfg.mask_token_id} an id of the "
                    "vocabulary"
                )
            for name in ("kv_page_size", "prefill_chunk", "max_model_len"):
                if getattr(ecfg, name) % Bk:
                    raise ValueError(
                        f"{mcfg.name}: {name} {getattr(ecfg, name)} is no "
                        f"multiple of block_length {Bk} (a block never "
                        "straddles a page, a chunk or the context's end)"
                    )
            # the benchmark's door (perfbench/reference/README.md "The
            # forced forward"): bound on a block model's runner ALONE, so
            # that every other model's numbers check keeps its driver
            self.forced_logits = self._forced_logits
        # explicit shard_map EP for MoE MLPs (ops/moe_ep.py). Not under
        # sp/pp: those paths already wrap layers in their own shard_map
        # and nesting is unsupported — they keep GSPMD MoE semantics.
        ep = int(mesh.shape.get("expert", 1)) if mesh is not None else 1
        self.ep_mesh = (
            mesh
            if (ep > 1 and self.sp == 1 and self.pp == 1 and mcfg.moe_experts)
            else None
        )

        def shard_rules(tree):
            if self.pp > 1:
                from ..parallel.pipeline import pp_param_shardings

                return pp_param_shardings(tree, mesh)
            from ..parallel.sharding import param_shardings

            return param_shardings(tree, mesh)

        if params is None:
            key = jax.random.PRNGKey(ecfg.seed)
            born_sharded = mesh is not None and not ecfg.quantize
            if born_sharded and shardings is None:
                shardings = shard_rules(
                    jax.eval_shape(
                        functools.partial(
                            transformer.init_params, mcfg, dtype=dtype
                        ),
                        key,
                    )
                )
            params = transformer.init_params(
                mcfg, key, dtype,
                shardings=shardings if born_sharded else None,
            )
        if ecfg.quantize == "int8":
            from ..ops.quant import is_quantized, quantize_params

            if not any(
                is_quantized(x)
                for x in jax.tree_util.tree_leaves(
                    params, is_leaf=is_quantized
                )
            ):
                params = quantize_params(params)
        if mesh is not None:
            if shardings is None:
                shardings = shard_rules(params)
            params = jax.device_put(params, shardings)
        else:
            # commit host leaves (checkpoint numpy, host-quantized int8)
            # to the device ONCE — otherwise every jitted dispatch
            # re-uploads them
            params = jax.device_put(params)
        self.params = params
        # pages the allocators may hand out. ``num_pages`` sizes it
        # explicitly; otherwise it is the worst case, bounded by what
        # the device's memory can hold beside the weights.
        # a model with window attention layers keeps K/V a pool a kind
        # (kvcache.py): the window layers' pool holds a row's window and
        # the tokens in flight, whatever its context. Sized with the
        # full pool from what fits, or by ``window_pages``; a runner
        # given ``num_pages`` alone, and any mesh, runs the mechanism at
        # its trivial setting: a window pool as large as the full one
        # under the identity map, nothing bound or released
        self.window_span = self._sized.window_span
        two_pools = (
            mcfg.num_window_layers > 0 and mesh is None
            and (num_pages is None or window_pages is not None)
        )
        if num_pages is not None:
            self.alloc_pages = num_pages
        else:
            self.alloc_pages, fit_window = self._pages_that_fit(
                worst_case,
                1 + ecfg.decode_batch_size * self.window_span
                if two_pools else 0,
            )
            if two_pools and window_pages is None:
                window_pages = fit_window
        # the decode kernel fetches a row's own pages and no other, so
        # the pool holds what the allocators hand out and nothing more
        self.num_pages = self.alloc_pages
        # (fetched, needed) K/V pages of the decode dispatches since the
        # last take_kv_pages
        self._kv_pages = None
        # the last masked decode_step's take_unmasked_ok
        self._unmasked_ok = None
        #: THE description of this runner's pools (kvcache.CacheLayout):
        #: shapes, bytes, what a row needs of each, what the pages support
        self.layout = cache_layout(
            mcfg, ecfg, self.num_pages, dtype, self._cache_sharding,
            window_pages if two_pools else None,
        )
        self.cache = self.layout.alloc()
        #: the host's side of the pools beside the page free list
        #: (kvcache.RowPools): what the scheduler admits a row against
        self.pools = RowPools(
            self.layout, self._tell_slots, self._tell_window
        )

    @property
    def has_state(self) -> bool:
        """The model keeps per-sequence state beside K/V (conv or mamba
        layers): callers of the verify dispatches commit it at the
        accepted length (``commit_verified``)."""
        return self.mcfg.num_conv_layers > 0 or self.mcfg.num_state_layers > 0

    def state_matrix_bytes(self, rows: int) -> int:
        """Bytes of the state layers' matrices alone (no conv columns)
        in ``rows`` rows' slots: what a delta-rule step's two products
        stream and a commit rewrites."""
        return int(rows * self.layout.entry_bytes("ssm"))

    def stream_bytes(self, tokens: int) -> int:
        """Bytes the residual stream of ``tokens`` tokens MUST move
        through a forward of a model whose stream is several lanes
        (``ModelConfig.hc_mult``; 0 for any other): every sublayer reads
        the n lanes once and writes them once, ``2 n C`` elements a
        token. A tile of tokens (28 KB a token at 4 lanes of 3,584)
        stays on the chip between the coefficients, the read and the
        mix, and the sublayer's input and output are the sublayer's own
        traffic: what the program moves beyond this is its loss. THE
        definition: the spans' ``hc_stream_bytes`` and the benchmark's
        readers (``perfbench/MHC_LAYERS.md``) take it from here."""
        m = self.mcfg
        return int(
            tokens * m.hc_sublayers * 2 * m.hc_mult * m.hidden_size
            * jnp.dtype(self.ecfg.activation_dtype).itemsize
        )

    def _window_state_bytes(self) -> int:
        """Bytes of the fused window's buffers for the state layers'
        uncommitted tokens at the configured batch and steps."""
        m, steps = self.mcfg, self.ecfg.decode_multi_step
        act = jnp.dtype(self.ecfg.activation_dtype)
        a_row = (m.state_conv_len + steps) * m.state_conv_dim * act.itemsize
        a_row += steps * sum(
            width * jnp.dtype(dt).itemsize
            for _, width, dt in transformer.pending_buffers(m, act)
        )
        if m.state_kind == "mamba1":
            # the rows' state in float32 as the scan carries it
            # (``transformer.running_state``), and the copy a step's new
            # state stands in beside it
            a_row += 2 * 4 * m.state_rows * m.state_inner
        return m.num_state_layers * self.ecfg.decode_batch_size * a_row

    def state_step_bytes(self, rows: int) -> int:
        """Bytes of mamba state a decode step reads for ``rows`` live
        rows (it reads each row's slot once and writes none: the window
        commits); 0 for a model that keeps none."""
        return rows * self.layout.slot_bytes

    # -- what the host's allocators tell the device (kvcache.RowPools) --

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    @part("cache")
    def _bind_slots_jit(self, cache: KVCache, pages, slots):
        return dataclasses.replace(
            cache, state_slot=cache.state_slot.at[pages].set(slots)
        )

    def _tell_slots(self, pairs) -> None:
        """``(page, slot)`` bindings the device has yet to learn, in ONE
        small dispatch of one shape for every admission batch (pads:
        page 0 <- slot 0)."""
        n = max(self.ecfg.prefill_batch_size, next_bucket(len(pairs)))
        pages, slots = np.zeros((2, n), np.int32)
        pages[: len(pairs)], slots[: len(pairs)] = np.array(pairs, np.int32).T
        self.cache = self._bind_slots_jit(
            self.cache, jnp.asarray(pages), jnp.asarray(slots)
        )

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    @part("cache")
    def _bind_window_jit(self, cache: KVCache, pages, wpages):
        return dataclasses.replace(
            cache, window_page=cache.window_page.at[pages].set(wpages)
        )

    def _tell_window(self, ids, wpages) -> None:
        """The page ids whose window page changed, 64 a dispatch (pads:
        page id 0 <- 0): ONE shape, so a burst (a job's sixteen rows
        released together were 160 ids and more) takes several
        dispatches and compiles nothing while requests are served."""
        m = 64
        for at in range(0, len(ids), m):
            n = min(m, len(ids) - at)
            pad = np.zeros((2, m), np.int32)
            pad[0, :n], pad[1, :n] = ids[at : at + n], wpages[at : at + n]
            self.cache = self._bind_window_jit(
                self.cache, jnp.asarray(pad[0]), jnp.asarray(pad[1])
            )

    def _window_pool_of(self, cache: KVCache, page_table):
        """``transformer.forward``'s ``window_pool``; None for a model
        with one pool."""
        if cache.wk_pages is None:
            return None
        return (
            cache.wk_pages, cache.wv_pages, window_table(cache, page_table)
        )

    def _page_bytes_per_device(self, *, window: bool = False) -> int:
        """One page as it sits on ONE device under the pool's sharding
        (``CacheLayout.page_bytes``: the description's number, whatever
        the pool's size); ``window``: a page of the window layers'
        pool."""
        return (
            self._sized.window_page_bytes if window
            else self._sized.page_bytes
        )

    def _pages_that_fit(self, want: int, want_window: int):
        """``(pages, window pages)``: ``want`` pages (and ``want_window``
        of the window layers' pool, 0 for a model with one pool or at
        the trivial setting, where a page carries both kinds), or as
        many as the device's memory limit holds
        beside what is already resident (the weights) and the reserve.
        Short of memory the FULL pool gives way first, down to one
        row's table: its worst case is every row at ``max_model_len``,
        which no traffic holds, while the window pool's is a span a row
        of the batch, which every full batch of rows past the window
        holds (divided in the proportion asked for, a batch of 128 rows
        of 8,192 beside 6.4 GB of weights kept window pages for 54
        rows: PERF.md section 6, PR 61).
        The scheduler admits against free pages, so a pool smaller than
        the worst case is a supported state; a pool too small for ONE
        full-context row is not, and raises here with the budget —
        rather than RESOURCE_EXHAUSTED out of ``alloc_cache``. Backends
        that report no limit (CPU) are not bounded."""
        dev = (
            self.mesh.devices.flat[0] if self.mesh is not None
            else jax.devices()[0]
        )
        # the weights must be resident before the device is asked
        # what is in use (dispatch is asynchronous)
        jax.block_until_ready(self.params)
        stats = dev.memory_stats() or {}
        limit = int(stats.get("bytes_limit") or 0)
        if not limit:
            return want, want_window
        in_use = int(stats.get("bytes_in_use") or 0)
        if self.mcfg.num_state_layers:
            # the state pool comes first: a slot a row of the batch; then
            # what a fused window carries of its uncommitted tokens (the
            # conv columns and ``pending_buffers``, a row of the batch a
            # step), which is no transient the reserve was sized for
            # once a token leaves 100 KB a layer (a delta rule's)
            in_use += (1 + default_state_slots(self.ecfg, want)) * (
                self._sized.slot_bytes
            ) + self._window_state_bytes()
        if self.mcfg.hc_mult > 1:
            # a prefill chunk's stream is no transient the reserve was
            # sized for: a sublayer holds the lanes it reads, the lanes
            # it writes and a float32 slab or two of the mix (at 4 lanes
            # of 3,584 over 4,096 tokens 117 MB a copy)
            in_use += 4 * self.ecfg.prefill_batch_size * (
                min(self.ecfg.prefill_chunk, self.ecfg.max_model_len)
                * self.mcfg.hc_mult * self.mcfg.hidden_size
                * jnp.dtype(self.ecfg.activation_dtype).itemsize
            )
        if self.mcfg.block_length > 1:
            # a denoising forward's logits are [batch x block, V]: the
            # head's product, its float32 copy, the scaled copy and the
            # draw's noise stand together (311 MB each at 128 rows of 4
            # over 151,936), which is no transient the reserve was
            # sized for
            in_use += 4 * 4 * (
                self.ecfg.decode_batch_size * self.mcfg.block_length
                * self.mcfg.vocab_size
            )
        reserve = int(limit * HBM_RESERVE_FRACTION)
        page = self._page_bytes_per_device()
        avail = limit - in_use - reserve
        wpage = fit_window = 0
        if self.mcfg.num_window_layers:
            wpage = self._page_bytes_per_device(window=True)
            if not want_window:
                page, wpage = page + wpage, 0   # one id, both kinds
        need = want * page + want_window * wpage
        if want_window:
            # short of memory the full pool shrinks first; the window
            # pool only once the full pool is at one row's table, and
            # never under one row's span
            floor_full = (
                1 + self.ecfg.max_pages_per_seq + self._margin_pages
            ) * page
            fit_window = want_window if need <= avail else min(
                want_window,
                max((avail - floor_full) // wpage, 1 + self.window_span),
            )
            avail -= fit_window * wpage
        fit = avail // page - self._margin_pages
        floor = 1 + self.ecfg.max_pages_per_seq
        if fit < floor:
            from .roofline import param_bytes_of

            gb = 1e9
            raise ValueError(
                f"{self.mcfg.name} does not fit {dev.device_kind}: device "
                f"limit {limit / gb:.2f} GB, in use {in_use / gb:.2f} GB "
                f"(weights {param_bytes_of(self.params) / gb:.2f} GB over "
                f"{self.n_devices} device(s)), reserve {reserve / gb:.2f} "
                f"GB; the smallest KV pool (one row at max_pages_per_seq="
                f"{self.ecfg.max_pages_per_seq}: {floor} pages x "
                f"{page / 1e6:.1f} MB) needs {floor * page / gb:.2f} GB"
            )
        return min(want, fit), fit_window

    def device_info(self) -> dict:
        """Device + model facts: what the bottleneck doctor grades decode
        windows against (engine/roofline.py denominators), and the
        runner's half of the device report (`sutro engine info`,
        chip_smoke.py). Computed once per runner — the param-tree walk
        is not free — and stored in each job's flight-recorder attrs."""
        cached = getattr(self, "_device_info", None)
        if cached is not None:
            return cached
        from .roofline import param_bytes_of, param_count_of

        devs = jax.devices()
        info = {
            "device_kind": str(
                getattr(devs[0], "device_kind", "") if devs else ""
            ),
            # the devices this runner's mesh spans (1 with no mesh) —
            # NOT every device the host has: per-chip rates divide by
            # what the runner computes on
            "n_devices": self.n_devices,
            "host_devices": len(devs),
            "mesh": (
                {a: int(n) for a, n in self.mesh.shape.items() if n > 1}
                if self.mesh is not None else {}
            ),
            "use_pallas": self.use_pallas,
            "pallas_reason": self.pallas_reason,
            "param_bytes": param_bytes_of(self.params),
            "n_params": param_count_of(self.params),
            "pool_pages": int(self.num_pages),
            "pool_bytes": int(
                sum(
                    x.nbytes
                    for x in jax.tree_util.tree_leaves(self.cache)
                )
            ),
            "num_layers": int(self.mcfg.num_layers),
            # the layers the pool spans (a conv layer has no K/V), and
            # the second kind of per-sequence state beside it
            "attn_layers": int(self.mcfg.num_attn_layers),
            "pool_layers": int(self.cache.k_pages.shape[0]),
            "state_layers": int(
                self.mcfg.num_conv_layers + self.mcfg.num_state_layers
            ),
            # what keeps a matrix state a slot ("mamba" | "kda"; None:
            # no layer does) and a slot's bytes over those layers
            "state_kind": self.mcfg.state_kind,
            "state_bytes_per_slot": int(self.layout.slot_bytes),
            "state_bytes": int(sum(
                0 if pool is None else pool.nbytes
                for pool in (
                    self.cache.conv, self.cache.ssm, self.cache.ssm_conv
                )
            )),
            "state_slots": int(self.cache.num_state_slots),
            # K/V a pool a kind: the window layers and their pool's pages
            # (0: one pool; equal to pool_pages: the trivial setting)
            "window_layers": int(self.mcfg.num_window_layers),
            "window_pool_pages": int(self.cache.num_window_pages),
            # latent layers keep ONE row a token, ``latent_page_bytes``
            # a page of every layer in use (0: the pool keeps K and V);
            # the pool's rows are ``latent_row_lanes`` wide, whole lane
            # tiles (``ModelConfig.page_width``; ``pool_bytes`` counts them)
            "latent_layers": int(self.mcfg.num_latent_layers),
            "latent_row_width": int(
                self.mcfg.latent_width if self.mcfg.num_latent_layers else 0
            ),
            "latent_row_lanes": int(
                self.mcfg.page_width if self.mcfg.num_latent_layers else 0
            ),
            "latent_page_bytes": int(
                self.layout.entry_bytes("k_pages", used=True)
                if self.mcfg.num_latent_layers else 0
            ),
            # latent layers with an indexer keep an index key a token too,
            # ``index_page_bytes`` a page of every layer (0: no indexer)
            "index_layers": int(
                self.mcfg.num_latent_layers if self.mcfg.index_topk else 0
            ),
            "index_key_width": int(self.mcfg.index_key_width),
            "index_topk": int(self.mcfg.index_topk),
            "index_page_bytes": int(self.layout.entry_bytes("ik_pages")),
            # the residual stream's lanes (1: the plain add) and the
            # sublayers that each mix them a token
            "hc_mult": int(self.mcfg.hc_mult),
            "hc_sublayers": int(self.mcfg.hc_sublayers),
            # generation by blocks: positions a block (1: a causal
            # model) and the id an open position holds (-1: none)
            "block_length": int(self.mcfg.block_length),
            "mask_token_id": int(self.mcfg.mask_token_id),
            "kv_heads": int(self.mcfg.num_kv_heads),
            "head_dim": int(self.mcfg.head_dim),
            "kv_dtype_bytes": (
                1
                if getattr(self.ecfg, "kv_quantize", None) == "int8"
                else jnp.dtype(self.ecfg.activation_dtype).itemsize
            ),
        }
        self._device_info = info
        return info

    def _route_stats(self, chunk):
        """[6] float32 from a forward's chunk: over the routed layers
        and the experts this chip HOLDS (``ModelConfig.moe_experts_held``;
        every expert of any other model), the mean number of distinct
        experts the dispatch's rows chose, the mean rows of the busiest
        expert, the mean rows an expert, the row-expert pairs that
        landed on them in all, the experts held a layer, and the pairs
        routed to experts this chip does not hold. None for a model
        that does not count its routing. Computed inside the dispatch's
        own program and fetched with its tokens."""
        route = chunk.route if isinstance(chunk, MixedChunk) else None
        if route is None:
            return None
        first, held = self.mcfg.moe_first_expert, self.mcfg.experts_held
        with part("ffn"):  # where the counts were computed
            every = route.astype(jnp.float32)               # [L_moe, E]
            r = every[:, first : first + held]
            return jnp.stack([
                jnp.mean(jnp.sum(r > 0, axis=-1).astype(jnp.float32)),
                jnp.mean(jnp.max(r, axis=-1)),
                jnp.mean(r),
                jnp.sum(r),
                jnp.float32(held),
                jnp.sum(every) - jnp.sum(r),
            ])

    def _state_at(self, cache: KVCache, page_table, start):
        """The conv layers' state of each row at ``start`` ([L_conv, B,
        K-1, H]); None for a model that keeps none."""
        return read_conv_state(
            cache, page_table, start,
            self.mcfg.num_conv_layers, self.mcfg.hidden_size,
        )

    def _state_past(self, cache: KVCache, page_table, start):
        """The mamba layers' state of each row at ``start``
        (``transformer.StatePast``); None for a model that keeps none."""
        return read_state(
            cache, page_table, start,
            self.mcfg.num_state_layers, self.mcfg.state_conv_dim,
        )

    def _count_state_commit(
        self, path: str, rows: int = 1, steps: int = 1
    ) -> None:
        """A dispatch that commits per-sequence state, by ``path``; for a
        model with delta-rule layers also the form they took and the
        state bytes its program moves and had to move (``rows`` rows,
        ``steps`` steps before the one commit; a verify chunk is counted
        where its accepted length commits, its forward as one read)."""
        if not (telemetry.ENABLED and self.has_state):
            return
        telemetry.STATE_COMMITS_TOTAL.inc(1.0, path)
        if self.mcfg.state_kind != "kda":
            return
        m = self.mcfg
        slot = float(self.state_matrix_bytes(rows))
        kernels = self.use_pallas and self.kernel_mesh is None
        if path in ("prefill", "chunk", "resume"):
            # the chunk form: the slot gathered, the final state written
            telemetry.KDA_DISPATCHES_TOTAL.inc(1.0, "chunked")
            read, commit, reads, commits = slot, slot, 1, 1
        else:
            telemetry.KDA_DISPATCHES_TOTAL.inc(1.0, "pending")
            # a kernel streams a row's slot once; the XLA forms gather it
            # (read, written, read again) and scatter it back
            reads, commits = steps, 1
            read = slot * reads * (1 if kernels else 3)
            commit = slot * (2 if kernels else 5)
        telemetry.KDA_STATE_BYTES_TOTAL.inc(read, "read")
        telemetry.KDA_STATE_BYTES_TOTAL.inc(commit, "commit")
        telemetry.KDA_STATE_BYTES_NEEDED_TOTAL.inc(slot * (reads + commits))

    @staticmethod
    def count_sample(temperature) -> None:
        """A dispatch that samples (a masked step, a window, an
        admission group's first tokens), by the side of
        ``ops.sampling.sample``'s cond the device takes: the SAME
        predicate on the host's copy of the [B] temperatures the
        program is given, padding rows included."""
        if telemetry.ENABLED:
            telemetry.SAMPLE_DISPATCHES_TOTAL.inc(
                1.0,
                "argmax" if np.all(np.asarray(temperature) <= 0.0)
                else "drawn",
            )

    def _count_latent(self, form: str, past_len=None, steps: int = 1) -> None:
        """A dispatch of a model of latent layers, by the form its
        attention takes (``transformer.mla_mixer``): "expanded" with no
        paged past, "absorbed" over one. Where the layers have an
        indexer (``ModelConfig.index_topk``) also by whether the
        selection bites: ``selected`` when some query of the dispatch
        has more than ``index_topk`` positions to choose from (a row's
        ``past_len`` [B] plus its ``steps`` tokens), else ``dense_short``
        (the selection is everything and the dense latent paths run);
        and, for a DECODE dispatch, the rows a query's context holds
        against the rows its attention reads (``selected``) and the
        rows it FETCHES to read them, a row-step at a time (host
        arithmetic, as ``_count_kv_pages``). ``fetched``: under the
        paged kernel (``use_pallas``: the latent variant, under the
        selection or dense) the rows of a row's pages up to its last
        token's, whatever is selected of them; in XLA the selected rows
        themselves, gathered by position, and the whole table where the
        dispatch is dense. The pending tokens and the own row count in
        all three."""
        if not (telemetry.ENABLED and self.mcfg.num_latent_layers):
            return
        telemetry.LATENT_ATTENTION_DISPATCHES_TOTAL.inc(1.0, form)
        topk = self.mcfg.index_topk
        if not topk:
            return
        past = np.zeros((1,), np.int64) if past_len is None else (
            np.asarray(past_len, np.int64).reshape(-1)
        )
        telemetry.SPARSE_ATTENTION_DISPATCHES_TOTAL.inc(
            1.0, "selected" if int(past.max()) + steps > topk
            else "dense_short",
        )
        if form != "absorbed" or past_len is None:
            return
        # a row's s-th step sees its past, the window's earlier tokens
        # and itself; padding rows (no past) are no rows
        live = past[past > 0, None]
        ctx = live + np.arange(1, steps + 1)[None]
        chosen = np.minimum(ctx, topk)
        telemetry.SPARSE_ATTENTION_ROWS_TOTAL.inc(float(ctx.sum()), "context")
        telemetry.SPARSE_ATTENTION_ROWS_TOTAL.inc(float(chosen.sum()), "selected")
        from ..ops import pallas_paged

        PS, MP = self.ecfg.kv_page_size, self.ecfg.max_pages_per_seq
        selecting = int(past.max()) + steps > topk
        if self.use_pallas and pallas_paged.paged_decode_supported(
            jax.ShapeDtypeStruct((1, 1, self.mcfg.page_width), jnp.float32),
            self.cache.k_pages, selection_pages=MP if selecting else 0,
        ):
            fetched = ctx - live + -(-live // PS) * PS
        else:
            fetched = chosen if selecting else ctx - live + MP * PS
        telemetry.SPARSE_ATTENTION_ROWS_TOTAL.inc(float(fetched.sum()), "fetched")

    def take_route_stats(self):
        """The routing counts of the last dispatch that was fetched
        (``_route_stats``; [6], or [steps, 6] for a fused window), as
        numpy, once; None when there are none. The program that made
        them has already been waited for by whoever fetched its tokens,
        so this is a copy of a few floats and no wait of its own."""
        stats, self._route_dev = getattr(self, "_route_dev", None), None
        return None if stats is None else np.asarray(stats)

    @staticmethod
    def _paged(cache: KVCache, page_table):
        """The ``paged_past`` tuple for transformer.forward: 3 elements
        for a bf16 cache, 5 (with per-token dequant scales) for int8."""
        if cache.quantized:
            return (
                cache.k_pages, cache.v_pages,
                cache.k_scale, cache.v_scale, page_table,
            )
        # a latent pool has no V: where its layers have an indexer the
        # index pool rides in V's place (transformer._mixed_trunk)
        second = cache.ik_pages if cache.v_pages is None else cache.v_pages
        return (cache.k_pages, second, page_table)

    # ------------------------------------------------------------------
    # tiered-KV page migration (engine/kvtier.py)
    # ------------------------------------------------------------------

    def read_pages(self, page_ids) -> dict:
        """Materialized HOST copies of ``page_ids``'s K/V payloads —
        the only device->host read path the tiered pool uses. Shapes:
        ``k``/``v`` ``[L, n, PS, KD]`` in the pool dtype (int8 when the
        pool is quantized, plus ``ks``/``vs`` per-token scales). The
        returned arrays are synchronously fetched, so the caller may
        free/reuse the pages the moment this returns."""
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        c = self.cache
        why = self.layout.refuses("read_pages")
        if why is not None:
            raise ValueError(why)
        out = {
            "k": np.asarray(c.k_pages[:, ids]),
            "v": np.asarray(c.v_pages[:, ids]),
        }
        if c.quantized:
            out["ks"] = np.asarray(c.k_scale[:, ids])
            out["vs"] = np.asarray(c.v_scale[:, ids])
        if c.conv is not None:
            # the pages' conv state rides with them, in its own dtype
            # (the tiers keep it as it is: kvtier.quantize_payload), as
            # [L_conv, n, K-1, H]: pages on axis 1, like K/V
            m = self.mcfg
            out["c"] = np.asarray(c.conv[ids]).reshape(
                len(out["k"][0]), m.num_conv_layers, m.conv_state_len,
                m.hidden_size,
            ).transpose(1, 0, 2, 3)
        return out

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    @part("cache")
    def _upload_pages_jit(self, cache: KVCache, ids, k, v, c=None):
        return dataclasses.replace(
            cache,
            k_pages=cache.k_pages.at[:, ids].set(k),
            v_pages=cache.v_pages.at[:, ids].set(v),
            conv=cache.conv if c is None else cache.conv.at[ids].set(c),
        )

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    @part("cache")
    def _upload_pages_q_jit(self, cache: KVCache, ids, k, v, ks, vs, c=None):
        return dataclasses.replace(
            cache,
            k_pages=cache.k_pages.at[:, ids].set(k),
            v_pages=cache.v_pages.at[:, ids].set(v),
            k_scale=cache.k_scale.at[:, ids].set(ks),
            v_scale=cache.v_scale.at[:, ids].set(vs),
            conv=cache.conv if c is None else cache.conv.at[ids].set(c),
        )

    def write_pages(self, page_ids, payload: dict) -> None:
        """Upload tier payloads into freshly allocated pages (promotion
        / hibernation resume). ``payload`` is the tier's canonical int8
        form (values + per-token scales) or a raw-dtype payload from
        ``read_pages``; an int8 payload promotes into an unquantized
        pool by dequantizing on the way up (the round-4 int8 bound is
        the parity contract, tests/test_kv_tiers.py)."""
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        c = self.cache
        why = self.layout.refuses("write_pages")
        if why is not None:
            raise ValueError(why)
        state = None
        if c.conv is not None:
            if "c" not in payload:
                # pages without their conv state would resume a
                # sequence from a wrong state: the caller re-prefills
                raise ValueError(
                    "page payload carries no conv state for a model "
                    "that keeps one"
                )
            state = jnp.asarray(
                np.asarray(payload["c"]).transpose(1, 0, 2, 3).reshape(
                    len(ids), -1
                )
            ).astype(c.conv.dtype)
            self._count_state_commit("resume")
        if c.quantized:
            self.cache = self._upload_pages_q_jit(
                c, ids,
                jnp.asarray(payload["k"]), jnp.asarray(payload["v"]),
                jnp.asarray(payload["ks"]), jnp.asarray(payload["vs"]),
                state,
            )
            return
        pool_dt = c.k_pages.dtype
        if payload["k"].dtype == np.int8:
            from .kvtier import dequantize_payload

            vals = dequantize_payload(payload, np.float32)
        else:
            vals = payload
        self.cache = self._upload_pages_jit(
            c, ids,
            jnp.asarray(vals["k"]).astype(pool_dt),
            jnp.asarray(vals["v"]).astype(pool_dt),
            state,
        )

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _prefill_jit(
        self, params, cache: KVCache, ids, valid_len, page_table, start
    ):
        B, T = ids.shape
        positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        # only the last valid position is sampled from: the LM head
        # runs on that one position per row, never on [B, T, V]
        last = jnp.maximum(valid_len - 1, 0)
        if self.pp > 1:
            from ..parallel.pipeline import pipeline_forward

            logits, hidden, (k, v) = pipeline_forward(
                self.mcfg, params, ids, positions, valid_len, self.mesh,
                n_microbatches=min(
                    self.ecfg.pp_microbatches or self.pp, B
                ),
                use_pallas=self.use_pallas,
            )
            with part("head"):
                logits = jnp.take_along_axis(
                    logits, last[:, None, None], axis=1
                )
        else:
            logits, hidden, (k, v) = transformer.forward(
                self.mcfg, params, ids, positions, valid_len,
                use_pallas=self.use_pallas,
                kernel_mesh=self.kernel_mesh,
                ring_mesh=self.mesh if self.sp > 1 else None,
                ep_mesh=self.ep_mesh,
                logit_positions=last,
                ssm_pending=False,
            )
        cache = write_kv(
            cache, k, v, page_table, start, valid_len,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )
        return logits[:, 0], cache, self._route_stats(k)

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _prefill_chunk_jit(
        self, params, cache: KVCache, ids, valid_len, page_table, start
    ):
        """One fixed-size chunk of a long prompt: attends over the pages
        written by earlier chunks (past_len = start), scatters its own K/V.
        A single compile serves every chunk of every long prompt."""
        B, C = ids.shape
        positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        logits, _, (k, v) = transformer.forward(
            self.mcfg, params, ids, positions, valid_len,
            paged_past=self._paged(cache, page_table),
            past_len=start,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
            ep_mesh=self.ep_mesh,
            logit_positions=jnp.maximum(valid_len - 1, 0),
            conv_state=self._state_at(cache, page_table, start),
            state_past=self._state_past(cache, page_table, start),
            ssm_pending=False,
            window_pool=self._window_pool_of(cache, page_table),
        )
        cache = write_kv(
            cache, k, v, page_table, start, valid_len,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )
        return logits[:, 0], cache, self._route_stats(k)

    def whole_blocks(self, n: int) -> int:
        """The leading tokens of ``n`` that are whole blocks: what a
        prefill of a model that generates by blocks takes of a prompt
        (the rest, under a block long, starts the first generated block:
        ``decode_block_async``'s ``first``). ``n`` for a causal model. A
        prefill entry point cuts what it is given to this length, so a
        chunk's padding starts at a block's edge and no valid query sees
        it."""
        Bk = self.mcfg.block_length
        return n if Bk == 1 else max(n, 0) // Bk * Bk

    def _prefill_out(self, logits, route, n: int, on_device: bool):
        """What a prefill entry point returns. ``on_device``: the
        program's own ``(logits [B, V], routing counts)`` where they
        lie, with no wait for the program (rows past ``n`` are the
        bucket's padding; the counts are None for a model that counts
        none): the scheduler samples first tokens from them on the
        device and fetches a whole admission wave at once. Otherwise the
        ``n`` real rows on the host, a wait for the program, the counts
        left for ``take_route_stats``."""
        if on_device:
            return logits, route
        self._route_dev = route
        return np.asarray(logits[:n])

    def prefill_bucket(self, rows: int, maxlen: int) -> Tuple[int, int]:
        """``(rows, length)`` of the program a prefill dispatch of
        ``rows`` rows, the longest of ``maxlen`` tokens, runs: both
        rounded up to the next bucket. THE place that says it: the
        prefill entry points build their arrays at it and the scheduler
        counts a dispatch's padding from it."""
        T = next_bucket(max(maxlen, 1), lo=16, hi=self.ecfg.max_context())
        if T % self.sp:  # ring prefill shards T over the seq axis
            T = -(-T // self.sp) * self.sp
        return next_bucket(max(rows, 1), lo=1, hi=1 << 16), T

    def prefill_buckets(self, lengths) -> List[Tuple[int, int]]:
        """The ``prefill_bucket`` of every program a prefill dispatch of
        rows of ``lengths`` tokens runs: one for the rows together, or,
        for a row longer than ``prefill_chunk`` (``prefill``), a whole
        chunk a program."""
        C = self.ecfg.prefill_chunk
        if len(lengths) == 1 and lengths[0] > C and self.sp == self.pp == 1:
            return [(1, C)] * -(-lengths[0] // C)
        return [self.prefill_bucket(len(lengths), max(lengths, default=1))]

    def prefill(
        self, token_ids: np.ndarray, page_table: np.ndarray,
        start: int = 0, on_device: bool = False,
    ):
        """One prompt ([T] int32) -> last-position logits [V]
        (``on_device``: ``_prefill_out``). ``page_table``
        is the slot's [MP] row.

        Long prompts (> ``prefill_chunk``) are processed in fixed-size
        chunks so attention transients stay O(chunk x ctx) instead of
        O(T^2) and one compile covers all lengths — except under
        sequence parallelism (sp > 1), where the ring path wants the full
        sequence resident and sharded (ops/ring_attention.py).

        ``start`` > 0 prefills a SUFFIX beginning at that global
        position, attending over pages that already hold positions
        < start (shared-prefix jobs: the common prefix was prefilled
        once into pages at the head of ``page_table``)."""
        if faults.ACTIVE is not None:
            faults.inject("runner.prefill")
        token_ids = token_ids[: self.whole_blocks(start + len(token_ids)) - start]
        n = len(token_ids)
        C = self.ecfg.prefill_chunk
        # the chunked paged path does not route through the ring (sp) or
        # pipeline (pp) wrappers — guard BEFORE any start>0 branch
        assert start == 0 or (self.sp == 1 and self.pp == 1), (
            "suffix prefill is unsupported under sp/pp"
        )
        self.pools.bind_fresh(page_table, [start])
        if start > 0 and n <= C:
            out = self.prefill_batch_at(
                [token_ids], page_table[None, :], [start],
                on_device=on_device,
            )
            return out if on_device else out[0]
        if (start > 0 or n > C) and self.sp == 1 and self.pp == 1:
            table_dev = jnp.asarray(page_table[None, :], jnp.int32)
            for off in range(0, n, C):
                seg = token_ids[off : off + C]
                ids = np.zeros((1, C), np.int32)
                ids[0, : len(seg)] = seg
                self._count_state_commit("chunk")
                self._count_latent("absorbed", steps=start + off + C)
                self.pools.bind_written(page_table, [start + off], [len(seg)])
                logits, self.cache, route = self._prefill_chunk_jit(
                    self.params,
                    self.cache,
                    jnp.asarray(ids),
                    jnp.asarray(np.array([len(seg)], np.int32)),
                    table_dev,
                    jnp.asarray(np.array([start + off], np.int32)),
                )
                # the chunk is dispatched: what slid out behind its end
                # goes back before the next chunk binds
                self.pools.release_behind(
                    page_table, [start + off + len(seg)]
                )
            out = self._prefill_out(logits, route, 1, on_device)
            return out if on_device else out[0]
        _, T = self.prefill_bucket(1, n)
        ids = np.zeros((1, T), np.int32)
        ids[0, :n] = token_ids
        self._count_state_commit("prefill")
        self._count_latent("expanded", steps=T)
        self.pools.bind_written(page_table, [0], [n])
        logits, self.cache, route = self._prefill_jit(
            self.params,
            self.cache,
            jnp.asarray(ids),
            # (numpy first: a Python list would be converted by a device
            # program of its own, two more dispatches a row)
            jnp.asarray(np.array([n], np.int32)),
            jnp.asarray(page_table[None, :], jnp.int32),
            jnp.asarray(np.array([0], np.int32)),
        )
        out = self._prefill_out(logits, route, 1, on_device)
        return out if on_device else out[0]

    def prefill_batch(
        self, rows: list, page_tables: np.ndarray,
        on_device: bool = False,
    ):
        """Batched prefill: N prompts ([Ti] int32 each) in ONE device
        program -> last-position logits [N, V] (``on_device``:
        ``_prefill_out``). ``page_tables`` is
        [N, MP]. Rows are padded to a (power-of-two x power-of-two)
        [B, T] bucket so compile count stays O(log^2); padding rows carry
        ``valid_len`` 0 and an all-zero table, so their K/V land on the
        garbage page and their logits are discarded.

        This is the batch-throughput path for classify-style jobs (the
        reference's headline workload, /root/reference/README.md:36-38):
        prefill FLOPs for many short rows ride one MXU dispatch instead
        of one per row."""
        if faults.ACTIVE is not None:
            faults.inject("runner.prefill")
        rows = [r[: self.whole_blocks(len(r))] for r in rows]
        n = len(rows)
        maxlen = max((len(r) for r in rows), default=1)
        B, T = self.prefill_bucket(n, maxlen)
        ids = np.zeros((B, T), np.int32)
        lens = np.zeros((B,), np.int32)
        tables = np.zeros((B, page_tables.shape[1]), np.int32)
        for i, r in enumerate(rows):
            ids[i, : len(r)] = r
            lens[i] = len(r)
            tables[i] = page_tables[i]
        self.pools.bind_fresh(tables[:n], [0] * n)
        self._count_state_commit("prefill", n)
        self._count_latent("expanded", steps=ids.shape[1])
        self.pools.bind_written(tables[:n], [0] * n, lens[:n])
        logits, self.cache, route = self._prefill_jit(
            self.params,
            self.cache,
            jnp.asarray(ids),
            jnp.asarray(lens),
            jnp.asarray(tables),
            jnp.zeros((B,), jnp.int32),
        )
        return self._prefill_out(logits, route, n, on_device)

    def prefill_batch_at(
        self, rows: list, page_tables: np.ndarray, starts,
        on_device: bool = False,
    ):
        """Batched SUFFIX prefill: like ``prefill_batch`` but each row
        begins at global position ``starts[i]``, attending over pages
        that already hold its earlier positions — the per-row dispatch
        for shared-prefix jobs (the common prefix occupies the head of
        every row's table; only the suffix rides this program). Padding
        rows carry ``valid_len`` 0, start 0 and an all-zero table, so
        their K/V land on the garbage page."""
        if faults.ACTIVE is not None:
            faults.inject("runner.prefill")
        rows = [
            r[: self.whole_blocks(int(s) + len(r)) - int(s)]
            for r, s in zip(rows, starts)
        ]
        n = len(rows)
        maxlen = max((len(r) for r in rows), default=1)
        B, T = self.prefill_bucket(n, maxlen)
        ids = np.zeros((B, T), np.int32)
        lens = np.zeros((B,), np.int32)
        st = np.zeros((B,), np.int32)
        tables = np.zeros((B, page_tables.shape[1]), np.int32)
        for i, r in enumerate(rows):
            ids[i, : len(r)] = r
            lens[i] = len(r)
            st[i] = starts[i]
            tables[i] = page_tables[i]
        self.pools.bind_fresh(tables[:n], st[:n])
        self._count_state_commit("chunk", n)
        self._count_latent("absorbed", steps=int(st.max()) + ids.shape[1])
        self.pools.bind_written(tables[:n], st[:n], lens[:n])
        logits, self.cache, route = self._prefill_chunk_jit(
            self.params,
            self.cache,
            jnp.asarray(ids),
            jnp.asarray(lens),
            jnp.asarray(tables),
            jnp.asarray(st),
        )
        self.pools.release_behind(tables[:n], st[:n] + lens[:n])
        return self._prefill_out(logits, route, n, on_device)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def _trunk_decode(
        self, params, cache: KVCache, ids, positions, past_len,
        page_table, window_past=None, kv_chunk: int = 1, pfx=None,
        conv_state=None, state_past=None,
    ):
        """One decode trunk forward over the paged past — the plain
        scanned forward, or the stage-local pipeline schedule under
        ``pipe > 1`` (parallel/pipeline.pipeline_decode).

        ``kv_chunk`` selects nothing: the decode kernel has one fetch
        schedule, read from ``past_len`` and the table. The name stays
        because the benchmark passes it (perfbench/sut.py).

        ``pfx`` = tuple of (pages [Pp_g] int32, pfx_len [B] int32)
        groups enabling Hydragen-style split decode over job-shared
        table-head prefixes (ops/attention.py); the prefix cache is
        disabled under pp, so the pipeline path never sees one.

        ``conv_state`` ([L_conv, B, K-1, H]) is the conv layers' state
        at ``positions`` for a caller that carries it itself (the fused
        window's scan); otherwise it is read from the cache at
        ``past_len``. ``state_past`` likewise for the mamba layers'
        (``transformer.StatePast``): a decode step reads the pool where
        it lies and advances nothing; ``write_kv`` commits."""
        B = ids.shape[0]
        if conv_state is None:
            conv_state = self._state_at(cache, page_table, past_len)
        if state_past is None:
            state_past = self._state_past(cache, page_table, past_len)
        ones = jnp.ones((B,), jnp.int32)
        if self.pp > 1:
            from ..parallel.pipeline import pipeline_decode

            return pipeline_decode(
                self.mcfg, params, ids, positions, ones,
                cache.k_pages, cache.v_pages, page_table, past_len,
                self.mesh, use_pallas=self.use_pallas,
                window_past=window_past,
            )
        return transformer.forward(
            self.mcfg, params, ids, positions, ones,
            paged_past=self._paged(cache, page_table),
            past_len=past_len,
            window_past=window_past,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
            ep_mesh=self.ep_mesh,
            pfx_groups=pfx,
            conv_state=conv_state,
            state_past=state_past, ssm_pending=True,
            window_pool=self._window_pool_of(cache, page_table),
        )

    def _chunk_for_table(self, page_table: np.ndarray) -> int:
        """1, whatever the table: the decode kernel fetches page by page
        in any layout (ops/pallas_paged.py). What is left of the choice
        between a chunked and a per-page schedule, kept because the
        benchmark calls it and hands the result to ``_trunk_decode``
        (perfbench/sut.py)."""
        return 1

    def _count_kv_pages(self, past_len, page_table, steps: int, pfx) -> None:
        """K/V pages a decode dispatch's attention fetches against the
        pages its rows' tokens fill, every step and attention layer of
        it folded in. Host arithmetic from what the dispatch is given:
        the Pallas kernel fetches a row's pages up to its last token's
        (past its shared prefix under a prefix split, whose pages the
        carry reads once for the batch); the gathered-page path fetches
        every row's whole table."""
        if not telemetry.ENABLED:
            return
        from ..ops import pallas_paged

        PS = self.ecfg.kv_page_size
        past = np.asarray(past_len, np.int64)
        table = np.asarray(page_table)
        # what the kernel's gate reads as a head: a latent layer's
        # absorbed query is as wide as the pool's row
        head = jax.ShapeDtypeStruct((
            1, 1, self.mcfg.page_width if self.mcfg.num_latent_layers
            else self.mcfg.kernel_head_dim,
        ), jnp.float32)
        kernel = self.use_pallas and pallas_paged.paged_decode_supported(
            head, self.cache.k_pages
        )
        fetched = needed = 0.0
        # a kind of attention layers at a time: (layers, window); a
        # window layer NEEDS the pages of its window, and fetches from
        # the page of its oldest visible position on
        W = self.mcfg.sliding_window
        # every READER of a pool fetches it: a "cross" layer reads a full
        # layer's pages again (``ModelConfig.kv_readers``)
        for pool, mixer, own, win in (
            ("full", "attention", self.mcfg.num_pool_layers, 0),
            ("window", "swa", self.mcfg.num_window_layers, W),
        ):
            layers = self.mcfg.kv_readers(mixer)
            if not layers:
                continue
            read = float(steps) * float(
                (np.minimum(past, win) if win else past).sum()
            )
            telemetry.KV_READ_TOKENS_TOTAL.inc(read * own, pool, "own")
            if layers > own:
                telemetry.KV_READ_TOKENS_TOTAL.inc(
                    read * (layers - own), pool, "shared"
                )
            first = first_live_page(past, win, PS) if win else 0
            need = (np.minimum(past, win - 1) if win else past) / PS
            if kernel:
                got = -(-past // PS) - first
                if pfx and self.kernel_mesh is None and not win:
                    shared = sum(np.asarray(n, np.int64) // PS for _, n in pfx)
                    got, need = got - shared, need - shared
            elif win:
                got = np.full(past.shape, min(
                    table.shape[-1], window_span_pages(win, 0, PS)
                ), np.int64)
            else:
                got = np.full(past.shape, table.shape[-1], np.int64)
            times = float(steps * layers)
            fetched += times * float(np.maximum(got, 0).sum())
            needed += times * float(np.maximum(need, 0).sum())
        telemetry.KV_PAGES_FETCHED_TOTAL.inc(fetched)
        telemetry.KV_PAGES_NEEDED_TOTAL.inc(needed)
        f0, n0 = self._kv_pages or (0.0, 0.0)
        self._kv_pages = (f0 + fetched, n0 + needed)

    def take_kv_pages(self):
        """``(fetched, needed)`` of the decode dispatches since the last
        take (``_count_kv_pages``), or None when there were none."""
        pages, self._kv_pages = self._kv_pages, None
        return pages

    @functools.partial(
        jax.jit, static_argnums=(0,), donate_argnums=(2,)
    )
    def _decode_jit(
        self, params, cache: KVCache, ids, past_len, page_table,
        rng, temperature, top_p, top_k, allowed_packed, row_seeds,
        penalties=None, pfx=None,
    ):
        B = ids.shape[0]
        allowed = None
        if allowed_packed is not None:
            allowed = unpack_mask(allowed_packed, self.mcfg.vocab_size)
        positions = past_len[:, None]  # current token position == past length
        logits, _, (k, v) = self._trunk_decode(
            params, cache, ids, positions, past_len, page_table,
            pfx=pfx,
        )
        cache = write_kv(
            cache, k, v, page_table, past_len, jnp.ones((B,), jnp.int32),
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )
        step_logits = logits[:, 0]  # [B, V]
        if penalties is not None:
            # pre-applied so the reported logprob is w.r.t. the
            # penalized distribution too (seen-bits arrive packed)
            from ..ops.sampling import apply_penalties

            seen_packed, ids_p, cnt_p, pres, freq, rep = penalties
            seen = unpack_mask(seen_packed, self.mcfg.vocab_size)
            step_logits = apply_penalties(
                step_logits, seen, ids_p, cnt_p, pres, freq, rep
            )
        tok = sample(
            step_logits, rng,
            temperature=temperature, top_p=top_p, top_k=top_k,
            allowed=allowed, row_seeds=row_seeds,
        )
        logp = cumulative_logprob(step_logits, tok)
        unmasked_ok = None
        if allowed is not None:
            # whether each row's UNMASKED argmax lay inside its mask:
            # what a speculative window's verify would have found, for
            # the scheduler's choice between window and masked step
            # (take_unmasked_ok)
            with part("sample"):
                top = jnp.argmax(step_logits, axis=-1)
                unmasked_ok = jnp.take_along_axis(
                    allowed, top[:, None], axis=1
                )[:, 0]
        return tok, logp, cache, self._route_stats(k), unmasked_ok

    def take_unmasked_ok(self) -> Optional[np.ndarray]:
        """[B] bool of the last masked ``decode_step``: whether a row's
        unmasked argmax was a token its mask allowed; None after a step
        without masks."""
        ok, self._unmasked_ok = self._unmasked_ok, None
        return ok

    def decode_step(
        self,
        last_tokens: np.ndarray,     # [B] int32
        past_len: np.ndarray,        # [B] int32
        page_table: np.ndarray,      # [B, MP] int32
        rng: jax.Array,
        temperature: np.ndarray,     # [B]
        top_p: np.ndarray,           # [B]
        top_k: Optional[np.ndarray] = None,     # [B] int32; None => disabled
        allowed: Optional[np.ndarray] = None,   # [B, ceil(V/8)] uint8
        row_seeds: Optional[np.ndarray] = None,  # [B] int32
        penalties=None,  # (seen_packed [B, ceil(V/8)] uint8, pen_ids
        #                   [B,K], pen_cnt [B,K], presence [B],
        #                   frequency [B], repetition [B]) — seen bits
        #                   arrive PRE-PACKED (scheduler maintains them
        #                   incrementally; no O(B*V) host work here)
        pfx=None,  # tuple of (pages [Pp_g], pfx_len [B]) split-prefix groups
    ) -> Tuple[np.ndarray, np.ndarray]:
        if faults.ACTIVE is not None:
            faults.inject("runner.decode")
        B = len(last_tokens)
        if top_k is None:
            top_k = np.zeros((B,), np.int32)
        if penalties is not None:
            seen_packed, ids_p, cnt_p, pres, freq, rep = penalties
            penalties = (
                jnp.asarray(seen_packed, jnp.uint8),
                jnp.asarray(ids_p, jnp.int32),
                jnp.asarray(cnt_p, jnp.float32),
                jnp.asarray(pres, jnp.float32),
                jnp.asarray(freq, jnp.float32),
                jnp.asarray(rep, jnp.float32),
            )
        self._count_state_commit("window", B)
        self._count_latent("absorbed", past_len)
        self.count_sample(temperature)
        self._count_kv_pages(past_len, page_table, 1, pfx)
        self.pools.bind_written(page_table, past_len, np.ones((B,), np.int32))
        tok, logp, self.cache, self._route_dev, ok = self._decode_jit(
            self.params,
            self.cache,
            jnp.asarray(last_tokens[:, None], jnp.int32),
            jnp.asarray(past_len, jnp.int32),
            jnp.asarray(page_table, jnp.int32),
            rng,
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
            # the masks arrive as the device program takes them: the
            # scheduler keeps and assembles them bit-packed (_fsm_masks)
            None if allowed is None else jnp.asarray(allowed, jnp.uint8),
            None if row_seeds is None else jnp.asarray(row_seeds, jnp.int32),
            penalties,
            self._pfx_jnp(pfx),
        )
        self._unmasked_ok = None if ok is None else np.asarray(ok)
        return np.asarray(tok), np.asarray(logp)

    @staticmethod
    def _pfx_jnp(pfx):
        if not pfx:
            return None
        return tuple(
            (jnp.asarray(p, jnp.int32), jnp.asarray(n, jnp.int32))
            for p, n in pfx
        )

    # ------------------------------------------------------------------
    # multi-step decode
    # ------------------------------------------------------------------

    @functools.partial(
        jax.jit, static_argnums=(0, 9, 11), donate_argnums=(2,)
    )
    def _decode_multi_jit(
        self, params, cache: KVCache, last, past_len, page_table,
        rng, temperature, top_p, steps: int, top_k,
        kv_chunk: int = 1, pfx=None,
    ):
        """``steps`` decode iterations in ONE device program: the sampled
        token feeds the next step on-device, so the host pays one dispatch
        + one fetch per window instead of per token. This is the
        throughput path for unconstrained generation — constrained rows
        need the host FSM between steps (scheduler falls back to
        single-step).

        The page pool is NOT threaded through the step scan: a carried
        pool would be read (attention) and written (scatter) every
        iteration, and XLA copies the multi-GB buffer pair per step to
        keep that safe — measured ~17 ms/step on v5e vs ~2.6 ms for the
        whole 28-layer trunk. Instead each step's K/V lands in a small
        carried window buffer ([L, B, steps, KVH*Dh] fused, in-place
        dynamic_update_slice) that attention reads alongside the pages,
        and the pool takes ONE bulk write per window out here where
        donation makes it truly in-place.

        ``kv_chunk`` (static) selects nothing, like ``_trunk_decode``'s:
        the benchmark's ahead-of-time compiles pass it by position
        (tests/perfbench/test_aot_v5e.py)."""
        del kv_chunk
        B = last.shape[0]
        toks, logps, wk, wv = self._window_scan(
            params, cache, last, past_len, page_table, rng,
            temperature, top_p, steps, top_k, pfx=pfx,
        )
        cache = write_kv(
            cache, wk, wv, page_table, past_len,
            jnp.full((B,), steps, jnp.int32),
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )
        route = wk.route if isinstance(wk, MixedChunk) else None
        return toks, logps, cache, route

    def _window_scan(
        self, params, cache: KVCache, last, past_len, page_table,
        rng, temperature, top_p, steps: int, top_k,
        allowed0=None, pfx=None,
    ):
        """The shared fused-window scan: ``steps`` trunk forwards over
        invariant pages + the carried window buffer, sampling on-device.
        Returns (toks [steps, B], logps [steps, B], wk, wv) with the
        window K/V NOT yet committed to pages — callers decide the
        commit (full window for unconstrained decode, verified prefix
        for speculative constrained decode). For a model with layers of
        several kinds ``wk`` is a ``MixedChunk``: the K window, the conv
        layers' state before the window followed by each step's gated
        input (carried through the scan like the K/V window, so ANY
        accepted prefix commits by ``write_kv``), and the routing
        counts of each step ([steps, 6], ``_route_stats``). The state
        layers' uncommitted tokens (conv columns, and what
        ``transformer.pending_buffers`` names) ride in STEP-MAJOR
        buffers (``transformer.window_buffer``): this scan owns the
        writes, one dense slab of each buffer after a step
        (``window_put``) and no copy of any; a layer's mixer reads the
        earlier steps' slabs where they lie and keeps the step's own
        token to itself (``StatePast.window``); ``MixedChunk.ssm`` hands
        the buffers to ``write_kv`` as they are.

        ``allowed0`` ([B, V] bool, optional) masks the FIRST step's
        logits only: a row whose previous window rejected a token takes
        its FSM-masked step INSIDE the next window (crossing the
        scaffold token), so one adversarial row no longer degrades the
        whole batch to masked single-steps.

        The window's buffers, their fill before the scan and their
        writes after a step are the ``cache`` part of the step
        (``lowering.PARTS``), the mask and the key of a step's draw its
        ``sample`` part; the scan's own carry and counter are no part's."""
        B = last.shape[0]
        L = self.mcfg.num_kv_layers   # full layers, then window layers
        KD = self.mcfg.page_width
        # window buffers hold UNQUANTIZED step K/V (they are read by
        # attention before ever touching the pool; write_kv quantizes
        # at commit) — under an int8 pool they stay in compute dtype
        dtype = (
            jnp.dtype(self.ecfg.activation_dtype)
            if cache.quantized
            else cache.k_pages.dtype
        )
        # FUSED trailing axis (like the page pool, kvcache.py): the
        # unfused [.., KVH, Dh] form pads KVH up to a full sublane tile
        # on TPU — a 2x memory expansion on multi-GB buffers at large B
        # V's buffer: as wide as K's, a latent layer's index keys, or none
        # (``ModelConfig.pool_row_widths``)
        widths = self.mcfg.pool_row_widths
        VD = widths[1] if len(widths) > 1 else 0
        with part("cache"):
            wk0 = jnp.zeros((L, B, steps, KD), dtype)
            wv0 = jnp.zeros((L, B, steps, VD), dtype) if VD else None
        mixed = not self.mcfg.homogeneous
        K1 = self.mcfg.conv_state_len or self.mcfg.state_conv_len
        wc0 = ws0 = past = run0 = None

        def window_of(state):  # [L, B, K-1, C] -> [L, B, K-1 + steps, C]
            return jnp.concatenate(
                [state, jnp.zeros(state.shape[:2] + (steps,)
                                  + state.shape[3:], state.dtype)],
                axis=2,
            )

        if self.mcfg.num_conv_layers:
            with part("cache"):
                wc0 = window_of(self._state_at(cache, page_table, past_len))
        pending = ()
        if self.mcfg.num_state_layers:
            # the pool is a constant of the scan, like the pages: the
            # window's tokens ride in buffers and write_kv commits them.
            # The buffers are STEP-MAJOR (``transformer.window_buffer``):
            # a step writes its token of every layer as one dense slab
            # of each, in place, and a layer reads the earlier steps'
            # slabs where they lie (``StatePast.window``)
            past = self._state_past(cache, page_table, past_len)
            m = self.mcfg
            Lm = m.num_state_layers
            pending = transformer.pending_buffers(
                m, jnp.dtype(self.ecfg.activation_dtype)
            )
            with part("cache"):
                ws0 = {
                    name: transformer.window_buffer(
                        steps + (K1 if name == "conv" else 0), Lm, B,
                        width, dt,
                    )
                    for name, width, dt in (
                        ("conv", m.state_conv_dim, past.conv.dtype),
                    ) + pending
                }
                for j in range(K1):  # the columns before the window
                    ws0["conv"] = transformer.window_put(
                        ws0["conv"], j, past.conv[:, :, j]
                    )
                # Mamba-1 layers: the rows' state itself rides the scan
                # (``transformer.running_state``; None for other kinds)
                run0 = transformer.running_state(m, past)

        def body(carry, step_idx):
            wk, wv, wc, ws, run, last = carry
            conv_state = None
            if wc is not None:
                with part("cache"):
                    conv_state = jax.lax.dynamic_slice_in_dim(
                        wc, step_idx, K1, axis=2
                    )
            logits, _, (k, v) = self._trunk_decode(
                params, cache, last[:, None],
                (past_len + step_idx)[:, None], past_len, page_table,
                window_past=(wk, wv, step_idx), pfx=pfx,
                conv_state=conv_state,
                state_past=None if ws is None else dataclasses.replace(
                    past, conv=ws["conv"],
                    window=tuple(ws[n] for n, _, _ in pending) + (step_idx,),
                    running=run,
                ),
            )
            route = self._route_stats(k)
            with part("cache"):
                if mixed:
                    if wc is not None:
                        wc = jax.lax.dynamic_update_slice(
                            wc, k.conv[:, :, K1:].astype(wc.dtype),
                            (0, 0, K1 + step_idx, 0),
                        )
                    if ws is not None:
                        # the step's token of every state layer ([L_m,
                        # B, 1, width]) as ONE slab at the step's place
                        ws = {
                            name: transformer.window_put(
                                buf,
                                step_idx + (K1 if name == "conv" else 0),
                                k.ssm[name][:, :, 0],
                            )
                            for name, buf in ws.items()
                        }
                        if run is not None:
                            run = k.ssm["S"]
                    k = k.k
                wk = jax.lax.dynamic_update_slice(
                    wk, k.astype(dtype).reshape(L, B, 1, KD),
                    (0, 0, step_idx, 0),
                )
                if wv is not None:
                    wv = jax.lax.dynamic_update_slice(
                        wv, v.astype(dtype).reshape(L, B, 1, VD),
                        (0, 0, step_idx, 0),
                    )
            step_logits = logits[:, 0]
            sample_logits = step_logits
            with part("sample"):
                if allowed0 is not None:
                    # masked sample == masked argmax for the greedy rows
                    # this path serves; logp stays over the UNMASKED
                    # logits — the same convention as the single-step
                    # path (sample under the mask, report full-vocab
                    # logprob), so cumulative_logprob is path-independent
                    sample_logits = jnp.where(
                        step_idx == 0,
                        jnp.where(allowed0, step_logits, NEG_INF),
                        step_logits,
                    )
                key = jax.random.fold_in(rng, step_idx)
            tok = sample(
                sample_logits, key,
                temperature=temperature, top_p=top_p, top_k=top_k,
            )
            logp = cumulative_logprob(step_logits, tok)
            return (wk, wv, wc, ws, run, tok), (tok, logp, route)

        (wk, wv, wc, ws, _, _), (toks, logps, route) = jax.lax.scan(
            body,
            (wk0, wv0, wc0, ws0, run0, last),
            jnp.arange(steps, dtype=jnp.int32),
        )
        if run0 is not None:
            # the commit runs from the pool and the window's tokens, for
            # ANY accepted length; what it needs beside them is the
            # layers' ``A`` (``kvcache._advance_mamba1``)
            ws["A"] = transformer.mamba1_decay(
                params["layers"]["mamba1"]["a_log"]
            )
        if mixed:
            # ``ws`` goes to ``kvcache.write_state`` as the scan carried it:
            # the commit reads each layer's tokens where they lie
            # (``MixedChunk.ssm``), no transposed copy is made for it
            wk = MixedChunk(k=wk, conv=wc, route=route, ssm=ws)
        return toks, logps, wk, wv

    def decode_multi(
        self,
        last_tokens: np.ndarray,     # [B] int32
        past_len: np.ndarray,        # [B] int32
        page_table: np.ndarray,      # [B, MP] int32
        rng: jax.Array,
        temperature: np.ndarray,     # [B]
        top_p: np.ndarray,           # [B]
        steps: int,
        top_k: Optional[np.ndarray] = None,
        pfx=None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (tokens [steps, B], logprobs [steps, B])."""
        toks, logps = self.decode_multi_async(
            last_tokens, past_len, page_table, rng, temperature, top_p,
            steps, top_k=top_k, pfx=pfx,
        )
        self._route_dev = self.window_route
        return np.asarray(toks), np.asarray(logps)

    def decode_multi_async(
        self,
        last_tokens,                 # [B] int32 (numpy OR device array)
        past_len: np.ndarray,        # [B] int32
        page_table: np.ndarray,      # [B, MP] int32
        rng: jax.Array,
        temperature: np.ndarray,     # [B]
        top_p: np.ndarray,           # [B]
        steps: int,
        top_k: Optional[np.ndarray] = None,
        pfx=None,  # tuple of (pages [Pp_g], pfx_len [B]) split-prefix groups
    ) -> Tuple[jax.Array, jax.Array]:
        """Like ``decode_multi`` but returns DEVICE arrays without
        blocking: dispatch is async, so callers can chain the next
        window off ``toks[-1]`` (still on device) before this window's
        results are fetched, and the device runs that window while the
        host accepts this one. On a host that holds the chip the host's
        share is small (2-4 % of a generate window, PERF.md §5: ledger
        PR 25, 29); what the chaining buys is that the device never
        waits for it."""
        if faults.ACTIVE is not None:
            faults.inject("runner.decode")
        B = past_len.shape[0]
        if top_k is None:
            top_k = np.zeros((B,), np.int32)
        # the window's routing counts stay on the device beside its
        # tokens; whoever fetches the tokens fetches them
        self._count_state_commit("window", B, steps)
        self._count_latent("absorbed", past_len, steps)
        self.count_sample(temperature)
        self._count_kv_pages(past_len, page_table, steps, pfx)
        self.pools.bind_written(page_table, past_len, np.full((B,), steps))
        toks, logps, self.cache, self.window_route = self._decode_multi_jit(
            self.params,
            self.cache,
            jnp.asarray(last_tokens, jnp.int32),
            jnp.asarray(past_len, jnp.int32),
            jnp.asarray(page_table, jnp.int32),
            rng,
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32),
            steps,
            jnp.asarray(top_k, jnp.int32),
            pfx=self._pfx_jnp(pfx),
        )
        return toks, logps

    # ------------------------------------------------------------------
    # generation by blocks (``ModelConfig.block_length`` > 1)
    # ------------------------------------------------------------------

    def _block_forward(
        self, params, cache: KVCache, x, start, page_table, past_len=None,
        window_past=None,
    ):
        """One forward of a block ``x`` [B, Bk] at positions ``start``
        [B] + 0..Bk-1 over the paged past (``past_len`` tokens, ``start``
        where no window runs), the window's earlier blocks and the block
        itself under the block mask: ``(logits [B, Bk, V], K/V of the
        block)``. Both kinds of forward are this one: a denoising
        forward reads the logits and drops the K/V, a commit forward
        keeps the K/V and reads no logits (the head is then dead code
        and the compiler drops it)."""
        B, Bk = x.shape
        positions = start[:, None] + jnp.arange(Bk, dtype=jnp.int32)[None]
        logits, _, (k, v) = transformer.forward(
            self.mcfg, params, x, positions, jnp.full((B,), Bk, jnp.int32),
            paged_past=self._paged(cache, page_table),
            past_len=start if past_len is None else past_len,
            window_past=window_past,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
            ep_mesh=self.ep_mesh,
        )
        return logits, (k, v)

    @functools.partial(
        jax.jit, static_argnums=(0, 14), donate_argnums=(2,)
    )
    def _decode_block_jit(
        self, params, cache: KVCache, first, live, past_len, page_table,
        rng, temperature, top_p, top_k, steps, rule, tau, blocks: int,
    ):
        """A WINDOW of ``blocks`` whole blocks for the batch in one
        device program: for each block a ``while_loop`` of denoising
        forwards (K/V not kept) that ends when no row holds a mask, at
        most ``Bk`` turns, then the commit forward of the filled block.
        ``first`` [B, Bk] is the window's first block as the host knows
        it (a new row's leftover prompt tokens, then masks; all masks
        for a row that continues; no mask at all for a slot that is not
        live); every later block starts as masks. ``steps`` [B] (the
        denoising forwards a row's block takes: a forward fills the even
        share ``Bk // steps``, the first ``Bk % steps`` one more),
        ``rule`` [B] and ``tau`` [B] are operands, not static: one
        program serves every request.

        As in ``_decode_multi_jit`` the pool is a CONSTANT of the loops:
        a committed block's K/V lands in the window's buffers, which the
        later blocks' attention reads beside the pages, and the pool
        takes one write a window. Returns tokens and log-probabilities
        ``[blocks * Bk, B]`` in position order (``decode_multi_async``'s
        layout), the denoising forwards each block ran ``[blocks]``, the
        cache and the routing counts ``[forwards, 6]`` padded with
        zeros."""
        m = self.mcfg
        B, Bk = first.shape
        L, KD = m.num_kv_layers, m.page_width
        MASK = m.mask_token_id
        dtype = cache.k_pages.dtype
        W = blocks * Bk
        with part("cache"):
            wk0 = jnp.zeros((L, B, W, KD), dtype)
            wv0 = jnp.zeros((L, B, W, KD), dtype)
        rep = lambda a: jnp.repeat(a, Bk)            # a row's, a position
        temp_n, top_p_n, top_k_n = rep(temperature), rep(top_p), rep(top_k)
        base, extra = Bk // steps, Bk % steps
        zero_route = jnp.zeros((6,), jnp.float32)

        def one_block(carry, b):
            wk, wv = carry
            start = past_len + b * Bk
            window_past = (wk, wv, b * Bk)

            def forward(x):
                return self._block_forward(
                    params, cache, x, start, page_table, past_len,
                    window_past,
                )

            def denoise(c):
                x, lp, t, routes = c
                with jax.named_scope("bd_denoise"):
                    logits, (k, _) = forward(x)
                x0, conf, logp = sample_with_confidence(
                    logits.reshape(B * Bk, -1),
                    jax.random.fold_in(jax.random.fold_in(rng, b), t),
                    temperature=temp_n, top_p=top_p_n, top_k=top_k_n,
                    exclude=MASK,
                )
                x, taken = transfer(
                    x, x0.reshape(B, Bk), conf.reshape(B, Bk),
                    base + (t < extra).astype(jnp.int32), rule, tau, MASK,
                )
                lp = jnp.where(taken, logp.reshape(B, Bk), lp)
                route = self._route_stats(k)
                if route is not None:
                    routes = routes.at[t].set(route)
                return x, lp, t + 1, routes

            x = jnp.where(
                b == 0, first,
                jnp.where(live[:, None], jnp.int32(MASK), jnp.int32(0)),
            )
            x, lp, turns, routes = jax.lax.while_loop(
                lambda c: jnp.any(c[0] == MASK) & (c[2] < Bk),
                denoise,
                (x, jnp.zeros((B, Bk), jnp.float32), jnp.int32(0),
                 jnp.zeros((Bk + 1, 6), jnp.float32)),
            )
            with jax.named_scope("bd_commit"):
                _, (k, v) = forward(x)
            route = self._route_stats(k)
            routes = routes.at[Bk].set(
                zero_route if route is None else route
            )
            if isinstance(k, MixedChunk):
                k = k.k
            with part("cache"):
                wk = jax.lax.dynamic_update_slice(
                    wk, k.astype(dtype).reshape(L, B, Bk, KD),
                    (0, 0, b * Bk, 0),
                )
                wv = jax.lax.dynamic_update_slice(
                    wv, v.astype(dtype).reshape(L, B, Bk, KD),
                    (0, 0, b * Bk, 0),
                )
            return (wk, wv), (x, lp, turns, routes)

        (wk, wv), (xs, lps, turns, routes) = jax.lax.scan(
            one_block, (wk0, wv0), jnp.arange(blocks, dtype=jnp.int32)
        )
        cache = write_kv(
            cache, wk, wv, page_table, past_len,
            jnp.full((B,), W, jnp.int32),
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )
        # [blocks, B, Bk] -> [blocks * Bk, B]: position order
        toks = xs.transpose(0, 2, 1).reshape(W, B)
        logps = lps.transpose(0, 2, 1).reshape(W, B)
        return toks, logps, turns, cache, routes.reshape(-1, 6)

    def decode_block_async(
        self,
        first: np.ndarray,           # [B, Bk] int32
        live: np.ndarray,            # [B] bool
        past_len: np.ndarray,        # [B] int32, multiples of Bk
        page_table: np.ndarray,      # [B, MP] int32
        rng: jax.Array,
        temperature: np.ndarray,     # [B]
        top_p: np.ndarray,           # [B]
        blocks: int,
        top_k: Optional[np.ndarray] = None,
        steps: Optional[np.ndarray] = None,   # [B] int32 in 1..Bk
        rule: Optional[np.ndarray] = None,    # [B] int32 (sampling.STATIC..)
        tau: Optional[np.ndarray] = None,     # [B] float32
    ):
        """One window of ``blocks`` blocks for the batch
        (``_decode_block_jit``), dispatched without a wait:
        ``(tokens [blocks * Bk, B], logprobs, denoising forwards a
        block [blocks])`` on the device; the routing counts wait in
        ``window_route`` as a fused window's do. A window needs no
        token of the window before it (a later block starts as masks),
        so windows chain with nothing handed over."""
        if faults.ACTIVE is not None:
            faults.inject("runner.decode")
        from ..models.configs import REMASKING

        m = self.mcfg
        B, Bk = first.shape
        if top_k is None:
            top_k = np.zeros((B,), np.int32)
        if steps is None:
            steps = np.full((B,), m.denoising_steps or Bk, np.int32)
        if rule is None:
            rule = np.full((B,), REMASKING.index(m.remasking), np.int32)
        if tau is None:
            tau = np.full((B,), m.confidence_threshold, np.float32)
        self.count_sample(temperature)
        # every forward of a block reads the row's pages once
        self._count_kv_pages(
            past_len, page_table, blocks * (int(np.max(steps)) + 1), None
        )
        toks, logps, turns, self.cache, self.window_route = (
            self._decode_block_jit(
                self.params,
                self.cache,
                jnp.asarray(first, jnp.int32),
                jnp.asarray(live, bool),
                jnp.asarray(past_len, jnp.int32),
                jnp.asarray(page_table, jnp.int32),
                rng,
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(top_p, jnp.float32),
                jnp.asarray(top_k, jnp.int32),
                jnp.asarray(np.clip(steps, 1, Bk), jnp.int32),
                jnp.asarray(rule, jnp.int32),
                jnp.asarray(tau, jnp.float32),
                blocks,
            )
        )
        return toks, logps, turns

    @functools.partial(jax.jit, static_argnums=(0,))
    def _decode_block_denoise_jit(self, params, cache, x, start, page_table):
        """A denoising forward alone: the block's logits, no K/V kept."""
        with jax.named_scope("bd_denoise"):
            return self._block_forward(params, cache, x, start, page_table)[0]

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _decode_block_commit_jit(self, params, cache, x, start, page_table):
        """A commit forward alone: the block's K/V written, no logits."""
        with jax.named_scope("bd_commit"):
            _, (k, v) = self._block_forward(
                params, cache, x, start, page_table
            )
        return write_kv(
            cache, k, v, page_table, start,
            jnp.full((x.shape[0],), x.shape[1], jnp.int32),
            use_pallas=self.use_pallas, kernel_mesh=self.kernel_mesh,
        )

    def _forced_logits(self, seq, n_prefill: int, n_decode: int):
        """The benchmark's forced forward (perfbench/reference/README.md):
        float32 ``[1 + n_decode, V]``, the logits at position
        ``n_prefill - 1`` from this runner's own prefill program over
        ``seq[:n_prefill]``, then for each following block the
        DENOISING program over the given tokens (its logits at the
        block's positions; ids that are ``mask_token_id`` are a block as
        the timed path feeds it) and the COMMIT program (its K/V
        written). Whole blocks only. Bound as ``forced_logits`` on a
        block model's runner alone (``__init__``)."""
        Bk = self.mcfg.block_length
        seq = np.asarray(seq, np.int32)
        if n_prefill % Bk or n_decode % Bk or n_prefill < Bk or (
            len(seq) < n_prefill + n_decode
        ):
            raise ValueError(
                f"forced_logits: n_prefill {n_prefill} and n_decode "
                f"{n_decode} must be whole blocks of {Bk} (and at least one "
                f"prefilled) within the {len(seq)} tokens given"
            )
        PS = self.ecfg.kv_page_size
        table = np.zeros((self.ecfg.max_pages_per_seq,), np.int32)
        n_pages = -(-(n_prefill + n_decode) // PS)
        table[:n_pages] = np.arange(1, n_pages + 1)
        out = [np.asarray(self.prefill(seq[:n_prefill], table), np.float32)]
        table_dev = jnp.asarray(table[None], jnp.int32)
        for at in range(n_prefill, n_prefill + n_decode, Bk):
            x = jnp.asarray(seq[None, at : at + Bk])
            start = jnp.asarray([at], jnp.int32)
            logits = self._decode_block_denoise_jit(
                self.params, self.cache, x, start, table_dev
            )
            out.extend(np.asarray(logits[0], np.float32))
            # the block is committed as GIVEN: a mask among the ids is
            # scored above and its place filled by the caller's next call
            self.cache = self._decode_block_commit_jit(
                self.params, self.cache, x, start, table_dev
            )
        return np.stack(out)

    # ------------------------------------------------------------------
    # masked-candidate verification (FSM fast-forward)
    # ------------------------------------------------------------------

    def _verify_forward(
        self, params, cache: KVCache, ids, valid_len, page_table, start
    ):
        """The verify trunk: one forward over [B, C] known tokens
        against the paged past, K/V written for the inputs, plus the
        plain greedy choice per position. A block model's COMMIT forward
        (``_block_forward`` with every position filled) shares its shape,
        several known tokens a row over the paged past whose K/V is kept,
        and nothing else: under the block mask it takes the paged
        kernel's block form where this chunk gathers, it keeps its K/V
        in the window's buffers for one write a window where this writes
        at once, and it reads no logits."""
        C = ids.shape[1]
        positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        logits, _, (k, v) = transformer.forward(
            self.mcfg, params, ids, positions, valid_len,
            paged_past=self._paged(cache, page_table),
            past_len=start,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
            ep_mesh=self.ep_mesh,
            conv_state=self._state_at(cache, page_table, start),
            state_past=self._state_past(cache, page_table, start),
            ssm_pending=True,
            window_pool=self._window_pool_of(cache, page_table),
        )
        # K/V of every input is written (rejected positions are dead
        # stores past the accepted length); conv state is ONE value a
        # page, so it waits for the accepted length: the chunk's gated
        # inputs go back to the caller (``commit_verified``)
        pending = None
        if isinstance(k, MixedChunk) and (
            k.conv is not None or k.ssm is not None
        ):
            pending = MixedChunk(k=None, conv=k.conv, ssm=k.ssm)
            k = dataclasses.replace(k, conv=None, ssm=None)
        cache = write_kv(
            cache, k, v, page_table, start, valid_len,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )
        with part("sample"):
            lg = logits.astype(jnp.float32)                   # [B, C, V]
            plain = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            plain_lp = jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), plain[..., None], axis=-1
            )[..., 0]
        return lg, plain, plain_lp, cache, pending

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _verify_cand_jit(
        self, params, cache: KVCache, ids, valid_len, page_table, start,
        cand, cand_n,
    ):
        """Masked-candidate verification (FSM fast-forward over BPE-style
        vocabs): position (b, j)'s choice is the argmax over its SMALL
        candidate id list — exactly the masked-path token, without
        shipping [B, C, V] masks (the candidate operand is [B, C, M]
        ids, ~KBs). Also returns the plain greedy tokens so rows
        without a plan ride the dispatch as ordinary greedy steps.
        Candidate logprobs are w.r.t. the FULL-vocab softmax — the same
        distribution ``cumulative_logprob`` reports on the masked
        single-step path (which samples under the mask but reports
        unmasked logprobs), so a row's cumulative_logprob no longer
        depends on which path committed each token."""
        lg, plain, plain_lp, cache, pending = self._verify_forward(
            params, cache, ids, valid_len, page_table, start
        )
        with part("sample"):
            g = jnp.take_along_axis(lg, cand, axis=2)         # [B, C, M]
            M = cand.shape[2]
            ok = (
                jnp.arange(M, dtype=jnp.int32)[None, None, :]
                < cand_n[..., None]
            )
            g = jnp.where(ok, g, NEG_INF)
            idx = jnp.argmax(g, axis=-1)                      # [B, C]
            ctok = jnp.take_along_axis(
                cand, idx[..., None], axis=2
            )[..., 0].astype(jnp.int32)
            lse_v = jax.scipy.special.logsumexp(lg, axis=-1)  # [B, C]
            clp = (
                jnp.take_along_axis(lg, ctok[..., None], axis=-1)[..., 0]
                - lse_v
            )
        return ctok, clp, plain, plain_lp, cache, pending

    def verify_candidates(
        self,
        last_tokens: np.ndarray,   # [B] int32
        drafts: np.ndarray,        # [B, K] int32 (pad anything)
        draft_len: np.ndarray,     # [B] int32
        cand: np.ndarray,          # [B, K+1, M] int32 (pad id 0)
        cand_n: np.ndarray,        # [B, K+1] int32 — 0 = unplanned pos
        past_len: np.ndarray,      # [B] int32
        page_table: np.ndarray,    # [B, MP] int32
    ):
        """Returns (cand_toks, cand_logps, plain_toks, plain_logps),
        each [B, K+1]. Input row b is ``[last, d0..d_{L-1}]`` with
        valid_len L+1 (K/V written for inputs; an accepted output
        token's K/V is written by the next dispatch that consumes it)."""
        B, K = drafts.shape
        ids = np.zeros((B, K + 1), np.int32)
        ids[:, 0] = last_tokens
        ids[:, 1:] = drafts
        self.pools.bind_written(
            page_table, past_len, np.asarray(draft_len) + 1
        )
        self._count_latent("absorbed", past_len, K + 1)
        ct, cl, pt, pl, self.cache, pending = self._verify_cand_jit(
            self.params,
            self.cache,
            jnp.asarray(ids),
            jnp.asarray(draft_len + 1, jnp.int32),
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(past_len, jnp.int32),
            jnp.asarray(cand, jnp.int32),
            jnp.asarray(cand_n, jnp.int32),
        )
        self._hold_verified(pending, page_table, past_len)
        return (
            np.asarray(ct), np.asarray(cl),
            np.asarray(pt), np.asarray(pl),
        )

    def _hold_verified(self, pending, page_table, past_len) -> None:
        """Keep a verify dispatch's conv inputs until the caller has
        decided each row's accepted length."""
        self._verified = None if pending is None else (
            pending,
            np.array(page_table, np.int32, copy=True),
            np.array(past_len, np.int32, copy=True),
        )

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _commit_state_jit(self, cache: KVCache, held, page_table, start, n):
        return write_kv(
            cache, held, None, page_table, start, n,
            use_pallas=self.use_pallas, kernel_mesh=self.kernel_mesh,
        )

    def commit_verified(self, accepted: np.ndarray) -> None:
        """After ``verify_candidates`` on a model that keeps conv state
        (``has_state``): commit each row's state after its first ``accepted[b]`` INPUT tokens (0: the row keeps
        the state it had). One gather and scatter, no second forward. A
        model without such state needs no call, and a call is a no-op."""
        held, self._verified = getattr(self, "_verified", None), None
        if held is None:
            return
        chunk, page_table, past_len = held
        self._count_state_commit("verify", len(accepted))
        self.cache = self._commit_state_jit(
            self.cache, chunk,
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(past_len, jnp.int32),
            jnp.asarray(accepted, jnp.int32),
        )

    @functools.partial(jax.jit, static_argnums=(0,))
    def _merge_last_jit(self, prev_last, refresh_mask, refresh_vals):
        """Device-side merge for pipelined windows: rows whose slot was
        re-admitted between dispatches take their host-known first token;
        everyone else chains the previous window's last sampled token.
        No host sync — all inputs are uploads or device arrays."""
        return jnp.where(refresh_mask, refresh_vals, prev_last)

    def merge_last(self, prev_last, refresh_mask, refresh_vals):
        return self._merge_last_jit(
            prev_last,
            jnp.asarray(refresh_mask, bool),
            jnp.asarray(refresh_vals, jnp.int32),
        )

    def resident(self, x) -> jax.Array:
        """``x`` where this runner's programs leave their results: an
        argument that is sometimes an upload and sometimes a program's
        result has ONE signature once it went through here (a jitted
        function is lowered again for an uncommitted array where it had
        a committed one, and the other way round). Under a mesh results
        are committed, so ``x`` is put whole on every device; on one
        device nothing is (the pool, the weights, every result), and an
        array committed here would commit the pool through the first
        window that took it: a second lowering of every program that
        takes the pool. A no-op for an array that already lies so."""
        if self.mesh is None:
            return jnp.asarray(x)
        sharding = getattr(self, "_resident_sharding", None)
        if sharding is None:
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = self._resident_sharding = NamedSharding(
                self.mesh, PartitionSpec()
            )
        return jax.device_put(x, sharding)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _merge_first_jit(
        self, prev_last, refresh_mask, refresh_vals, first_mask, first
    ):
        """``_merge_last_jit`` for a window that goes out before the
        admission wave in front of it is resolved: a row of the wave
        takes its first token from ``first`` ([B] by slot, where
        admission's sample wrote it), which the host has not seen."""
        return jnp.where(
            first_mask, first,
            jnp.where(refresh_mask, refresh_vals, prev_last),
        )

    def merge_first(
        self, prev_last, refresh_mask, refresh_vals, first_mask, first
    ):
        """The last tokens of a window some of whose rows' first tokens
        are still on the device. One program whatever the wave's rows
        and dispatches, and whether ``prev_last`` is the window before's
        sample row or (nothing in flight) the host's own."""
        return self._merge_first_jit(
            self.resident(jnp.asarray(prev_last, jnp.int32)),
            jnp.asarray(refresh_mask, bool),
            jnp.asarray(refresh_vals, jnp.int32),
            jnp.asarray(first_mask, bool),
            self.resident(first),
        )

    # ------------------------------------------------------------------
    # speculative window decode (constrained rows)
    # ------------------------------------------------------------------

    @functools.partial(jax.jit, static_argnums=(0, 8))
    def _decode_window_jit(
        self, params, cache: KVCache, last, past_len, page_table,
        rng, temperature, steps: int, top_p, top_k,
        allowed0=None, pfx=None,
    ):
        """Like ``_decode_multi_jit`` but WITHOUT the page commit: the
        sampled window and its K/V buffers return to the host, which
        verifies constrained rows against their FSMs and commits only
        each row's accepted prefix (``commit_window``). The cache is a
        read-only input here, so a rejected suffix costs nothing.
        ``allowed0`` arrives bit-packed, like the masked step's masks."""
        if allowed0 is not None:
            allowed0 = unpack_mask(allowed0, self.mcfg.vocab_size)
        return self._window_scan(
            params, cache, last, past_len, page_table, rng,
            temperature, top_p, steps, top_k,
            allowed0=allowed0, pfx=pfx,
        )

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _commit_window_jit(
        self, cache: KVCache, wk, wv, page_table, past_len, accepted
    ):
        return write_kv(
            cache, wk, wv, page_table, past_len, accepted,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )

    def decode_window(
        self,
        last_tokens: np.ndarray,     # [B] int32
        past_len: np.ndarray,        # [B] int32
        page_table: np.ndarray,      # [B, MP] int32
        rng: jax.Array,
        temperature: np.ndarray,     # [B]
        top_p: np.ndarray,           # [B]
        steps: int,
        top_k: Optional[np.ndarray] = None,
        allowed0: Optional[np.ndarray] = None,  # [B, ceil(V/8)] uint8,
        #                                         bit-packed; step 0 only
        pfx=None,  # tuple of (pages [Pp_g], pfx_len [B]) split-prefix groups
    ):
        """Speculative window: returns (tokens [steps, B], logprobs
        [steps, B], window_kv handle). Pages are NOT written — call
        ``commit_window(handle, accepted)`` with per-row accepted token
        counts. ``allowed0`` FSM-masks the first step for rows whose
        previous window rejected a token (scheduler per-row recovery)."""
        if faults.ACTIVE is not None:
            faults.inject("runner.decode")
        B = len(last_tokens)
        if top_k is None:
            top_k = np.zeros((B,), np.int32)
        self._count_kv_pages(past_len, page_table, steps, pfx)
        self._count_latent("absorbed", past_len, steps)
        self.count_sample(temperature)
        toks, logps, wk, wv = self._decode_window_jit(
            self.params,
            self.cache,
            jnp.asarray(last_tokens, jnp.int32),
            jnp.asarray(past_len, jnp.int32),
            jnp.asarray(page_table, jnp.int32),
            rng,
            jnp.asarray(temperature, jnp.float32),
            steps,
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
            None if allowed0 is None else jnp.asarray(allowed0, jnp.uint8),
            self._pfx_jnp(pfx),
        )
        # copy: callers may pass live views (native runtime) that mutate
        # during host-side verification before commit_window
        handle = (
            wk, wv,
            np.array(past_len, np.int32, copy=True),
            np.array(page_table, np.int32, copy=True),
        )
        self._route_dev = wk.route if isinstance(wk, MixedChunk) else None
        return np.asarray(toks), np.asarray(logps), handle

    def commit_window(self, handle, accepted: np.ndarray) -> None:
        """Write each row's accepted window prefix into the page pool."""
        wk, wv, past_len, page_table = handle
        self._count_state_commit(
            "window", len(past_len), int(jax.tree.leaves(wk)[0].shape[2])
        )
        self.pools.bind_written(page_table, past_len, accepted)
        self.cache = self._commit_window_jit(
            self.cache, wk, wv,
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(past_len, jnp.int32),
            jnp.asarray(accepted, jnp.int32),
        )

    # ------------------------------------------------------------------
    # embeddings
    # ------------------------------------------------------------------

    @functools.partial(jax.jit, static_argnums=(0,))
    def _embed_jit(self, params, ids, valid_len):
        B, T = ids.shape
        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (B, T)
        )
        emb, _, _ = transformer.forward(
            self.mcfg, params, ids, positions, valid_len,
            use_pallas=self.use_pallas,
            kernel_mesh=self.kernel_mesh,
        )
        return emb

    def embed_batch(self, rows: list) -> np.ndarray:
        """List of token-id arrays -> [N, H] float32 embeddings."""
        if faults.ACTIVE is not None:
            faults.inject("runner.embed")
        n = len(rows)
        maxlen = max((len(r) for r in rows), default=1)
        T = next_bucket(max(maxlen, 1), lo=16, hi=self.ecfg.max_context())
        ids = np.zeros((n, T), np.int32)
        lens = np.zeros((n,), np.int32)
        for i, r in enumerate(rows):
            ids[i, : len(r)] = r
            lens[i] = len(r)
        emb = self._embed_jit(
            self.params, jnp.asarray(ids), jnp.asarray(lens)
        )
        return np.asarray(emb, np.float32)
