"""Engine configuration.

No analogue exists in the reference (its engine is the remote service,
SURVEY §0); this is the "config file + kwargs override" layer SURVEY §5.6
prescribes for the TPU build: mesh shape, dtype policy, KV paging, and
batching budgets, resolved from defaults <- ~/.sutro/engine.json <- kwargs.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from pathlib import Path
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass
class EngineConfig:
    # --- device mesh -------------------------------------------------------
    # Axis sizes; 0 => infer from available devices (tp gets devices not
    # claimed by ep, remainder folds into dp). Defaults are explicit
    # single-device: TP/EP need model-divisibility knowledge, so spreading
    # over all chips is an explicit choice (engine.json or kwargs), not a
    # surprise. Axes: ("data", "seq", "expert", "model") — DP over
    # DCN/outer, SP/EP/TP over ICI (SURVEY §5.8). ``sp`` > 1 enables
    # ring-attention sequence parallelism for long-prompt prefill
    # (ops/ring_attention.py).
    dp: int = 1
    tp: int = 1
    ep: int = 1
    sp: int = 1
    pp: int = 1                     # pipeline stages (parallel/pipeline.py)
    pp_microbatches: int = 0        # 0 => min(pp, batch)
    # --- dtype policy ------------------------------------------------------
    activation_dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    quantize: Optional[str] = None  # "int8" => weight-only per-channel
                                    # quantization of the projection
                                    # matrices (ops/quant.py)
    kv_quantize: Optional[str] = None  # "int8" => KV cache pages stored
                                    # int8 with per-token scales: halves
                                    # decode HBM traffic and doubles
                                    # page capacity (kvcache.write_kv
                                    # quantizes, the paged kernel /
                                    # gather fallback dequantize).
                                    # Works under dp/tp/sp/ep meshes
                                    # (scales are full-KD amax, hence
                                    # shard-invariant and replicated);
                                    # pp only warns+ignores (pipeline
                                    # decode carries no scale pools)
    # --- KV cache / batching ----------------------------------------------
    kv_page_size: int = 64          # tokens per KV page
    max_pages_per_seq: int = 128    # => max context 8192 by default
    decode_batch_size: int = 64     # fixed decode slot count (static shapes)
    prefill_chunk: int = 512        # prompts longer than this prefill in
                                    # fixed-size chunks (runner.prefill)
    prefill_batch_size: int = 8     # short rows prefilled per device
                                    # dispatch (runner.prefill_batch)
    interactive_slots: int = 0      # reserved-slot budget for the online
                                    # serving tier (serving/gateway.py):
                                    # up to this many decode slots may be
                                    # taken by interactive /v1 requests,
                                    # preempting batch rows when the
                                    # batch is full (the preempted row
                                    # re-admits row-granularly). 0 = the
                                    # serving endpoints 404 and the batch
                                    # path is bit-identical to before
    max_batch_tokens: int = 32768   # admission budget: sum of in-flight
                                    # worst-case totals (scheduler._reserve)
    max_model_len: int = 8192
    decode_multi_step: int = 8      # decode steps fused into one device
                                    # program when no row needs host-side
                                    # FSM masks/seeds
                                    # (runner.decode_multi_async);
                                    # amortizes dispatch+fetch latency.
                                    # The scheduler pays min-cap
                                    # all-or-nothing tails that grow
                                    # with this value. Every cell of
                                    # BENCHMARK.json runs 8; no other
                                    # value has a chip number
    decode_lookahead: int = 2       # fused windows in flight at once on the
                                    # unconstrained decode path: window k+1
                                    # chains off window k's device-resident
                                    # tokens, so the device runs it while
                                    # the host accepts window k (the host
                                    # is 2-4 % of a generate window on a
                                    # host that holds the chip: PERF.md
                                    # §5); 1 = the same path at a depth of
                                    # one (dispatch, fetch and accept in
                                    # one iteration). An admission wave is
                                    # resolved BEHIND the window it feeds
                                    # (scheduler._hold_wave), so the depth
                                    # holds while rows turn over: the wait
                                    # for the wave's prefills has that
                                    # window queued behind them
    constrain_fastforward: int = 16  # FSM fast-forward ("jump
                                    # decoding") width: when a schema's
                                    # FSM forces exactly one next token
                                    # (scaffold regions like
                                    # '{"field": "'), peel up to this
                                    # many forced tokens host-side and
                                    # commit them through ONE parallel
                                    # verify forward instead of
                                    # step-by-step windows that reject
                                    # their unmasked samples there.
                                    # Exact for greedy constrained rows
                                    # (forced tokens are
                                    # model-independent; the bonus
                                    # token follows the speculative
                                    # window's accept rule). 0 = off
    prefill_piggyback: bool = True  # Sarathi-style chunked-prefill
                                    # interleave: a long prompt admits as
                                    # a PREFILLING slot that advances one
                                    # prefill chunk per scheduler
                                    # iteration while the active rows
                                    # keep decoding — instead of the
                                    # whole batch stalling for the full
                                    # multi-chunk prefill
    prefix_split: bool = False      # Hydragen-style split decode over
                                    # the shared prefix (Pallas path
                                    # only): member rows' prefix
                                    # attention is computed ONCE per
                                    # step for the whole batch (one HBM
                                    # read of the shared pages per
                                    # layer instead of one per row) and
                                    # injected as the paged kernel's
                                    # initial online-softmax carry
                                    # (ops/pallas_paged.py). Same f32
                                    # math, different summation order —
                                    # last-ulp differences only.
                                    # Default off: no cell turns it
                                    # on and it has no chip number
                                    # (ROADMAP.md 2.10 names the cell
                                    # that decides it)
    prefix_cache: bool = True       # shared-prefix KV reuse: a job whose
                                    # rows share a common token prefix
                                    # (templates send one system prompt
                                    # for every row) prefills that prefix
                                    # ONCE into page-aligned shared pages;
                                    # slots reference them read-only and
                                    # prefill only their own suffix
                                    # (scheduler._setup_prefix)
    prefix_store: bool = True       # engine-lifetime radix prefix store
                                    # (engine/prefixstore.py): page-
                                    # aligned template shells stay
                                    # resident in the paged KV pool
                                    # ACROSS jobs, co-batched jobs,
                                    # resumes, and interactive requests
                                    # — a repeated shell prefills only
                                    # its novel tail. Refcount-pinned
                                    # pages, LRU eviction under
                                    # allocation pressure.
                                    # $SUTRO_PREFIX_STORE overrides when
                                    # set ("0"/"off" forces off); off =
                                    # bit-identical to the per-job
                                    # prefix_cache path
    kv_tiers: bool = False          # tiered paged-KV pool (engine/
                                    # kvtier.py): HBM -> pinned host RAM
                                    # -> disk. Cold unpinned prefix-store
                                    # leaves DEMOTE to an int8 host tier
                                    # instead of evicting; preemption
                                    # victims hibernate their pages and
                                    # resume by page-upload instead of
                                    # full re-prefill; session-id chat
                                    # checkpoints idle conversations down
                                    # the tiers. $SUTRO_KV_TIERS
                                    # overrides when set ("0"/"off"
                                    # forces off); off = bit-identical,
                                    # ZERO tier ops (tests/test_kv_tiers)
    kv_tier_host_pages: int = 4096  # host-tier budget in KV pages
                                    # (int8: ~page_size*KD bytes/page/
                                    # layer); overflow spills to disk
    kv_tier_disk: bool = True       # disk tier under sutro_home()/
                                    # kvtier (jobstore partial-store
                                    # idiom: atomic rename, torn bundles
                                    # quarantined); off = host-only
    tokenize_threads: int = 0       # >1 splits batched prompt encodes
                                    # across a thread pool — only pays
                                    # for tokenizers whose encode_batch
                                    # releases the GIL (HF rust); the
                                    # byte tokenizer ignores extra
                                    # threads profitably at 0
    # --- generation defaults ----------------------------------------------
    max_new_tokens: int = 1024
    temperature: float = 0.7
    top_p: float = 0.95
    top_k: int = 0                  # 0 = disabled
    # --- robustness / failure domains (engine/faults.py, FAILURES.md) ------
    fault_plan: Optional[str] = None    # deterministic fault-injection
                                        # plan (DSL/JSON); None falls back
                                        # to $SUTRO_FAULT_PLAN; empty/off
                                        # means ZERO added work per row
    control: Optional[str] = None       # SLO enforcement control plane
                                        # (engine/control.py): "1"/"on"
                                        # for defaults, or "k=v,..."
                                        # (window=60,wait=2,aging=30,...).
                                        # $SUTRO_CONTROL overrides when
                                        # set ("0"/"off" forces off).
                                        # None/off = ZERO added work and
                                        # bit-identical batch results
    row_retries: int = 2                # per-row failure domain: a row
                                        # whose decode/constrain raises is
                                        # re-admitted as a fresh request up
                                        # to this many times, then
                                        # quarantined into an error-column
                                        # result (the job still SUCCEEDs)
    io_retries: int = 4                 # bounded attempts for transient
                                        # jobstore I/O (partial flush,
                                        # streamed finalize)
    io_backoff_base: float = 0.05       # first-retry backoff (seconds);
                                        # doubles per attempt with
                                        # deterministic jitter, capped at
                                        # io_backoff_cap
    io_backoff_cap: float = 2.0
    dp_stall_timeout: float = 600.0     # dp coordinator: seconds of
                                        # silence from a connected rank
                                        # before it is declared stalled
                                        # (0 disables the watchdog).
                                        # $SUTRO_DP_STALL_TIMEOUT
                                        # overrides when set; must be
                                        # >= 0 (engine/dphost.py
                                        # configure_channel)
    dp_heartbeat: float = 20.0          # dp worker liveness beacon
                                        # period in seconds (0 disables;
                                        # $SUTRO_DP_HEARTBEAT overrides;
                                        # must be >= 0)
    # --- runtime -----------------------------------------------------------
    use_pallas: Optional[bool] = None   # None => auto (TPU yes, CPU no)
    weights_dir: Optional[str] = None   # local HF-style checkpoint root
    seed: int = 0
    profile_dir: Optional[str] = None   # capture per-job jax.profiler
                                        # traces here (engine/profiling.py)

    def resolved_mesh(
        self, n_devices: int
    ) -> Tuple[int, int, int, int, int]:
        """Resolve (dp, pp, sp, ep, tp) against the actual device count:
        tp gets what's specified (default: all devices not claimed by
        ep/sp/pp), remaining devices fold into dp."""
        pp = self.pp or 1
        sp = self.sp or 1
        ep = self.ep or 1
        tp = self.tp or max(1, n_devices // (ep * sp * pp))
        dp = self.dp or max(1, n_devices // (tp * ep * sp * pp))
        if dp * pp * sp * ep * tp > n_devices:
            raise ValueError(
                f"Mesh dp*pp*sp*ep*tp={dp * pp * sp * ep * tp} exceeds "
                f"{n_devices} devices"
            )
        return dp, pp, sp, ep, tp

    def mesh_devices(self, n_devices: int) -> int:
        """Devices one runner's mesh spans on a host with ``n_devices``
        (1 = no mesh). THE per-chip divisor: a one-chip engine on a
        four-chip host computes on one chip, so dividing its rate by
        ``jax.device_count()`` would report a quarter of it."""
        return math.prod(self.resolved_mesh(n_devices))

    def max_context(self) -> int:
        return min(self.max_model_len, self.kv_page_size * self.max_pages_per_seq)


def sutro_home() -> Path:
    """THE resolution rule for the sutro state directory (one
    definition: load_engine_config, validation.py, and the compile
    cache must never disagree on where sutro-home is)."""
    return Path(os.environ.get("SUTRO_HOME", Path.home() / ".sutro"))


_CACHE_ENABLED = False

#: the persistent compile cache when nothing outside placed it: one
#: fixed, git-ignored directory inside the checkout. The path is part
#: of the cache key, so it must not depend on SUTRO_HOME, a pid, the
#: time or a temp name — a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xla_cache"

_CACHE_DIR_OPTION = "jax_compilation_cache_dir"


def enable_compile_cache() -> Optional[str]:
    """Resolve JAX's persistent compilation cache once per process and
    return its directory (None = caching off). Idempotent.

    Every engine process — the HTTP daemon, bench subprocesses, DP
    workers — compiles the same decode/prefill programs (qwen3-4b on a
    v5e: 106 s of compiling cold, 15 s from the cache — PR 21); the
    on-disk cache (content-addressed, a stock JAX feature) lets every
    process after the first load the executables instead.

    One rule, this one site: where ``JAX_COMPILATION_CACHE_DIR`` is set
    (JAX binds it at import) the program sets no directory in code;
    otherwise it uses ``DEFAULT_COMPILE_CACHE_DIR``. Opt out with
    ``SUTRO_COMPILE_CACHE=0`` (tests/conftest.py does, and points the
    suite at a session-private directory itself)."""
    global _CACHE_ENABLED
    import jax

    opt = os.environ.get("SUTRO_COMPILE_CACHE")
    if opt != "0" and not _CACHE_ENABLED:
        _CACHE_ENABLED = True
        # XLA:CPU AOT cache entries embed the compiling host's machine
        # features, and feature detection can differ between processes
        # on the same box (observed on the build host, again in PR 21:
        # '+prefer-no-scatter ... could lead to execution errors such
        # as SIGILL' on cross-process loads). CPU caching is therefore
        # explicit opt-in (SUTRO_COMPILE_CACHE=1); TPU executables
        # target the accelerator and don't carry host-CPU features.
        wanted = opt == "1" or jax.default_backend() != "cpu"
        if wanted and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            DEFAULT_COMPILE_CACHE_DIR.mkdir(parents=True, exist_ok=True)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 2.0
            )
            jax.config.update(
                _CACHE_DIR_OPTION, str(DEFAULT_COMPILE_CACHE_DIR)
            )
    return getattr(jax.config, _CACHE_DIR_OPTION) or None


def load_engine_config(**overrides: Any) -> EngineConfig:
    """defaults <- $SUTRO_HOME/engine.json <- explicit kwargs."""
    cfg: Dict[str, Any] = {}
    path = sutro_home() / "engine.json"
    if path.exists():
        try:
            cfg.update(json.loads(path.read_text()))
        except Exception:
            pass
    cfg.update({k: v for k, v in overrides.items() if v is not None})
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    return EngineConfig(**{k: v for k, v in cfg.items() if k in fields})
