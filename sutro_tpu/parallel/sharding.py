"""Sharding rules: parameter/cache/data placement over the mesh.

Megatron-style tensor parallelism expressed as ``NamedSharding`` per pytree
leaf — XLA inserts the collectives (SURVEY §5.8: "pick a mesh, annotate
shardings, let XLA insert collectives"):

- attention: head dimension of wq/wk/wv sharded over ``model``; wo sharded
  on its input (head) dimension → one all-reduce per attention block;
- MLP: w_gate/w_up sharded on the FFN dim, w_down on its input → one
  all-reduce per MLP block;
- MoE: the *expert* axis of we_* shards over ``expert`` and the FFN dim
  over ``model`` (EP×TP); router replicated;
- embed replicated (token gather is cheap, avoids vocab-gather
  collectives on every prefill chunk); lm_head sharded over vocab so the
  logits matmul is parallel, with the all-gather deferred to sampling;
- KV cache pages shard the KV-head axis over ``model``, matching the
  attention-head sharding, so decode attention needs no KV collectives.

All rules are path-based over the params pytree from
models/transformer.init_params and engine/weights.load_checkpoint.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# leaf name (within params["layers"] or top level) -> PartitionSpec
_LAYER_RULES: Dict[str, P] = {
    "attn_norm": P(),
    "mlp_norm": P(),
    "post_attn_norm": P(),
    "post_mlp_norm": P(),
    "q_norm": P(),
    "k_norm": P(),
    "sink": P(None, "model"),            # [L, NH]
    "wq": P(None, None, "model"),        # [L, H, NHD]
    "wk": P(None, None, "model"),
    "wv": P(None, None, "model"),
    "bq": P(None, "model"),
    "bk": P(None, "model"),
    "bv": P(None, "model"),
    "wo": P(None, "model", None),        # [L, NHD, H]
    "bo": P(),
    "w_gate": P(None, None, "model"),    # [L, H, F]
    "w_up": P(None, None, "model"),
    "w_down": P(None, "model", None),    # [L, F, H]
    "router": P(),                       # [L, H, E]
    "router_b": P(),                     # [L, E]
    "we_gate": P(None, "expert", None, "model"),  # [L, E, H, F]
    "we_up": P(None, "expert", None, "model"),
    "we_down": P(None, "expert", "model", None),  # [L, E, F, H]
    "we_up_t": P(None, "expert", "model", None),  # [L, E, F, H]: two-matrix
    #                                               experts' first, output-major
    "shared_gate": P(None, None, "model"),        # [L, H, Fs]
    "shared_up": P(None, None, "model"),
    "shared_down": P(None, "model", None),        # [L, Fs, H]
    "we_gate_b": P(None, "expert", "model"),      # [L, E, F]
    "we_up_b": P(None, "expert", "model"),
    "we_down_b": P(None, "expert", None),         # [L, E, H]
}

_TOP_RULES: Dict[str, P] = {
    "embed": P(),                        # replicated (see module docstring)
    "final_norm": P(),
    "lm_head": P(None, "model"),         # [H, V] — vocab-parallel logits
}


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """Pytree of NamedSharding matching ``params`` structure.

    Quantized leaves (ops/quant.py: ``{"qw", "scale"}`` under the weight
    name) inherit the weight's rule; ``scale``'s collapsed reduction axis
    (size 1) drops its mesh axis so size-1 dims are never sharded."""

    def rule(path, leaf) -> NamedSharding:
        names = [p.key for p in path if hasattr(p, "key")]
        leaf_name = names[-1]
        if leaf_name in ("qw", "scale") and len(names) >= 2:
            leaf_name = names[-2]
        if "layers" in names:
            spec = _LAYER_RULES.get(leaf_name, P())
        else:
            spec = _TOP_RULES.get(leaf_name, P())
        if len(spec) > leaf.ndim:
            spec = P(*spec[: leaf.ndim])
        if any(d == 1 for d in leaf.shape) and len(spec):
            spec = P(
                *(
                    None if leaf.shape[i] == 1 else ax
                    for i, ax in enumerate(spec)
                )
            )
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(rule, params)


def check_tp_divides_kv_heads(mesh: Mesh, kv_heads: Optional[int]) -> None:
    """The fused KV-pool trailing axis is KV-head-major (kvcache.py), so
    sharding it over ``model`` splits whole KV heads across the TP axis —
    PROVIDED the model-axis size divides the KV head count. A mid-head
    split would silently corrupt per-shard attention."""
    tp = int(mesh.shape.get("model", 1))
    if kv_heads is not None and kv_heads % max(tp, 1):
        raise ValueError(
            f"TP axis size {tp} must divide num_kv_heads {kv_heads}: the "
            "fused KV-pool axis shards in whole-head blocks"
        )


def cache_shardings(mesh: Mesh, kv_heads: Optional[int] = None) -> NamedSharding:
    """[L, NP, PS, KVH*Dh]: fused trailing axis over ``model`` in
    whole-KV-head blocks (see check_tp_divides_kv_heads)."""
    check_tp_divides_kv_heads(mesh, kv_heads)
    return NamedSharding(mesh, P(None, None, None, "model"))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch rows shard over ``data`` (DP)."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_params(params: Any, mesh: Mesh) -> Any:
    """device_put the whole pytree with its rules (host -> sharded HBM)."""
    return jax.device_put(params, param_shardings(params, mesh))
