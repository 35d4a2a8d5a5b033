"""HF safetensors checkpoint -> engine parameter pytree.

The reference never touches weights (models live server-side; SURVEY §2.3).
Here local checkpoint dirs (``EngineConfig.weights_dir/<engine_key>/``)
holding standard HuggingFace safetensors shards are mapped into the
scan-stacked pytree layout of models/transformer.py:

- per-layer tensors are stacked on a leading layer axis,
- projection matrices are transposed to [in, out] (HF stores [out, in]) so
  the forward is plain ``x @ w`` on the MXU,
- dtype-cast to the engine param dtype (bfloat16 by default),
- shapes validated against the ModelConfig before any device transfer.

Loading is lazy per-tensor (safetensors mmap) so host RSS stays ~one
tensor; sharded device placement happens in the runner via NamedSharding.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..models.configs import ModelConfig
from .config import EngineConfig


class _ShardIndex:
    """name -> (file, loader) over one or many .safetensors shards."""

    def __init__(self, ckpt_dir: str):
        from safetensors import safe_open

        self._open = safe_open
        self.dir = ckpt_dir
        self.files: Dict[str, str] = {}
        index_path = os.path.join(ckpt_dir, "model.safetensors.index.json")
        if os.path.exists(index_path):
            index = json.loads(open(index_path).read())
            for name, fname in index["weight_map"].items():
                self.files[name] = os.path.join(ckpt_dir, fname)
        else:
            for fname in sorted(os.listdir(ckpt_dir)):
                if fname.endswith(".safetensors"):
                    path = os.path.join(ckpt_dir, fname)
                    with safe_open(path, framework="np") as f:
                        for name in f.keys():
                            self.files[name] = path
        self._handles: Dict[str, Any] = {}

    def __contains__(self, name: str) -> bool:
        return name in self.files

    def get(self, name: str) -> np.ndarray:
        path = self.files[name]
        if path not in self._handles:
            self._handles[path] = self._open(path, framework="np")
        return self._handles[path].get_tensor(name)

    def names(self) -> List[str]:
        return list(self.files)


def _first(idx: _ShardIndex, *names: str) -> Optional[str]:
    for n in names:
        if n in idx:
            return n
    return None


def load_checkpoint(
    ckpt_dir: str, mcfg: ModelConfig, ecfg: EngineConfig
) -> Dict[str, Any]:
    """Load + remap an HF checkpoint for any supported family."""
    idx = _ShardIndex(ckpt_dir)
    dtype = jnp.dtype(ecfg.param_dtype)
    L = mcfg.num_layers

    def resolve(name: str) -> str:
        """Embedding-model checkpoints saved from the bare trunk (e.g.
        Qwen3-Embedding's Qwen3Model) drop the ``model.`` prefix."""
        if name in idx:
            return name
        if name.startswith("model.") and name[6:] in idx:
            return name[6:]
        return name

    def get(name: str, transpose: bool = False) -> np.ndarray:
        arr = idx.get(resolve(name))
        if transpose:
            arr = np.ascontiguousarray(arr.T)
        return arr

    def stack(
        fmt: str | Callable[[int], str], transpose: bool = False
    ) -> jnp.ndarray:
        outs = []
        for i in range(L):
            name = fmt(i) if callable(fmt) else fmt.format(i=i)
            outs.append(get(name, transpose))
        return jnp.asarray(np.stack(outs), dtype)

    def maybe_stack(fmt: str, transpose: bool = False) -> Optional[jnp.ndarray]:
        if resolve(fmt.format(i=0)) in idx:
            return stack(fmt, transpose)
        return None

    if not mcfg.homogeneous:
        params = _load_mixed(mcfg, get, dtype)
        _validate_mixed(params, mcfg)
        return params

    p = "model.layers.{i}."
    layers: Dict[str, Any] = {
        "attn_norm": stack(p + "input_layernorm.weight"),
        "wq": stack(p + "self_attn.q_proj.weight", transpose=True),
        "wk": stack(p + "self_attn.k_proj.weight", transpose=True),
        "wv": stack(p + "self_attn.v_proj.weight", transpose=True),
        "wo": stack(p + "self_attn.o_proj.weight", transpose=True),
    }
    if mcfg.attn_bias:
        layers["bq"] = stack(p + "self_attn.q_proj.bias")
        layers["bk"] = stack(p + "self_attn.k_proj.bias")
        layers["bv"] = stack(p + "self_attn.v_proj.bias")
        layers["bo"] = stack(p + "self_attn.o_proj.bias")
    if mcfg.qk_norm:
        layers["q_norm"] = stack(p + "self_attn.q_norm.weight")
        layers["k_norm"] = stack(p + "self_attn.k_norm.weight")
    if mcfg.attention_sink:
        layers["sink"] = stack(p + "self_attn.sinks")

    if mcfg.post_norms:
        # Gemma3 norm quartet
        layers["post_attn_norm"] = stack(p + "post_attention_layernorm.weight")
        layers["mlp_norm"] = stack(p + "pre_feedforward_layernorm.weight")
        layers["post_mlp_norm"] = stack(p + "post_feedforward_layernorm.weight")
    else:
        layers["mlp_norm"] = stack(p + "post_attention_layernorm.weight")

    if mcfg.moe_experts:
        E = mcfg.moe_experts
        router = maybe_stack(p + "mlp.gate.weight", transpose=True)
        if router is None:
            router = maybe_stack(p + "mlp.router.weight", transpose=True)
        if router is None:
            raise KeyError("No MoE router weight found in checkpoint")
        layers["router"] = router

        def stack_experts(sub: str) -> jnp.ndarray:
            outs = []
            for i in range(L):
                per = []
                for e in range(E):
                    name = f"model.layers.{i}.mlp.experts.{e}.{sub}.weight"
                    per.append(np.ascontiguousarray(idx.get(name).T))
                outs.append(np.stack(per))
            return jnp.asarray(np.stack(outs), dtype)

        probe = f"model.layers.0.mlp.experts.0.gate_proj.weight"
        if probe in idx:
            layers["we_gate"] = stack_experts("gate_proj")
            layers["we_up"] = stack_experts("up_proj")
            layers["we_down"] = stack_experts("down_proj")
        else:
            # gpt-oss fused layout: experts.gate_up_proj [E, H, 2F] with
            # gate/up interleaved on the last axis (+ biases [E, 2F]),
            # experts.down_proj [E, F, H] (+ bias [E, H])
            gu, down = [], []
            for i in range(L):
                gu.append(idx.get(f"model.layers.{i}.mlp.experts.gate_up_proj"))
                down.append(idx.get(f"model.layers.{i}.mlp.experts.down_proj"))
            gu_arr = np.stack(gu)  # [L, E, H, 2F]
            layers["we_gate"] = jnp.asarray(gu_arr[..., 0::2], dtype)
            layers["we_up"] = jnp.asarray(gu_arr[..., 1::2], dtype)
            layers["we_down"] = jnp.asarray(np.stack(down), dtype)
        if mcfg.moe_bias:
            rb = maybe_stack(p + "mlp.router.bias")
            if rb is None:
                rb = maybe_stack(p + "mlp.gate.bias")
            if rb is not None:
                layers["router_b"] = rb
            gub_probe = "model.layers.0.mlp.experts.gate_up_proj_bias"
            if gub_probe in idx:
                gub = np.stack(
                    [
                        idx.get(
                            f"model.layers.{i}.mlp.experts.gate_up_proj_bias"
                        )
                        for i in range(L)
                    ]
                )  # [L, E, 2F]
                layers["we_gate_b"] = jnp.asarray(gub[..., 0::2], dtype)
                layers["we_up_b"] = jnp.asarray(gub[..., 1::2], dtype)
                layers["we_down_b"] = stack(
                    p + "mlp.experts.down_proj_bias"
                )
    else:
        layers["w_gate"] = stack(p + "mlp.gate_proj.weight", transpose=True)
        layers["w_up"] = stack(p + "mlp.up_proj.weight", transpose=True)
        layers["w_down"] = stack(p + "mlp.down_proj.weight", transpose=True)

    params: Dict[str, Any] = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "final_norm": jnp.asarray(get("model.norm.weight"), dtype),
        "layers": layers,
    }
    if not mcfg.tie_embeddings and mcfg.head == "lm":
        name = _first(idx, "lm_head.weight")
        if name:
            params["lm_head"] = jnp.asarray(get(name, transpose=True), dtype)

    _validate(params, mcfg)
    return params


def _load_mixed(mcfg: ModelConfig, get, dtype) -> Dict[str, Any]:
    """A model whose layers are of several kinds (the ``lfm2_moe``
    tensor names): each layer's tensors go onto ITS kind's stack, in
    layer order (models/transformer.py ``_init_mixed_layers``).
    ``Linear`` weights are transposed to [in, out]; the depthwise conv
    weight [H, 1, K] becomes [H, K]."""
    if mcfg.num_window_layers:
        # the block is Qwen3-MoE's, and loading it under those names
        # would rotate every layer alike and window none
        raise NotImplementedError(
            f"{mcfg.name}: loading a checkpoint of model_type 'mellum' "
            "or 'laguna' is not written: its tensor names (per-layer "
            "self_attn / mlp.experts, whether q_norm / k_norm exist, the "
            "gate's and the shared expert gate's leaves) and the reading "
            "of rope_parameters by layer kind are unconfirmed; the model "
            "runs on seeded random weights"
        )
    if mcfg.num_state_layers:
        raise NotImplementedError(
            f"{mcfg.name}: loading a checkpoint with {mcfg.state_kind} "
            "layers (the granitemoehybrid / solar_open2 tensor names) is "
            "not written; the model runs on seeded random weights"
        )
    if mcfg.hc_mult > 1:
        raise NotImplementedError(
            f"{mcfg.name}: loading a checkpoint of a model whose residual "
            "stream is several lanes (hc_mult; the xing4_0 tensor names "
            "of a sublayer's phi, bias and alphas) is not written; the "
            "model runs on seeded random weights"
        )
    if mcfg.block_length > 1:
        raise NotImplementedError(
            f"{mcfg.name}: loading a checkpoint of a model that generates "
            "by blocks (block_length; model_type 'sdar_moe') is not "
            "written: there is no file to hold the reading against here, "
            "and block_length and mask_token_id are no keys of the "
            "published config; the model runs on seeded random weights"
        )
    stacks: Dict[str, Dict[str, list]] = {}

    def put(kind: str, name: str, arr: np.ndarray) -> None:
        stacks.setdefault(kind, {}).setdefault(name, []).append(arr)

    for i, (mixer, ffn) in enumerate(zip(mcfg.mixers, mcfg.ffns)):
        p = f"model.layers.{i}."
        if mixer == "conv":
            put("conv", "attn_norm", get(p + "operator_norm.weight"))
            put("conv", "w_in", get(p + "conv.in_proj.weight", True))
            w = get(p + "conv.conv.weight")
            put("conv", "w_conv", w.reshape(w.shape[0], w.shape[-1]))
            put("conv", "w_out", get(p + "conv.out_proj.weight", True))
        else:
            a = p + "self_attn."
            put("attn", "attn_norm", get(p + "operator_norm.weight"))
            put("attn", "wq", get(a + "q_proj.weight", True))
            put("attn", "wk", get(a + "k_proj.weight", True))
            put("attn", "wv", get(a + "v_proj.weight", True))
            put("attn", "wo", get(a + "out_proj.weight", True))
            put("attn", "q_norm", get(a + "q_layernorm.weight"))
            put("attn", "k_norm", get(a + "k_layernorm.weight"))
        f = p + "feed_forward."
        put(ffn, "mlp_norm", get(p + "ffn_norm.weight"))
        if ffn == "dense":
            put("dense", "w_gate", get(f + "w1.weight", True))
            put("dense", "w_up", get(f + "w3.weight", True))
            put("dense", "w_down", get(f + "w2.weight", True))
        else:
            put("moe", "router", get(f + "gate.weight", True))
            put("moe", "router_bias", get(f + "expert_bias"))
            for name, sub in (("we_gate", "w1"), ("we_up", "w3"),
                              ("we_down", "w2")):
                put("moe", name, np.stack([
                    get(f + f"experts.{e}.{sub}.weight", True)
                    for e in range(mcfg.moe_experts)
                ]))
    layers = {
        kind: {
            name: jnp.asarray(
                np.stack(arrs),
                jnp.float32 if name == "router_bias" else dtype,
            )
            for name, arrs in leaves.items()
        }
        for kind, leaves in stacks.items()
    }
    try:
        final = get("model.embedding_norm.weight")
    except KeyError:
        final = get("model.norm.weight")
    return {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "final_norm": jnp.asarray(final, dtype),
        "layers": layers,
    }


def _validate_mixed(params: Dict[str, Any], mcfg: ModelConfig) -> None:
    """Every leaf's shape against what ``init_params`` would build."""
    import jax

    from ..models.transformer import init_params

    want = jax.eval_shape(
        lambda key: init_params(mcfg, key), jax.random.PRNGKey(0)
    )
    got_flat = dict(jax.tree_util.tree_leaves_with_path(params))
    for path, leaf in jax.tree_util.tree_leaves_with_path(want):
        got = got_flat.get(path)
        if got is None or tuple(got.shape) != tuple(leaf.shape):
            raise ValueError(
                f"Checkpoint shape mismatch at "
                f"{jax.tree_util.keystr(path)}: got "
                f"{None if got is None else tuple(got.shape)}, want "
                f"{tuple(leaf.shape)} for model {mcfg.name}"
            )


def _validate(params: Dict[str, Any], mcfg: ModelConfig) -> None:
    H, L = mcfg.hidden_size, mcfg.num_layers
    checks = {
        "embed": (mcfg.vocab_size, H),
        "layers.wq": (L, H, mcfg.q_size),
        "layers.wk": (L, H, mcfg.kv_size),
        "layers.wo": (L, mcfg.q_size, H),
    }
    for path, want in checks.items():
        node: Any = params
        for part in path.split("."):
            node = node[part]
        if tuple(node.shape) != want:
            raise ValueError(
                f"Checkpoint shape mismatch at {path}: got {tuple(node.shape)}, "
                f"want {want} for model {mcfg.name}"
            )
