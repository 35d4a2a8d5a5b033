"""What the routed tests share: a small Qwen3-MoE preset of the
program's, its published keys, and a stand-in for ``sut.System`` whose
logits come from the program's own ``forward``."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from sutro_tpu.models import transformer
from sutro_tpu.models.configs import ModelConfig

NUMBERS = {"sequences": 4, "quantile": 0.25, "cap": 0.3,
           "why": "the starting values of reference/README.md"}


def preset(hidden=256, layers=8, experts=128, top_k=8, vocab=2048) -> ModelConfig:
    """Qwen3-30B-A3B's shape at a test's size: 128 experts, top-8,
    expert width 3/8 of hidden, heads of 128, QK-norm, untied."""
    return ModelConfig(
        name="moe-test", vocab_size=vocab, hidden_size=hidden,
        num_layers=layers, num_heads=hidden // 64, num_kv_heads=2,
        head_dim=128, intermediate_size=3 * hidden, moe_experts=experts,
        moe_top_k=top_k, moe_intermediate_size=3 * hidden // 8,
        qk_norm=True, tie_embeddings=False,
    )


def published_keys(m: ModelConfig, **more):
    keys = {
        "name": m.name, "hidden_size": m.hidden_size,
        "num_hidden_layers": m.num_layers,
        "num_attention_heads": m.num_heads,
        "num_key_value_heads": m.num_kv_heads, "head_dim": m.head_dim,
        "intermediate_size": m.intermediate_size, "vocab_size": m.vocab_size,
        "tie_word_embeddings": m.tie_embeddings, "rms_norm_eps": m.norm_eps,
        "rope_theta": m.rope_theta,
    }
    if m.moe_experts:
        keys.update(
            num_experts=m.moe_experts, num_experts_per_tok=m.moe_top_k,
            moe_intermediate_size=m.moe_intermediate_size,
            norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
            reference="qwen3_moe", numbers=dict(NUMBERS),
        )
    keys.update(more)
    return keys


@functools.partial(jax.jit, static_argnums=(0,))
def program_logits(mcfg, params, ids):
    """The program's forward over one whole sequence, float32 logits [T, V]."""
    T = ids.shape[0]
    out, _, _ = transformer.forward(
        mcfg, params, ids[None], jnp.arange(T)[None], jnp.asarray([T])
    )
    return out[0].astype(jnp.float32)


class ForwardSystem:
    """Stands where ``perfbench.sut.System`` stands for
    ``correctness.numbers``. ``served`` are the weights the system
    computes with, ``stated`` those the reference is handed (the same
    unless the system is a wrong one that rounds its own); ``shift``
    scores the system one position early. ``memo`` (a dict) with ``tag``
    (what names ``served``) keeps a sequence's logits for the next
    system that computes the same."""

    def __init__(self, mcfg, served, stated=None, dtype="bfloat16", shift=0,
                 memo=None, tag=None):
        self.mcfg, self.served, self.dtype, self.shift = mcfg, served, dtype, shift
        self.stated = served if stated is None else stated
        self.memo, self.tag = memo, tag

    def serving_dtype(self):
        return self.dtype

    def _whole(self, seq):
        key = (self.mcfg, self.tag, seq.tobytes())
        if self.memo is None or key not in self.memo:
            full = np.asarray(program_logits(self.mcfg, self.served, jnp.asarray(seq)))
            if self.memo is None:
                return full
            self.memo[key] = full
        return self.memo[key]

    def logits_through_cache(self, ids, n_prefill, n_decode):
        ids = np.asarray(ids, np.int32)
        lo = n_prefill - 1 - self.shift

        def one(seq):
            return self._whole(seq)[lo : lo + 1 + n_decode]

        return one(ids) if ids.ndim == 1 else np.stack([one(s) for s in ids])

    def weights(self):
        return self.stated

    def kernel_paths(self):
        return {}

    def uses_kernels(self):
        return False


def through_float8(params):
    """Every matrix rounded through float8_e4m3 and back: the precision
    below bf16 that would tempt a later PR."""
    def rounded(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim >= 2 else x

    return jax.tree_util.tree_map(rounded, params)


def wrong_systems(mcfg, params, memo=None, tag=None):
    """name -> a call that builds (system, configuration keys the
    reference is given)."""
    keys = published_keys(mcfg)

    def system(cfg=mcfg, served=None, what="as stated", **more):
        return ForwardSystem(cfg, params if served is None else served,
                             stated=params, memo=memo, tag=(tag, what), **more)

    return {
        "top-7 in the system": lambda: (
            system(dataclasses.replace(mcfg, moe_top_k=mcfg.moe_top_k - 1)), keys),
        "weights through float8_e4m3": lambda: (
            system(served=through_float8(params), what="float8"), keys),
        "rope_theta 1e4 for 1e6": lambda: (
            system(dataclasses.replace(mcfg, rope_theta=1e4)), keys),
        "norm_topk_prob false in the reference": lambda: (
            system(), dict(keys, norm_topk_prob=False)),
        "scored one position early": lambda: (system(shift=1), keys),
    }
