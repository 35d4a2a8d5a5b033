"""What the LFM2-family tests share: a preset of the program's with
layers of two kinds at a test's size, its published keys, and the wrong
systems of the routed rule's table for this family. The stand-in for
``sut.System`` is ``routed_systems.ForwardSystem`` (the program's own
``forward`` over whole sequences)."""

import dataclasses

import jax
import jax.numpy as jnp

from sutro_tpu.models import transformer
from sutro_tpu.models.configs import ModelConfig
from sutro_tpu.ops import moe
from tests.perfbench.routed_systems import ForwardSystem, through_float8

# the routed rule's values for this family's tests: top-4 of 32 experts
# over 6 routed layers puts a quarter of a layer's FFN on each flipped
# selection, so the worst position of a CORRECT bf16 system reads up to
# 0.41 of the largest logit here (five seeds); the quantile, which is
# what catches the wrong systems, reads 0.025-0.030 against 0.06
NUMBERS = {"sequences": 8, "quantile": 0.25, "cap": 0.5,
           "why": "this preset's own readings on the CPU, in mixed_systems.py"}

PUBLISHED_KIND = {"conv": "conv", "attention": "full_attention"}


def preset(hidden=256, experts=32, top_k=4, vocab=2048, name="lfm2-test",
           layers=("conv", "conv", "attention", "conv", "conv", "conv",
                   "attention", "conv")) -> ModelConfig:
    """LFM2-24B-A2B's shape at a test's size: two kinds of layers in
    its period of four, two leading dense layers, heads of 64, a sigmoid
    router with a selection bias over ``experts`` experts, tied head."""
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=hidden,
        num_layers=len(layers), num_heads=hidden // 64, num_kv_heads=2,
        head_dim=64, intermediate_size=2 * hidden, norm_eps=1e-5,
        qk_norm=True, tie_embeddings=True, moe_experts=experts,
        moe_top_k=top_k, moe_intermediate_size=3 * hidden // 8,
        num_dense_layers=2, router_score="sigmoid", router_select_bias=True,
        layer_types=tuple(layers), conv_kernel=3,
    )


def published_keys(m: ModelConfig, **more):
    keys = {
        "name": m.name, "model_type": "lfm2_moe",
        "hidden_size": m.hidden_size, "num_hidden_layers": m.num_layers,
        "num_attention_heads": m.num_heads,
        "num_key_value_heads": m.num_kv_heads, "head_dim": m.head_dim,
        "intermediate_size": m.intermediate_size, "vocab_size": m.vocab_size,
        "tie_word_embeddings": m.tie_embeddings, "norm_eps": m.norm_eps,
        "rope_parameters": {"rope_theta": m.rope_theta, "rope_type": "default"},
        "layer_types": [PUBLISHED_KIND[k] for k in m.mixers],
        "conv_L_cache": m.conv_kernel, "conv_bias": False,
        "num_dense_layers": m.num_dense_layers,
        "num_experts": m.moe_experts, "num_experts_per_tok": m.moe_top_k,
        "moe_intermediate_size": m.moe_intermediate_size,
        "norm_topk_prob": m.router_renorm, "use_expert_bias": True,
        "routed_scaling_factor": m.router_scale,
        "reference": "lfm2_moe", "numbers": dict(NUMBERS),
    }
    keys.update(more)
    return keys


def louder_bias(params, factor=10.0):
    """The same weights with the selection bias ``factor`` times larger
    (standard deviation 0.2): as far from zero as a trained one may be,
    so that a system which misuses it is far off."""
    layers = dict(params["layers"])
    layers["moe"] = dict(
        layers["moe"], router_bias=layers["moe"]["router_bias"] * factor
    )
    return dict(params, layers=layers)


def swapped_b_and_c(params):
    """``w_in`` with its first two thirds exchanged: a system that reads
    [C | B | z] where the published order is [B | C | z]."""
    w = params["layers"]["conv"]["w_in"]
    H = w.shape[1]
    swapped = jnp.concatenate(
        [w[..., H : 2 * H], w[..., :H], w[..., 2 * H :]], axis=-1
    )
    layers = dict(params["layers"])
    layers["conv"] = dict(layers["conv"], w_in=swapped)
    return dict(params, layers=layers)


def bias_into_the_weights(monkeypatch):
    """Patch the program's router so that the weights come from
    ``score + bias`` (the selection's quantity) and not from the scores."""
    plain = moe._route

    def wrong(xt, router, router_b, top_k, *, score="softmax",
              select_bias=None, renorm=True, scale=1.0):
        top_idx, _, flat_expert, flat_token, _ = plain(
            xt, router, router_b, top_k, score=score,
            select_bias=select_bias, renorm=renorm, scale=scale,
        )
        s = jax.nn.sigmoid(xt.astype(jnp.float32) @ router.astype(jnp.float32))
        p = jnp.take_along_axis(s + select_bias, top_idx, axis=-1)
        p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-6)
        return top_idx, p, flat_expert, flat_token, p.reshape(-1)

    monkeypatch.setattr(moe, "_route", wrong)


def state_zeroed_at(monkeypatch, boundaries):
    """Patch the program's conv mixer so that the state is zeros again
    at each token index of ``boundaries``: what a runner computes that
    drops the state between two chunks (a prefill chunk, the prefill's
    end, a decode step)."""
    plain = transformer.conv_mixer

    def wrong(cfg, lp, x, state):
        cuts = [b for b in boundaries if 0 < b < x.shape[1]]
        ys, gs = [], []
        for lo, hi in zip([0] + cuts, cuts + [x.shape[1]]):
            y, g = plain(cfg, lp, x[:, lo:hi],
                         state if lo == 0 else jnp.zeros_like(state))
            ys.append(y)
            gs.append(g if lo == 0 else g[:, state.shape[1]:])
        return jnp.concatenate(ys, axis=1), jnp.concatenate(gs, axis=1)

    monkeypatch.setattr(transformer, "conv_mixer", wrong)


def wrong_systems(mcfg, params, monkeypatch, memo=None, tag=None):
    """name -> a call that builds (system, configuration keys the
    reference is given). A system whose PROGRAM is patched carries a
    configuration of another name, so that no cached trace of the
    correct program answers for it."""
    keys = published_keys(mcfg)

    def system(cfg=mcfg, served=None, what="as stated", **more):
        return ForwardSystem(cfg, params if served is None else served,
                             stated=params, memo=memo, tag=(tag, what), **more)

    def renamed(what):
        return dataclasses.replace(mcfg, name=f"{mcfg.name}: {what}")

    def with_bias_in_weights():
        bias_into_the_weights(monkeypatch)
        return system(renamed("bias weighs"), what="bias weighs"), keys

    def with_state_zeroed(boundaries, what):
        def make():
            state_zeroed_at(monkeypatch, boundaries)
            return system(renamed(what), what=what), keys

        return make

    return {
        "top-3 in the system": lambda: (
            system(dataclasses.replace(mcfg, moe_top_k=mcfg.moe_top_k - 1)), keys),
        "bias left out of selection": lambda: (
            system(dataclasses.replace(mcfg, router_select_bias=False)), keys),
        "bias added into the weights": with_bias_in_weights,
        "B and C swapped": lambda: (
            system(served=swapped_b_and_c(params), what="swapped"), keys),
        # a runner that never carries the state: zeros at the prefill's
        # end (192 tokens, correctness.N_PREFILL) and at every decode step
        "conv state zeroed at every chunk boundary": with_state_zeroed(
            list(range(192, 200)), "state zeroed at every boundary"),
        # ... and one that drops it between two prefill chunks only,
        # ninety tokens before the first scored position
        "conv state zeroed at one prefill chunk boundary": with_state_zeroed(
            [100], "state zeroed at 100"),
        "weights through float8_e4m3": lambda: (
            system(served=through_float8(params), what="float8"), keys),
        "renormalisation dropped": lambda: (
            system(dataclasses.replace(mcfg, router_renorm=False)), keys),
    }
