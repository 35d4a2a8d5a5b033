"""Model architecture configs for the engine's model catalog.

The reference ships no model code — its catalog is a list of names sent to a
remote fleet (/root/reference/sutro/common.py:20-45). Here each catalog name
maps to a full architecture spec for the in-tree TPU engine. One
config-driven decoder-only transformer (models/transformer.py) covers sixteen
families:

- Qwen3 dense (0.6b..32b): GQA + QK-RMSNorm, SwiGLU, RoPE
- Qwen3 MoE (30b-a3b, 235b-a22b): + top-k softmax router, no shared expert
- Llama 3.x: GQA, SwiGLU, RoPE (no QK-norm)
- Gemma 3: GQA + QK-norm, GeGLU-ish gated MLP, pre+post norms, 5:1
  local:global sliding-window attention, embedding scaling
- gpt-oss (20b/120b): MoE + attention sinks + alternating sliding window
- LFM2-MoE (24b-a2b): layers of two kinds (``layer_types``): a gated
  short convolution with K-1 columns of per-sequence state, or GQA
  attention; leading dense SwiGLU layers, then a sigmoid router with a
  selection-only bias
- Granite 4.0-H (micro): Mamba-2 layers (a state that is a matrix a
  head and K-1 conv columns, kept a SLOT a sequence) beside GQA
  attention without rotary embedding; a softmax scale of its own and
  three scalar multipliers (embedding, residual, logits)
- Mellum 2 (12b-a2.5b): attention layers of two kinds (``layer_types``
  "swa" | "attention"): three sliding-window layers (window 1,024,
  plain rotary embedding) to one full layer (YaRN with a stated
  attention factor), every FFN routed (64 experts, top-8, softmax over
  all then renormalised); K/V is kept a pool a kind
  (engine/kvcache.py)
- Nemotron 3 Nano (30b-a3b; ``model_type`` nemotron_h): 52 blocks of
  ONE sublayer each, ``h <- h + f(norm(h))``, by the published
  ``hybrid_override_pattern``: Mamba-2 (8 groups of B and C, the gated
  norm a group at a time), GQA attention without rotary embedding
  (16 query heads a KV head) or a routed FFN (sigmoid router with a
  selection bias over 128 experts, top-6, the chosen scores over their
  sum times 2.5; experts of TWO matrices under relu^2; one shared
  expert). ``-l14-ep2`` is one chip's share of a deployment: the first
  14 blocks, experts 0-63 of each layer (``moe_experts_held``: the
  router keeps its 128 outputs, the rest are another chip's) and half
  the vocabulary
- JoyAI-LLM-Flash (48b-a2.7b; ``model_type`` joyai_llm_flash, DeepSeek-V3's
  key set): every layer's mixer is latent attention ("mla"): a query
  projection of low rank (``q_lora_rank``, normed), ONE latent row a
  token (``kv_lora_rank`` normed values and a ``qk_rope_head_dim``
  rotary key shared by all heads, the pair (2i, 2i+1) turned:
  ``rope_interleave``) that is all the cache keeps
  (``ModelConfig.page_width``), per-head K (``qk_nope_head_dim`` + the
  shared rotary part) and V (``v_head_dim``) expanded from it for a
  chunk with no past and absorbed into the query and the output for
  everything over a paged past; a leading dense SwiGLU layer, then 256
  SwiGLU experts top-8 (sigmoid router, selection bias, the chosen
  scores over their sum times 2.5) beside one shared expert. ``-ep16``
  is one chip of sixteen that share every layer: experts 0-15 of each
  routed layer's 256, every layer, the whole vocabulary
- GLM-5 (744b-a40b; ``model_type`` glm_moe_dsa): the same latent
  attention at other widths (``v_head_dim`` 256 beside a
  ``qk_nope_head_dim`` of 192) under LEARNED SPARSE attention: every
  layer has an indexer (``index_n_heads`` heads of ``index_head_dim``
  from the same normed query latent, ONE LayerNormed, partly rotated
  index key a token, a weight a head) whose score ``sum_j w_j relu(q_j .
  k)`` picks the ``index_topk`` positions of a row's past that the
  attention's softmax runs over; the index key is cached beside the
  latent row, in a pool of its own on the same page table
  (``ModelConfig.pool_row_widths``). Three leading dense layers, then 256
  SwiGLU experts top-8 beside a shared one, routed as above. ``-l5-ep16``
  is one chip of the sixteen that share every layer of the first
  pipeline stage: a dense layer and four routed layers, experts 0-15 of
  each routed layer's 256, an eighth of the vocabulary
- Solar Open 2 (250b-a15b; ``model_type`` solar_open2): three layers of
  four are Kimi Delta Attention ("kda": a gated delta rule, ``kda_heads``
  heads of a ``kda_head_dim`` x ``kda_head_dim`` state kept a SLOT a
  sequence like Mamba-2's, a decay a CHANNEL and an output gate from
  low-rank pairs, ``beta`` in (0, 2), a 4-tap conv over q, k and v), the
  fourth softmax GQA without rotary embedding and with an output gate
  (``attn_gate``); every FFN routes 320 SwiGLU experts top-8 (sigmoid,
  selection bias, renormalised) beside a shared one. ``-l8-ep16`` is one
  chip of the sixteen that share every layer of the first of six
  pipeline stages: layers 0-7, experts 0-19 of each layer's 320, an
  eighth of the vocabulary
- Xing4.0 (29b-a4b; ``model_type`` xing4_0): JoyAI's key set at other
  numbers (a query latent of 768, two leading dense layers of 9,216, 64
  SwiGLU experts of 1,024 top-4 beside a shared one, the chosen scores
  over their sum times 2) with two things no other family has: the
  RESIDUAL STREAM is ``hc_mult`` = 4 lanes a token, which every sublayer
  reads through sigmoid weights, writes through weights in (0, 2) and
  mixes by a 4 x 4 matrix a token that twenty Sinkhorn passes make doubly
  stochastic (manifold-constrained hyper-connections:
  models/transformer.py ``hc_sublayer``); and YaRN (factor 64 over 4,096
  positions) on the latent layers' 64-wide rotary part, with the score's
  scale times ``(0.1 ln 64 + 1)^2``. ``-l7`` is the first of six
  pipeline stages: layers 0-6, every expert, the whole vocabulary
- SDAR (30b-a3b-chat; ``model_type`` sdar_moe): Qwen3-MoE's layer under
  a mask that is causal BY BLOCKS of ``block_length`` positions (a
  position sees every earlier block and the whole of its own), with
  logits that are not shifted (position i's are the distribution of the
  token AT i), generated by diffusion over a block: ``block_length``
  ``mask_token_id``s filled in over ``denoising_steps`` forwards by
  confidence (``remasking``), then one more forward that writes the
  block's K/V (engine/runner.py ``_decode_block_jit``). ``-l6`` is the
  first of eight pipeline stages: layers 0-5, every expert, the whole
  vocabulary
- Laguna S 2.1 (118b; ``model_type`` laguna): Mellum 2's key set (window
  and full attention layers, K/V a pool a kind, a rotary embedding a
  kind) with five things that are no numbers of it: QUERY HEADS A LAYER
  KIND (``window_num_heads`` 72 in the window layers, ``num_heads`` 48
  in the full ones, 8 KV heads in both: 9 and 6 query heads a KV head),
  an output gate a HEAD (``attn_gate`` "head": one sigmoid scalar a
  query head from the layer's normed input), a rotary part narrower
  than the head in the full layers alone (``rotary_dim`` 64 of 128
  under YaRN, factor 128; the window layers turn all 128 plainly at
  theta 10,000), a leading dense layer and then 256 SwiGLU experts of
  1,024 top-10 (softmax, the chosen over their sum, times 2.5) beside
  a shared expert with a sigmoid gate of its own
  (``moe_shared_gate``). ``-l9-ep8`` is one chip of the eight that
  share every layer of the first of six pipeline stages: layers 0-8,
  experts 0-31 of each routed layer's 256, an eighth of the vocabulary
- Phi-4-mini-flash-reasoning (``model_type`` phi4flash; SambaY, a
  decoder-hybrid-decoder, arXiv:2507.06607): blocks under LayerNorm with
  a bias (``block_norm``), a dense SwiGLU in every one, no positional
  embedding, and five mixers by the layer's place: Mamba-1 ("mamba1": a
  state ``[d_state, d_inner]`` a sequence with a decay a CHANNEL AND a
  state column, kept a SLOT like Mamba-2's), DIFFERENTIAL attention
  (``attn_differential``, arXiv:2410.05258: heads paired, two softmaxes
  over one value of two heads, their difference under a learned lambda
  and a norm of its own) over a window ("swa") in the first half and
  over everything in ONE layer ("attention"), whose K/V the second
  half's "cross" layers read again with queries of their own
  (``kv_source_layer``) between gated memory units ("gmu": a gate on
  the scan output of ONE Mamba-1 layer of the same forward,
  ``memory_layer``); projection biases on the attention layers

Hyperparameters follow the public model cards; exactness matters only when
loading real checkpoints (engine/weights.py validates shapes against these).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple


#: ``layer_types`` entries that name an FFN (``ModelConfig.one_sublayer``)
FFN_KINDS = ("dense", "moe")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    qk_norm: bool = False                 # Qwen3 / Gemma3
    tie_embeddings: bool = True
    # MoE (0 experts => dense MLP)
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_intermediate_size: int = 0
    # gpt-oss: router + per-expert projection biases
    moe_bias: bool = False
    # the first ``num_dense_layers`` layers of a routed model carry a
    # dense MLP of ``intermediate_size`` instead of experts
    num_dense_layers: int = 0
    # The router's form (ops/moe.py ``_route``). "softmax": softmax over
    # the top-k logits (Qwen3-MoE, gpt-oss). "sigmoid": per-expert
    # sigmoid scores; the weights are the chosen experts' scores,
    # divided by their sum (+1e-6) when ``router_renorm``, times
    # ``router_scale``. ``router_select_bias`` adds a per-expert
    # parameter (``router_bias`` [E]) to the scores for the top-k
    # SELECTION only; it never enters the weights.
    router_score: str = "softmax"
    router_select_bias: bool = False
    router_renorm: bool = True
    router_scale: float = 1.0
    # what ``router_renorm`` adds to the chosen scores' sum (LFM2: 1e-6,
    # Nemotron-H: 1e-20)
    router_renorm_eps: float = 1e-6
    # An expert layer told which experts it holds: the router keeps
    # ``moe_experts`` outputs and its top-k; the layer holds experts
    # ``moe_first_expert .. + moe_experts_held`` (0: all of them) and
    # computes the assignments that land on those; the rest are another
    # chip's and are left out (ops/moe.py ``held_rows``).
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    # rows a held share's products take in a prefill, in the shares an
    # even router would send this chip (ops/moe.py ``_share_row_cap``):
    # its rows beyond that send EVERY expanded row through the products
    # (8 x the work at a sixteenth), so the room is sized for how uneven
    # the router is. 2 until a model says otherwise
    moe_share_rows: int = 2
    # the most tokens of a dispatch a routed layer takes at once, one
    # equal tile after another (0: the whole dispatch; ops/moe.py
    # ``moe_mlp``). A layer's temporaries are several copies of its
    # EXPANDED rows, ``tokens x top_k x hidden``: at 8,192 tokens,
    # top-10 and 3,072 they stood at 3.4 GB, the whole of what the pools
    # leave free (PERF.md section 6, PR 61). A model's field and no
    # byte budget inside ``moe_mlp``: what tells this model from one
    # whose 16,384-token prefill expands to three times the bytes and
    # fits is the memory its weights and pools leave, which the layer
    # cannot see
    moe_token_tile: int = 0
    # False: an expert is TWO matrices, ``down(act(up x))`` (``we_up``,
    # ``we_down``; no ``we_gate``), as is the shared expert
    moe_gated: bool = True
    # width of ONE shared expert, of the experts' form, that every token
    # takes beside its routed ones (0: none)
    moe_shared_intermediate_size: int = 0
    # the shared expert's output is multiplied by ``sigmoid(x w_s)``,
    # ``w_s`` [H, 1]: one scalar a token (Qwen2-MoE's shared expert gate)
    moe_shared_gate: bool = False
    # Per-layer mixer kinds, "attention" | "swa" | "conv" | "mamba" |
    # "mla" | "kda" | "mamba1" | "gmu" | "cross";
    # empty => attention everywhere. A "swa" layer is attention over the
    # last ``sliding_window`` positions, with the plain rotary embedding
    # of ``local_rope_theta`` (``rope_theta`` when that is None) whatever
    # scaling the full layers have; its K/V lives in a pool of its own
    # kind (engine/kvcache.py). A "conv" layer is the gated short
    # convolution of the LFM2 family: depthwise, causal, ``conv_kernel``
    # taps, and K-1 columns of per-sequence state beside the paged K/V.
    # An entry may also name an FFN kind, "moe" | "dense": the model's
    # blocks are then ONE sublayer each, ``h <- h + f(norm(h))``: that
    # FFN alone, and at a mixer's entry the mixer alone (Nemotron-H's
    # ``hybrid_override_pattern``).
    layer_types: Tuple[str, ...] = ()
    conv_kernel: int = 0
    # A "mamba" layer is Mamba-2 (models/transformer.py ``mamba_mixer``):
    # ``mamba_heads`` heads of ``mamba_head_dim``, each with a state
    # [head_dim, mamba_state]; B and C shared by the heads of one of
    # ``mamba_groups`` groups; a depthwise causal conv of ``mamba_conv``
    # taps over [x | B | C]; the chunked scan at ``mamba_chunk`` tokens.
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state: int = 0
    mamba_groups: int = 1
    mamba_conv: int = 0
    mamba_chunk: int = 256
    # A "kda" layer is Kimi Delta Attention (models/transformer.py
    # ``kda_mixer``): ``kda_heads`` heads, each with a state
    # [kda_head_dim (k), kda_head_dim (v)] that every token multiplies
    # by ``(I - beta k k^T) Diag(exp g)`` before it adds ``beta k v^T``;
    # a depthwise causal conv of ``kda_conv`` taps over [q | k | v]; the
    # log-decay ``g`` a CHANNEL and the output gate from pairs of
    # matrices of rank ``kda_rank``; ``beta = kda_beta_scale *
    # sigmoid(.)`` (2: eigenvalues of ``I - beta k k^T`` down to -1); the
    # chunk form at ``kda_chunk`` tokens.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0
    kda_rank: int = 0
    kda_beta_scale: float = 1.0
    kda_chunk: int = 64
    # A "mamba1" layer is Mamba-1 (models/transformer.py
    # ``mamba1_mixer``): ``mamba1_inner`` channels, each with
    # ``mamba1_state`` state columns; the step ``dt`` a channel from a
    # pair of rank ``mamba1_dt_rank``; B and C a token, shared by every
    # channel; ``A`` [inner, state], so a token's decay ``exp(dt A)`` is
    # a value a channel AND a column (no head axis: Mamba-2's chunk form
    # does not apply); a depthwise causal conv of ``mamba1_conv`` taps
    # over x alone; a prefill scans ``mamba1_chunk`` tokens at a time
    mamba1_inner: int = 0
    mamba1_state: int = 0
    mamba1_conv: int = 0
    mamba1_dt_rank: int = 0
    mamba1_chunk: int = 64
    # A "gmu" layer is a gated memory unit, ``(m * silu(u W_1)) W_2``: a
    # gate of ``mamba1_inner`` on ``m``, the scan output ``y`` (with the
    # ``D x`` term, before its own gate) that Mamba-1 layer
    # ``memory_layer`` gave the SAME token in the same forward. It keeps
    # nothing. A "cross" layer is an attention layer with a query, an
    # output projection and nothing else of its own: its K and V are
    # those of the "attention" layer ``kv_source_layer`` for the same
    # row (that layer's pool, its chunk's K/V). It keeps nothing either
    memory_layer: int = -1
    kv_source_layer: int = -1
    # The form of the output gate of the model's attention layers ("":
    # none), ``sigmoid(x W_gate)`` from the layer's normed input, times
    # the attention's output before ``wo``: "channel" one value a channel
    # (``W_gate`` [H, heads x head_dim]: Solar Open 2), "head" one scalar
    # a query head (``W_gate`` [H, heads]: Laguna)
    attn_gate: str = ""
    # DIFFERENTIAL attention (arXiv:2410.05258) in every "attention",
    # "swa" and "cross" layer: query heads (2i, 2i+1) are ONE head's
    # ``(q1, q2)``, KV heads pair the same way into ``(k1, k2)`` and one
    # value ``[v1 | v2]`` of two heads; ``a_j = softmax(q_j k_j^T) v``,
    # the head's output ``RMSNorm(a1 - lambda a2) (1 - lambda_init)``
    # with ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
    # ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at layer ``l``
    # (models/transformer.py ``differential_heads``). The cache keeps
    # ``num_kv_heads`` heads of ``head_dim`` as any model's; the kernels
    # are called at the PAIRS, heads of ``kernel_head_dim``
    attn_differential: bool = False
    # Query heads of a "swa" layer where they are not ``num_heads`` (0:
    # they are); both kinds keep ``num_kv_heads``, so the pools and a
    # chunk's K/V are of one width and only Q, the gate and ``wo``
    # differ. ``heads_of`` is THE place a layer kind's count is read
    window_num_heads: int = 0
    # Elements of a head that take the rotary embedding, the first ones,
    # in half-split pairs inside them; the rest pass through (0: the
    # whole head) in the FULL layers; a window layer turns the whole
    # head (``rotary_dim_of``)
    rotary_dim: int = 0
    # An "mla" layer is latent attention (models/transformer.py
    # ``mla_mixer``): ``c_q = norm(x W_qa)`` of ``q_lora_rank``, a head's
    # query ``qk_nope_head_dim`` wide plus ``qk_rope_head_dim`` that takes
    # the rotary embedding; ``x W_kva`` = ``kv_lora_rank`` latent values
    # (normed) | ONE rotary key of ``qk_rope_head_dim`` for all heads:
    # that row is what the cache keeps (``page_width``); a head's K and
    # V (``v_head_dim``) come from the latent values through ``w_kvb``.
    # The softmax scale is 1 / sqrt(nope + rope). ``head_dim`` and
    # ``num_kv_heads`` size nothing of such a layer.
    # ``rope_interleave``: the rotary pairs are (2i, 2i+1), not
    # (i, i + half)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # ``index_topk`` > 0 gives every "mla" layer an INDEXER (learned
    # sparse attention): ``q_I = c_q W_Iqb`` of ``index_n_heads`` heads
    # ``index_head_dim`` wide, ONE key a token ``k_I = LayerNorm(x W_Ik)``
    # (scale and bias, eps ``index_norm_eps``), the rotary embedding on
    # the first ``qk_rope_head_dim`` of both (interleaved pairs), a weight
    # a head ``w = x W_Iw / sqrt(heads * width)``; ``I(t, s) = sum_j w_j(t)
    # relu(q_I_j(t) . k_I(s))`` and the layer's softmax runs over the
    # ``index_topk`` positions ``s <= t`` of largest ``I`` alone (all of
    # them while there are no more; ties to the lower position). ``k_I``
    # is cached beside the latent row (``pool_row_widths``)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_norm_eps: float = 1e-6
    # seeded weights alone: the standard deviation of the indexer's
    # scores and of the attention's logits over a row's keys that the
    # draws of ``w_iqb`` / ``w_ik`` and of the key halves of ``w_qb`` /
    # ``w_kvb`` aim at (0: every matrix at variance 1 / fan-in, where
    # both are near uniform over the keys and a subset's mean is close
    # to the whole's; models/transformer.py ``_init_mixed_layers``)
    seeded_peaked_attention: float = 0.0
    # Granite's scalar multipliers (1.0: none) and softmax scale (None:
    # 1/sqrt(head_dim)); "nope" applies no rotary embedding
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    position_embedding: str = "rope"
    # Sliding window attention: 0 => full attention everywhere.
    sliding_window: int = 0
    # "none" | "alternate" (gpt-oss: even layers sliding) |
    # "gemma" (5 local : 1 global)
    sliding_pattern: str = "none"
    # gpt-oss learnable attention sinks
    attention_sink: bool = False
    # qkv/o projection biases (gpt-oss)
    attn_bias: bool = False
    # Gemma-style zero-centered RMSNorm weights (out = x * (1 + w))
    norm_zero_centered: bool = False
    # the norm in front of a block's sublayers and of the head:
    # "rmsnorm" (a scale) | "layernorm" (mean taken off, a scale and a
    # bias: ``*_norm`` and ``*_norm_b``); models/transformer.py
    # ``block_norm`` is THE place it is read
    block_norm: str = "rmsnorm"
    # Gemma3 extras
    post_norms: bool = False              # post-attn/post-mlp RMSNorm
    embed_scale: bool = False             # embeddings * sqrt(hidden)
    local_rope_theta: Optional[float] = None  # gemma local layers use 10k
    # YaRN RoPE scaling (gpt-oss ships with factor 32 over a 4096-token
    # original window). 0 disables.
    rope_scaling_factor: float = 0.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    # what cos and sin are multiplied by under YaRN, where the published
    # file states it (None: 0.1 ln(factor) + 1)
    rope_attention_factor: Optional[float] = None
    # YaRN on a latent layer (the DeepSeek-V3 family's form): the softmax
    # scale is multiplied by ``(0.1 mscale_all_dim ln(factor) + 1)^2``
    # (0: it is not), and cos and sin by ``rope_attention_factor`` =
    # m(factor, mscale) / m(factor, mscale_all_dim), 1.0 where both are 1
    rope_mscale_all_dim: float = 0.0
    # Manifold-constrained hyper-connections (mHC, arXiv:2512.24880; 1:
    # the plain ``h += f(norm h)``): the residual stream of a token is
    # ``hc_mult`` lanes ``X [n, C]``; every sublayer reads its input as a
    # sigmoid-weighted sum of the lanes, writes its output to each lane
    # under a weight in (0, 2) and mixes the lanes by a 4 x 4 matrix a
    # token that ``hc_sinkhorn_iters`` column-then-row normalisations
    # (``hc_eps`` in each denominator) of ``exp(clip(logits, +-
    # hc_res_clamp))`` make doubly stochastic
    # (models/transformer.py ``hc_sublayer``)
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # activation: "silu" (SwiGLU) | "gelu" (GeGLU) | "swiglu_oss" (clamped)
    # | "relu2" (relu(x)^2: the ungated experts of Nemotron-H)
    activation: str = "silu"
    # head: "lm" | "embedding" (pooled, normalized)
    head: str = "lm"
    # embedding pooling: "mean" | "last" (Qwen3-Embedding pools the
    # final valid token's hidden state, not the mean)
    pooling: str = "mean"
    # chat template key for engine/tokenizer.render_chat
    chat_template: str = "chatml"
    # How SEEDED random weights are drawn where no checkpoint is loaded
    # (models/transformer.py ``init_params``; a model of several layer
    # kinds). Every matrix is normal with variance 1 / fan-in. For the
    # embedding that makes the first block's output fifty times the
    # token's own vector: every row's hidden state is then nearly the
    # same, and a softmax router sends most rows of a batch to the same
    # few experts (PERF.md section 6, PR 34). ``seeded_unit_embedding``
    # draws the embedding's elements at unit variance instead, so that
    # a row's hidden state stays its token's
    seeded_unit_embedding: bool = False
    # seeded weights alone: what a routed expert's second matrix is drawn
    # at, times 1 / (fan-in x router_scale^2). At 1 a sum of ``top_k``
    # independent experts is 1 / sqrt(top_k) of the block's input and ONE
    # selection flipped by a rounding moves it by sqrt(2) / top_k: 0.18
    # at top-8, 0.35 at top-4, where every later router then reads a
    # stream a sixth off and flips in turn (PERF.md section 6, PR 54).
    # 0.5 gives a top-4 layer a top-8 layer's share a selection; the
    # Xing4.0 presets take it, the CPU tests' ``tiny-xing-mhc`` with
    # them, so that the tests draw under the rule the cell runs. No
    # rule by ``moe_top_k`` for every model: that would redraw the
    # weights of cells whose limits were set from readings
    seeded_expert_gain: float = 1.0
    # Generation by diffusion over blocks (SDAR, arXiv:2510.06303; 1: a
    # causal model, one token a row a forward). The attention mask is
    # causal by blocks of ``block_length`` positions: position i sees j
    # iff ``j // block_length <= i // block_length``, in the prefill and
    # in every later forward, and the logits at i are the distribution
    # of the token AT i. A block is generated as ``block_length``
    # ``mask_token_id``s that ``denoising_steps`` forwards (0: as many as
    # the block is long; a request may state fewer) fill in, the
    # positions of a forward chosen by ``remasking``
    # ("low_confidence_dynamic": every position whose drawn token has a
    # probability over ``confidence_threshold``, at least the step's even
    # share; "low_confidence_static": that share, the most confident
    # first; "sequential": leftmost first), and one more forward of the
    # filled block writes its K/V (ops/sampling.py ``transfer``,
    # engine/runner.py ``_decode_block_jit``). Such a model lists its
    # layers' kinds (``layer_types``): the walk by kind reads a routed
    # layer's experts where they lie and counts its routing
    block_length: int = 1
    mask_token_id: int = -1
    denoising_steps: int = 0
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def heads_of(self, mixer: str) -> int:
        """Query heads of an attention layer of kind ``mixer``
        ("attention" | "swa")."""
        if mixer == "swa" and self.window_num_heads:
            return self.window_num_heads
        return self.num_heads

    @property
    def kernel_head_dim(self) -> int:
        """The head the attention kernels' gates and block-diagonal
        products see: a PAIR of heads under differential attention
        (``[k1 | k2]`` lies in a page's row as the write left it)."""
        return self.head_dim * (2 if self.attn_differential else 1)

    def rotary_dim_of(self, mixer: str) -> int:
        """Elements of a head that a layer of kind ``mixer`` turns."""
        if mixer == "swa":
            return self.head_dim
        return self.rotary_dim or self.head_dim

    @property
    def one_sublayer(self) -> bool:
        """``layer_types`` names FFN kinds too: a block is a mixer
        alone or an FFN alone."""
        return any(t in FFN_KINDS for t in self.layer_types)

    @property
    def mixers(self) -> Tuple[str, ...]:
        """Each layer's mixer kind, in order ("none": an FFN alone)."""
        if self.one_sublayer:
            return tuple(
                "none" if t in FFN_KINDS else t for t in self.layer_types
            )
        return self.layer_types or ("attention",) * self.num_layers

    @property
    def ffns(self) -> Tuple[str, ...]:
        """Each layer's FFN kind, "dense" | "moe", in order ("none": a
        mixer alone)."""
        if self.one_sublayer:
            return tuple(
                t if t in FFN_KINDS else "none" for t in self.layer_types
            )
        if not self.moe_experts:
            return ("dense",) * self.num_layers
        d = self.num_dense_layers
        return ("dense",) * d + ("moe",) * (self.num_layers - d)

    @property
    def experts_held(self) -> int:
        """Experts whose weights this chip holds, a routed layer."""
        return self.moe_experts_held or self.moe_experts

    @property
    def homogeneous(self) -> bool:
        """Every layer the same block and no list of kinds: the
        parameters are one stack ``params["layers"][name] [L, ...]`` and
        the walk is one scan. A model that LISTS its layers' kinds
        (``layer_types``) is walked by kind whatever they are."""
        return not self.layer_types and (
            len(set(zip(self.mixers, self.ffns))) == 1
            and self.mixers[0] == "attention"
        )

    @property
    def num_attn_layers(self) -> int:
        """Layers that keep K/V over the whole context: what the page
        pool spans."""
        return self.mixers.count("attention")

    @property
    def num_window_layers(self) -> int:
        """"swa" layers: what the WINDOW page pool spans."""
        return self.mixers.count("swa")

    @property
    def num_latent_layers(self) -> int:
        """"mla" layers: they keep ONE latent row a token, in the page
        pool's place (``page_width``), and no V."""
        return self.mixers.count("mla")

    @property
    def num_pool_layers(self) -> int:
        """Layers the page pool spans: the full attention layers, or the
        latent layers of a model that has those (never both)."""
        return self.num_latent_layers or self.num_attn_layers

    @property
    def latent_width(self) -> int:
        """What a token leaves in the cache a latent layer: its normed
        latent values, then its rotated shared key (the leading
        elements of a pool's row: ``page_width``)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def page_width(self) -> int:
        """Elements one token keeps in a page of one layer of a pool
        (K's, and as many of V where the pool has a V): THE place that
        says it, for the pools' shapes, a page's bytes and the writes.
        A latent row is padded with zeros to whole tiles of 128 lanes
        (576 -> 640): the device keeps a minor axis of 576 in 640 lanes
        anyway, so the padding costs no memory, and over a pool whose
        rows are no whole tiles the compiler copies the pool for every
        scatter once it passes ~3 GB (PERF.md section 6, PR 42)."""
        if self.num_latent_layers:
            return -(-self.latent_width // 128) * 128
        return self.num_kv_heads * self.head_dim

    @property
    def pool_has_values(self) -> bool:
        """The page pool keeps a V pool beside K's (a latent row serves
        both products: one pool)."""
        return not self.num_latent_layers

    @property
    def index_key_width(self) -> int:
        """Elements of the index key a token keeps a latent layer beside
        its latent row (0: the layers have no indexer)."""
        return self.index_head_dim if self.index_topk else 0

    @property
    def pool_row_widths(self) -> Tuple[int, ...]:
        """The width of a token's row in each pool of the page pool's
        layers, all on ONE page table: K and V, a latent row alone, or a
        latent row and an index key. THE place that says what a token of
        a layer keeps: ``kvcache.alloc_cache``, ``write_kv``, a page's
        bytes (the runner's ``_page_bytes_per_device``), ``device_info``
        and the fused window's buffers read it here."""
        if self.pool_has_values:
            return (self.page_width, self.page_width)
        if self.index_key_width:
            return (self.page_width, self.index_key_width)
        return (self.page_width,)

    @property
    def num_cross_layers(self) -> int:
        """"cross" layers: readers of ``kv_source_layer``'s K/V that
        keep none of their own."""
        return self.mixers.count("cross")

    def kv_readers(self, mixer: str) -> int:
        """Layers that read a pool of kind ``mixer`` ("attention" |
        "swa") in a step: the pool's own layers, and for the full pool
        the "cross" layers that read one of them again."""
        if mixer == "swa":
            return self.num_window_layers
        return self.num_pool_layers + self.num_cross_layers

    @property
    def num_kv_layers(self) -> int:
        """Layers with K/V of any kind: a chunk's K/V is stacked
        over them, the full layers first."""
        return (
            self.num_attn_layers + self.num_window_layers
            + self.num_latent_layers
        )

    @property
    def num_conv_layers(self) -> int:
        return self.mixers.count("conv")

    @property
    def conv_state_len(self) -> int:
        """Columns of conv state a sequence keeps a conv layer (K-1)."""
        return max(self.conv_kernel - 1, 0) if self.num_conv_layers else 0

    @property
    def num_mamba_layers(self) -> int:
        return self.mixers.count("mamba")

    @property
    def mamba_inner(self) -> int:
        """Width of a mamba layer's x, z and y (heads x head_dim)."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of a mamba layer's conv: [x | B | C]."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def mamba_conv_len(self) -> int:
        """Conv columns a sequence keeps a mamba layer (taps - 1)."""
        return max(self.mamba_conv - 1, 0) if self.num_mamba_layers else 0

    @property
    def hc_sublayers(self) -> int:
        """Sublayers that each mix the lanes of the residual stream a
        token (a layer's mixer and its FFN: ``hc_mult``); 0 for a model
        of one lane."""
        if self.hc_mult == 1:
            return 0
        both = self.mixers + self.ffns
        return len(both) - both.count("none")

    @property
    def num_kda_layers(self) -> int:
        return self.mixers.count("kda")

    @property
    def num_mamba1_layers(self) -> int:
        return self.mixers.count("mamba1")

    @property
    def kda_inner(self) -> int:
        """Width of a kda layer's q, k, v and o (heads x head_dim)."""
        return self.kda_heads * self.kda_head_dim

    # -- layers that keep a MATRIX state a sequence, in the slot pool ---
    # ONE description for every such kind: the pool's shapes
    # (engine/kvcache.py), a slot's bytes, the runner's report and the
    # fused window's buffers read these and no family's own counts

    @property
    def state_kind(self) -> Optional[str]:
        """The mixer kind whose state lives in the slot pool ("mamba" |
        "kda" | "mamba1"; None: the model keeps no such state). One kind
        a model: ``_check_mixed`` refuses two."""
        return next(
            (k for k in ("mamba", "kda", "mamba1") if k in self.mixers), None
        )

    @property
    def num_state_layers(self) -> int:
        kind = self.state_kind
        return self.mixers.count(kind) if kind else 0

    @property
    def state_rows(self) -> int:
        """The MAJOR axis of a layer's state in its slot: Mamba-2's and
        Mamba-1's N, a delta-rule head's key axis."""
        return {
            "mamba": self.mamba_state, "kda": self.kda_head_dim,
            "mamba1": self.mamba1_state,
        }.get(self.state_kind, 0)

    @property
    def state_inner(self) -> int:
        """The minor axis: heads x a head's channels (value axis)."""
        return {
            "mamba": self.mamba_inner, "kda": self.kda_inner,
            "mamba1": self.mamba1_inner,
        }.get(self.state_kind, 0)

    @property
    def state_conv_dim(self) -> int:
        """Channels of the conv in front of a state layer: Mamba-2's
        [x | B | C], a delta-rule layer's [q | k | v], Mamba-1's x."""
        return {
            "mamba": self.mamba_conv_dim, "kda": 3 * self.kda_inner,
            "mamba1": self.mamba1_inner,
        }.get(self.state_kind, 0)

    @property
    def state_conv_len(self) -> int:
        """Conv columns a sequence keeps a state layer (taps - 1)."""
        taps = {
            "mamba": self.mamba_conv, "kda": self.kda_conv,
            "mamba1": self.mamba1_conv,
        }.get(self.state_kind, 0)
        return max(taps - 1, 0)

    def window_for_layer(self, layer: int) -> int:
        """Per-layer attention window (0 = full); SURVEY §5.7
        long-context. By the layer's kind where the config lists its
        layers, else by ``sliding_pattern``: one per-layer list either
        way (``window_array``)."""
        if self.sliding_window <= 0:
            return 0
        if self.layer_types:
            return self.sliding_window if self.mixers[layer] == "swa" else 0
        if self.sliding_pattern == "none":
            return 0
        if self.sliding_pattern == "alternate":
            return self.sliding_window if layer % 2 == 0 else 0
        if self.sliding_pattern == "gemma":
            return 0 if (layer + 1) % 6 == 0 else self.sliding_window
        return 0

    def window_array(self) -> Tuple[int, ...]:
        return tuple(self.window_for_layer(i) for i in range(self.num_layers))


def _qwen3(name: str, h: int, l: int, nh: int, nkv: int, inter: int,
           hd: int = 128, tie: bool = True, head: str = "lm",
           vocab: int = 151_936) -> ModelConfig:
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=l,
        num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        intermediate_size=inter, qk_norm=True, tie_embeddings=tie,
        rope_theta=1_000_000.0, head=head, chat_template="chatml",
        # Qwen3-Embedding pools the last valid token (model card), not
        # the mean
        pooling="last" if head == "embedding" else "mean",
    )


def _qwen3_moe(name: str, h: int, l: int, nh: int, nkv: int,
               experts: int, top_k: int, moe_inter: int,
               vocab: int = 151_936) -> ModelConfig:
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=l,
        num_heads=nh, num_kv_heads=nkv, head_dim=128,
        intermediate_size=moe_inter, qk_norm=True, tie_embeddings=False,
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter, rope_theta=1_000_000.0,
        chat_template="chatml",
    )


#: generation by blocks: the transfer rules of ``ModelConfig.remasking``,
#: in the order the device program numbers them (ops/sampling.py)
REMASKING = ("low_confidence_static", "low_confidence_dynamic", "sequential")


def _sdar(name: str, base: ModelConfig, *, mask_id: int, block: int = 4,
          inter: Optional[int] = None) -> ModelConfig:
    """The published ``sdar_moe`` keys are Qwen3-MoE's: ``base`` (a
    ``_qwen3_moe`` config) with its layers listed by kind, the block
    mask and the generation by diffusion over a block. ``block`` and
    ``mask_id`` are no keys of the published file (perfbench/reference/
    sdar_moe.md: the family's released commands, the tokenizer's
    ``<|MASK|>``). ``inter``: the published ``intermediate_size``, which
    no layer uses (every FFN is routed). Seeded weights take a
    unit-variance embedding, as every softmax-routed model of the
    benchmark does."""
    return dataclasses.replace(
        base, name=name, layer_types=("attention",) * base.num_layers,
        block_length=block, mask_token_id=mask_id,
        intermediate_size=inter or base.intermediate_size,
        seeded_unit_embedding=True,
    )


def _llama(name: str, h: int, l: int, nh: int, nkv: int, inter: int,
           vocab: int = 128_256, tie: bool = False) -> ModelConfig:
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=l,
        num_heads=nh, num_kv_heads=nkv, head_dim=h // nh,
        intermediate_size=inter, qk_norm=False, tie_embeddings=tie,
        rope_theta=500_000.0, norm_eps=1e-5, chat_template="llama3",
    )


def _gemma3(name: str, h: int, l: int, nh: int, nkv: int, inter: int,
            hd: int, vocab: int = 262_208) -> ModelConfig:
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=l,
        num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        intermediate_size=inter, qk_norm=True, tie_embeddings=True,
        rope_theta=1_000_000.0, local_rope_theta=10_000.0,
        sliding_window=1024, sliding_pattern="gemma", post_norms=True,
        embed_scale=True, activation="gelu", chat_template="gemma",
        norm_zero_centered=True,
    )


def _gpt_oss(name: str, h: int, l: int, nh: int, nkv: int,
             experts: int, top_k: int, moe_inter: int) -> ModelConfig:
    return ModelConfig(
        name=name, vocab_size=201_088, hidden_size=h, num_layers=l,
        num_heads=nh, num_kv_heads=nkv, head_dim=64,
        intermediate_size=moe_inter, qk_norm=False, tie_embeddings=False,
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter, rope_theta=150_000.0,
        sliding_window=128, sliding_pattern="alternate",
        attention_sink=True, attn_bias=True, moe_bias=True,
        activation="swiglu_oss",
        chat_template="chatml",
        rope_scaling_factor=32.0, rope_original_max=4096,
    )


#: LFM2-24B-A2B's published ``layer_types`` (config.json): conv, conv,
#: then (full_attention, conv, conv, conv) nine times, then
#: full_attention, conv
_LFM2_24B_LAYERS: Tuple[str, ...] = (
    ("conv", "conv") + ("attention", "conv", "conv", "conv") * 9
    + ("attention", "conv")
)


def _lfm2_moe(name: str, layer_types: Tuple[str, ...], *, h: int = 2048,
              nh: int = 32, nkv: int = 8, inter: int = 11776,
              experts: int = 64, top_k: int = 4, moe_inter: int = 1536,
              dense_layers: int = 2, vocab: int = 65_536,
              template: str = "chatml") -> ModelConfig:
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h,
        num_layers=len(layer_types), num_heads=nh, num_kv_heads=nkv,
        head_dim=h // nh, intermediate_size=inter, norm_eps=1e-5,
        rope_theta=1_000_000.0, qk_norm=True, tie_embeddings=True,
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter, num_dense_layers=dense_layers,
        router_score="sigmoid", router_select_bias=True,
        router_renorm=True, router_scale=1.0,
        layer_types=layer_types, conv_kernel=3, chat_template=template,
    )


#: granite-4.0-h-micro's published ``layer_types`` (config.json):
#: attention at layers 5, 15, 25 and 35, Mamba-2 everywhere else
_GRANITE_H_MICRO_LAYERS: Tuple[str, ...] = (
    ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
) * 4


def _granite_hybrid(name: str, layer_types: Tuple[str, ...], *,
                    h: int = 2048, nh: int = 32, nkv: int = 8,
                    inter: int = 8192, m_heads: int = 64,
                    m_head_dim: int = 64, m_state: int = 128,
                    m_chunk: int = 256, vocab: int = 100_352,
                    template: str = "chatml") -> ModelConfig:
    """The published ``granitemoehybrid`` keys of a dense member
    (``num_local_experts`` 0: the FFN is the shared SwiGLU alone)."""
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h,
        num_layers=len(layer_types), num_heads=nh, num_kv_heads=nkv,
        head_dim=h // nh, intermediate_size=inter, norm_eps=1e-5,
        rope_theta=10_000.0, qk_norm=False, tie_embeddings=True,
        layer_types=layer_types,
        mamba_heads=m_heads, mamba_head_dim=m_head_dim,
        mamba_state=m_state, mamba_groups=1, mamba_conv=4,
        mamba_chunk=m_chunk,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, attention_multiplier=0.015625,
        position_embedding="nope", chat_template=template,
    )


def sambay_layers(layers: int, mb_per_layer: int = 2) -> Tuple[str, ...]:
    """The mixers of a decoder-hybrid-decoder of ``layers`` layers (the
    published modelling code's rule from ``mb_per_layer`` and the
    half-way split; perfbench/reference/sambay_diff.md): every
    ``mb_per_layer``-th layer up to the middle is Mamba-1, the others
    before it differential attention over a window; the layer after the
    middle attends over everything; behind it gated memory units and
    cross layers alternate."""
    half = layers // 2
    return tuple(
        ("mamba1" if l % mb_per_layer == 0 else "swa") if l <= half
        else "attention" if l == half + 1
        else ("gmu" if l % mb_per_layer == 0 else "cross")
        for l in range(layers)
    )


def _phi4flash(name: str, layers: int = 32, *, h: int = 2560, nh: int = 40,
               nkv: int = 20, inter: int = 10_240, d_state: int = 16,
               dt_rank: int = 0, window: int = 512, chunk: int = 64,
               vocab: int = 200_064, template: str = "chatml") -> ModelConfig:
    """The published ``phi4flash`` keys (hidden, heads, intermediate,
    window, eps, ``mb_per_layer`` 2, a tied head, no MLP bias); what has
    no key is the modelling code's constant: ``d_inner`` twice the
    hidden size, ``d_state`` 16, 4 conv taps, ``dt_rank`` ceil(h / 16),
    heads of ``h / nh``, a softmax scale of ``head_dim ** -0.5``."""
    kinds = sambay_layers(layers)
    half = layers // 2
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=layers,
        num_heads=nh, num_kv_heads=nkv, head_dim=h // nh,
        intermediate_size=inter, norm_eps=1e-5, qk_norm=False,
        tie_embeddings=True, layer_types=kinds,
        mamba1_inner=2 * h, mamba1_state=d_state, mamba1_conv=4,
        mamba1_dt_rank=dt_rank or -(-h // 16), mamba1_chunk=chunk,
        memory_layer=half, kv_source_layer=half + 1,
        attn_differential=True, attn_bias=True, block_norm="layernorm",
        sliding_window=window, position_embedding="nope",
        chat_template=template,
        # seeded weights alone: at 1 / H a token's embedding is a
        # fiftieth of the first block's output, every row's hidden state
        # is nearly the same, and bfloat16 against float32 read 0.03-0.047
        # of the largest logit where the limit is 0.06 (PERF.md section
        # 6, PR 64); at unit variance a row's state stays its token's
        seeded_unit_embedding=True,
    )


#: Mellum2-12B-A2.5B's published ``layer_types`` (config.json):
#: sliding_attention x 3, full_attention, seven times
_MELLUM2_LAYERS: Tuple[str, ...] = ("swa", "swa", "swa", "attention") * 7


def _mellum2(name: str, layer_types: Tuple[str, ...], *, h: int = 2304,
             nh: int = 32, nkv: int = 4, hd: int = 128, inter: int = 7168,
             experts: int = 64, top_k: int = 8, moe_inter: int = 896,
             window: int = 1024, rope_original: int = 8192,
             vocab: int = 98_304, template: str = "chatml") -> ModelConfig:
    """The published ``mellum`` keys: Qwen3-MoE's block (QK-norm, softmax
    router renormalised over the chosen, no shared expert; every layer
    routed, so ``intermediate_size`` is unused) with ``layer_types`` of
    sliding and full attention and a rotary embedding a kind: YaRN
    (factor 16 over ``rope_original``, attention factor as stated) on
    the full layers, plain on the window layers, theta 500,000 both."""
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h,
        num_layers=len(layer_types), num_heads=nh, num_kv_heads=nkv,
        head_dim=hd, intermediate_size=inter, norm_eps=1e-6,
        qk_norm=True, tie_embeddings=False,
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter,
        rope_theta=500_000.0, local_rope_theta=500_000.0,
        rope_scaling_factor=16.0, rope_original_max=rope_original,
        rope_attention_factor=1.2772588722239782,
        sliding_window=window, layer_types=layer_types,
        chat_template=template, seeded_unit_embedding=True,
    )


def _laguna(name: str, layers: int = 48, *, h: int = 3072, nh: int = 48,
            window_nh: int = 72, nkv: int = 8, hd: int = 128,
            rotary: int = 64, inter: int = 12_288, experts: int = 256,
            top_k: int = 10, moe_inter: int = 1024, held: int = 0,
            first: int = 0, window: int = 512, rope_original: int = 8192,
            factor: float = 128.0,
            attention_factor: Optional[float] = 1.4852030263919618,
            vocab: int = 100_352, token_tile: int = 4096,
            template: str = "chatml") -> ModelConfig:
    """The published ``laguna`` keys: layer ``l`` is full attention iff
    ``l % 4 == 0`` (``layer_types``), else sliding over ``window``;
    ``num_attention_heads_per_layer`` gives a full layer ``nh`` query
    heads and a window layer ``window_nh`` over the same ``nkv`` KV
    heads; ``gating`` per-head; ``rope_parameters`` by kind: YaRN
    (``factor`` over ``rope_original``, theta 500,000, ``attention_factor``
    as the file states it; None: 0.1 ln(factor) + 1) over the first ``rotary`` elements of a head in the full
    layers (``partial_rotary_factor`` 0.5), the plain embedding at
    theta 10,000 over the whole head in the window layers. Layer 0's
    FFN is dense (``mlp_only_layers`` [0]); the others route by softmax,
    the chosen over their sum (``norm_topk_prob``) times
    ``moe_routed_scaling_factor`` 2.5, beside ONE shared expert of an
    expert's width under a sigmoid gate of its own. QK norm, the gate's
    form, the softmax and the shared expert's gate are assumed
    (perfbench/reference/laguna_moe.md). ``held`` / ``first``: the
    experts this chip holds of each routed layer (0: all);
    ``token_tile``: ``ModelConfig.moe_token_tile``."""
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=layers,
        num_heads=nh, window_num_heads=window_nh, num_kv_heads=nkv,
        head_dim=hd, intermediate_size=inter, norm_eps=1e-6,
        qk_norm=True, tie_embeddings=False, attn_gate="head",
        layer_types=tuple(
            "attention" if i % 4 == 0 else "swa" for i in range(layers)
        ),
        rope_theta=500_000.0, local_rope_theta=10_000.0,
        rotary_dim=rotary,
        rope_scaling_factor=factor, rope_original_max=rope_original,
        rope_attention_factor=attention_factor,
        sliding_window=window,
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter, num_dense_layers=1,
        moe_experts_held=held, moe_first_expert=first,
        moe_shared_intermediate_size=moe_inter, moe_shared_gate=True,
        router_score="softmax", router_renorm=True, router_scale=2.5,
        moe_token_tile=token_tile,
        chat_template=template, seeded_unit_embedding=True,
    )


#: NVIDIA-Nemotron-3-Nano-30B-A3B's published
#: ``hybrid_override_pattern`` (config.json): a block a symbol
_NEMOTRON_3_NANO_PATTERN = (
    "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
)
_NEMOTRON_H_SYMBOLS = {
    "M": "mamba", "*": "attention", "E": "moe", "-": "dense",
}


def _nemotron_h(name: str, pattern: str, *, h: int = 2688, nh: int = 32,
                nkv: int = 2, hd: int = 128, m_heads: int = 64,
                m_head_dim: int = 64, m_state: int = 128, m_groups: int = 8,
                m_chunk: int = 128, experts: int = 128, top_k: int = 6,
                moe_inter: int = 1856, shared_inter: int = 3712,
                held: int = 0, first: int = 0, vocab: int = 131_072,
                template: str = "chatml") -> ModelConfig:
    """The published ``nemotron_h`` keys: ``pattern`` a block a symbol
    (M Mamba-2, * attention, E routed FFN), each block ONE sublayer
    under one norm. Mamba-2 with ``m_groups`` groups of B and C (the
    gated norm a group at a time), inner width heads x head_dim; GQA
    with no rotary embedding; a sigmoid router with a selection bias,
    the chosen scores over their sum (+1e-20) times 2.5, experts of two
    matrices under relu^2 beside one shared expert. ``held`` /
    ``first``: the experts this chip holds of each layer (0: all)."""
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h,
        num_layers=len(pattern), num_heads=nh, num_kv_heads=nkv,
        head_dim=hd, intermediate_size=moe_inter, norm_eps=1e-5,
        rope_theta=10_000.0, qk_norm=False, tie_embeddings=False,
        layer_types=tuple(_NEMOTRON_H_SYMBOLS[c] for c in pattern),
        mamba_heads=m_heads, mamba_head_dim=m_head_dim,
        mamba_state=m_state, mamba_groups=m_groups, mamba_conv=4,
        mamba_chunk=m_chunk, position_embedding="nope",
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter,
        moe_experts_held=held, moe_first_expert=first,
        moe_gated=False, moe_shared_intermediate_size=shared_inter,
        activation="relu2",
        router_score="sigmoid", router_select_bias=True,
        router_renorm=True, router_scale=2.5, router_renorm_eps=1e-20,
        chat_template=template, seeded_unit_embedding=True,
    )


def _latent_moe(name: str, layers: int = 40, *, h: int = 2048, nh: int = 32,
                q_rank: int = 1536, kv_rank: int = 512, nope: int = 128,
                rope: int = 64, v_dim: int = 128, inter: int = 7168,
                experts: int = 256, top_k: int = 8, moe_inter: int = 768,
                dense_layers: int = 1, held: int = 0, first: int = 0,
                vocab: int = 129_280, theta: float = 32_000_000.0,
                eps: float = 1e-6, index: Tuple[int, int, int] = (0, 0, 0),
                peaked: float = 0.0, share_rows: int = 2,
                router_scale: float = 2.5, expert_gain: float = 1.0,
                yarn: Tuple[float, int, float, float] = (0.0, 0, 1.0, 1.0),
                hc_mult: int = 1,
                template: str = "chatml") -> ModelConfig:
    """DeepSeek-V3's key set (defaults: the published ``joyai_llm_flash``
    file): latent attention in every layer, rotary ``theta`` on the
    ``rope`` part alone in interleaved pairs, no scaling;
    ``dense_layers`` leading dense SwiGLU layers, then a sigmoid router
    with a selection bias (``noaux_tc``; ``n_group`` 1), the chosen
    scores over their sum (+1e-20) times 2.5, gated experts beside ONE
    shared expert of an expert's width. ``head_dim`` is the file's (the
    rotary part) and ``num_kv_heads`` its ``nh``: neither sizes anything
    here. ``held`` / ``first``: the experts this chip holds of each layer
    (0: all). ``index`` = (``index_n_heads``, ``index_head_dim``,
    ``index_topk``) of ``glm_moe_dsa``'s indexer (zeros: none);
    ``peaked``: ``ModelConfig.seeded_peaked_attention``; ``share_rows``:
    ``ModelConfig.moe_share_rows``; ``expert_gain``:
    ``ModelConfig.seeded_expert_gain``. ``yarn`` = (``factor``,
    ``original_max_position_embeddings``, ``mscale``, ``mscale_all_dim``)
    of a ``rope_scaling`` of type yarn at the published ``beta_fast`` 32
    and ``beta_slow`` 1 (factor 0: none); ``hc_mult``: the lanes of the
    residual stream (``xing4_0``: 4, at the published 20 Sinkhorn passes,
    eps 1e-6 and clamp 30). The
    multi-token-prediction block (``num_nextn_predict_layers`` 1) is no
    part of the next-token logits and is not built."""
    factor, original, mscale, all_dim = yarn

    def m(a: float) -> float:
        return 0.1 * a * math.log(factor) + 1.0

    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=layers,
        num_heads=nh, num_kv_heads=nh, head_dim=rope,
        intermediate_size=inter, norm_eps=eps, rope_theta=theta,
        qk_norm=False, tie_embeddings=False,
        layer_types=("mla",) * layers,
        rope_scaling_factor=factor, rope_original_max=original,
        rope_attention_factor=m(mscale) / m(all_dim) if factor else None,
        rope_mscale_all_dim=all_dim if factor else 0.0,
        hc_mult=hc_mult,
        q_lora_rank=q_rank, kv_lora_rank=kv_rank, qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=v_dim, rope_interleave=True,
        index_n_heads=index[0], index_head_dim=index[1],
        index_topk=index[2], seeded_peaked_attention=peaked,
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter, num_dense_layers=dense_layers,
        moe_experts_held=held, moe_first_expert=first,
        moe_share_rows=share_rows,
        moe_shared_intermediate_size=moe_inter,
        router_score="sigmoid", router_select_bias=True,
        router_renorm=True, router_scale=router_scale,
        router_renorm_eps=1e-20,
        chat_template=template, seeded_unit_embedding=True,
        seeded_expert_gain=expert_gain,
    )


def _solar_kda(name: str, layers: int = 48, *, h: int = 4096, nh: int = 64,
               nkv: int = 8, hd: int = 128, k_heads: int = 64,
               k_head_dim: int = 128, k_rank: int = 0, k_chunk: int = 64,
               inter: int = 10_240, experts: int = 320, top_k: int = 8,
               moe_inter: int = 1280, held: int = 0, first: int = 0,
               vocab: int = 196_608, template: str = "chatml") -> ModelConfig:
    """The published ``solar_open2`` keys: layer ``i`` is softmax GQA iff
    ``i % 4 == 0`` (``gqa_layers``; no rotary embedding, an output gate a
    channel), Kimi Delta Attention otherwise (``kda_use_full_proj``
    false: the decay's and the gate's projections are pairs of rank
    ``k_rank``, 0: the head's width; ``kda_allow_neg_eigval``: beta
    doubled; a 4-tap conv); every FFN routed (sigmoid scores, a selection
    bias, the chosen scores over their sum (+1e-20) times 1) beside ONE
    shared expert of an expert's width. ``intermediate_size`` is read by
    no layer (``first_k_dense_replace`` 0). ``held`` / ``first``: the
    experts this chip holds of each layer (0: all)."""
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=h, num_layers=layers,
        num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        intermediate_size=inter, norm_eps=1e-5, rope_theta=10_000.0,
        qk_norm=False, tie_embeddings=False,
        layer_types=tuple(
            "attention" if i % 4 == 0 else "kda" for i in range(layers)
        ),
        position_embedding="nope", attn_gate="channel",
        kda_heads=k_heads, kda_head_dim=k_head_dim, kda_conv=4,
        kda_rank=k_rank or k_head_dim, kda_beta_scale=2.0,
        kda_chunk=k_chunk,
        moe_experts=experts, moe_top_k=top_k,
        moe_intermediate_size=moe_inter,
        moe_experts_held=held, moe_first_expert=first,
        moe_shared_intermediate_size=moe_inter,
        router_score="sigmoid", router_select_bias=True,
        router_renorm=True, router_scale=1.0, router_renorm_eps=1e-20,
        chat_template=template, seeded_unit_embedding=True,
    )


#: GLM-5's published widths (``glm_moe_dsa``): 64 heads, nope 192 | rope
#: 64, V 256, an indexer of 32 heads of 128 that keeps 2,048 positions
_GLM5 = dict(
    h=6144, nh=64, q_rank=2048, kv_rank=512, nope=192, rope=64, v_dim=256,
    inter=12_288, experts=256, top_k=8, moe_inter=2048, theta=1_000_000.0,
    eps=1e-5, index=(32, 128, 2048), peaked=1.5,
    # four routed layers and a byte tokenizer's 28 distinct tokens (and a
    # bucket's padding, all one token): seeded routers are uneven enough
    # that twice the even share overflowed in one layer of four a seed,
    # and the rate took three levels 5 % apart (PERF.md section 6, PR 46)
    share_rows=4,
)

#: the published ``xing4_0`` file: hidden 3,584, 32 heads, a query latent of
#: 768, two leading dense layers of 9,216, 64 experts of 1,024 top-4 (the
#: chosen scores over their sum times 2), rotary base 10,000 under YaRN
#: (factor 64 over 4,096 positions, mscale 1 = mscale_all_dim 1), four lanes
_XING4 = dict(
    h=3584, nh=32, q_rank=768, kv_rank=512, nope=128, rope=64, v_dim=128,
    inter=9216, experts=64, top_k=4, moe_inter=1024, dense_layers=2,
    vocab=131_072, theta=10_000.0, router_scale=2.0, expert_gain=0.5,
    yarn=(64.0, 4096, 1.0, 1.0), hc_mult=4,
)


MODEL_CONFIGS: Dict[str, ModelConfig] = {
    # Qwen3 dense
    "qwen3-0.6b": _qwen3("qwen3-0.6b", 1024, 28, 16, 8, 3072),
    "qwen3-4b": _qwen3("qwen3-4b", 2560, 36, 32, 8, 9728),
    "qwen3-8b": _qwen3("qwen3-8b", 4096, 36, 32, 8, 12288, tie=False),
    "qwen3-14b": _qwen3("qwen3-14b", 5120, 40, 40, 8, 17408, tie=False),
    "qwen3-32b": _qwen3("qwen3-32b", 5120, 64, 64, 8, 25600, tie=False),
    # Qwen3 MoE
    "qwen3-30b-a3b": _qwen3_moe("qwen3-30b-a3b", 2048, 48, 32, 4, 128, 8, 768),
    "qwen3-235b-a22b": _qwen3_moe("qwen3-235b-a22b", 4096, 94, 64, 4, 128, 8, 1536),
    # SDAR-30B-A3B-Chat: as published (30,532,122,624 parameters), and
    # the first of eight pipeline stages: layers 0-5, every expert, the
    # whole vocabulary, with the final norm and the head so that a token
    # can be sampled (4,361,055,744 parameters, 8.72 GB in bf16)
    "sdar-30b-a3b-chat": _sdar(
        "sdar-30b-a3b-chat",
        _qwen3_moe("sdar", 2048, 48, 32, 4, 128, 8, 768), mask_id=151_669,
        inter=6144,
    ),
    "sdar-30b-a3b-chat-l6": _sdar(
        "sdar-30b-a3b-chat-l6",
        _qwen3_moe("sdar", 2048, 6, 32, 4, 128, 8, 768), mask_id=151_669,
        inter=6144,
    ),
    # Llama
    "llama-3.2-3b": _llama("llama-3.2-3b", 3072, 28, 24, 8, 8192, tie=True),
    "llama-3.1-8b": _llama("llama-3.1-8b", 4096, 32, 32, 8, 14336),
    "llama-3.3-70b": _llama("llama-3.3-70b", 8192, 80, 64, 8, 28672),
    # Gemma 3
    "gemma3-4b": _gemma3("gemma3-4b", 2560, 34, 8, 4, 10240, 256),
    "gemma3-12b": _gemma3("gemma3-12b", 3840, 48, 16, 8, 15360, 256),
    "gemma3-27b": _gemma3("gemma3-27b", 5376, 62, 32, 16, 21504, 128),
    # gpt-oss
    "gpt-oss-20b": _gpt_oss("gpt-oss-20b", 2880, 24, 64, 8, 32, 4, 2880),
    "gpt-oss-120b": _gpt_oss("gpt-oss-120b", 2880, 36, 64, 8, 128, 4, 2880),
    # LFM2-MoE: as published, and its first ten layers (both dense
    # layers and two whole periods: what one 16 GB chip holds in bf16
    # with every expert of its 8 routed layers)
    "lfm2-24b-a2b": _lfm2_moe("lfm2-24b-a2b", _LFM2_24B_LAYERS),
    "lfm2-24b-a2b-l10": _lfm2_moe(
        "lfm2-24b-a2b-l10", _LFM2_24B_LAYERS[:10]
    ),
    # Granite 4.0-H: Mamba-2 + NoPE GQA, dense; whole on one v5e
    "granite-4.0-h-micro": _granite_hybrid(
        "granite-4.0-h-micro", _GRANITE_H_MICRO_LAYERS
    ),
    # Phi-4-mini-flash-reasoning: whole (7.71 GB in bf16, one v5e)
    "phi-4-mini-flash-reasoning": _phi4flash("phi-4-mini-flash-reasoning"),
    # Mellum 2: as published (24.3 GB in bf16), and its first eight
    # layers (two whole periods, every expert: 7.6 GB, one v5e)
    "mellum2-12b-a2.5b": _mellum2("mellum2-12b-a2.5b", _MELLUM2_LAYERS),
    "mellum2-12b-a2.5b-l8": _mellum2(
        "mellum2-12b-a2.5b-l8", _MELLUM2_LAYERS[:8]
    ),
    # Nemotron 3 Nano: as published (31.6 B parameters), and the first
    # pipeline stage's share on one of the two chips that divide each
    # layer: the first 14 blocks (two whole units of MEMEM*E), experts
    # 0-63 of each layer's 128, rows 0-65,535 of the vocabulary
    # (4.58 B parameters, 9.17 GB in bf16)
    "nemotron-3-nano-30b-a3b": _nemotron_h(
        "nemotron-3-nano-30b-a3b", _NEMOTRON_3_NANO_PATTERN
    ),
    "nemotron-3-nano-30b-a3b-l14-ep2": _nemotron_h(
        "nemotron-3-nano-30b-a3b-l14-ep2", _NEMOTRON_3_NANO_PATTERN[:14],
        held=64, first=0, vocab=65_536,
    ),
    # JoyAI-LLM-Flash: as published (48.9 B parameters without its
    # multi-token-prediction block), and one chip of the sixteen that
    # share every layer: all 40 layers, the whole vocabulary, experts
    # 0-15 of each routed layer's 256 (4.78 B parameters, 9.55 GB in
    # bf16)
    "joyai-llm-flash": _latent_moe("joyai-llm-flash"),
    "joyai-llm-flash-ep16": _latent_moe(
        "joyai-llm-flash-ep16", held=16, first=0,
    ),
    # GLM-5: as published (743.9 B parameters without its
    # multi-token-prediction block), and one chip of the sixteen that
    # share every layer of the FIRST pipeline stage: a leading dense
    # layer and four routed layers, experts 0-15 of each routed layer's
    # 256, rows 0-19,359 of the vocabulary (3.91 B parameters, 7.82 GB)
    "glm-5": _latent_moe(
        "glm-5", 78, dense_layers=3, vocab=154_880, **_GLM5,
    ),
    "glm-5-l5-ep16": _latent_moe(
        "glm-5-l5-ep16", 5, dense_layers=1, held=16, first=0,
        vocab=19_360, **_GLM5,
    ),
    # Xing4.0-29B-A4B: as published (29,505,505,264 parameters without
    # its multi-token-prediction block), and the FIRST of six v5e
    # pipeline stages: layers 0-6 (both dense layers and five routed
    # ones), every expert, the whole vocabulary (4,920,866,746
    # parameters, 9.84 GB in bf16)
    "xing4.0-29b-a4b": _latent_moe("xing4.0-29b-a4b", **_XING4),
    "xing4.0-29b-a4b-l7": _latent_moe("xing4.0-29b-a4b-l7", 7, **_XING4),
    # Solar-Open2-250B: as published (250.3 B parameters), and one chip
    # of the sixteen that share every layer of the FIRST of six pipeline
    # stages: layers 0-7 (2 GQA + 6 KDA), experts 0-19 of each layer's
    # 320, rows 0-24,575 of the vocabulary (3.90 B parameters, 7.80 GB)
    "solar-open2-250b": _solar_kda("solar-open2-250b"),
    "solar-open2-250b-l8-ep16": _solar_kda(
        "solar-open2-250b-l8-ep16", 8, held=20, first=0, vocab=24_576,
    ),
    # Laguna S 2.1: as published (117.7 B parameters), and one chip of
    # the eight that share every layer of the FIRST of six pipeline
    # stages: layers 0-8 (the dense layer and two whole periods: 3 full
    # + 6 window layers), experts 0-31 of each routed layer's 256, rows
    # 0-12,543 of the vocabulary (3.20 B parameters, 6.40 GB in bf16)
    "laguna-s-2.1": _laguna("laguna-s-2.1"),
    "laguna-s-2.1-l9-ep8": _laguna(
        "laguna-s-2.1-l9-ep8", 9, held=32, first=0, vocab=12_544,
    ),
    # Embeddings (Qwen3 trunk + last-token-pool head)
    "qwen3-emb-0.6b": _qwen3("qwen3-emb-0.6b", 1024, 28, 16, 8, 3072, head="embedding"),
    "qwen3-emb-6b": _qwen3("qwen3-emb-6b", 4096, 36, 32, 8, 12288, tie=False, head="embedding"),
    "qwen3-emb-8b": _qwen3("qwen3-emb-8b", 4096, 36, 32, 8, 12288, tie=False, head="embedding"),
    # Tiny configs for tests / CI (CPU-friendly; byte-level vocab)
    "tiny-dense": ModelConfig(
        name="tiny-dense", vocab_size=512, hidden_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32, intermediate_size=256,
        qk_norm=True, chat_template="plain",
    ),
    "tiny-moe": ModelConfig(
        name="tiny-moe", vocab_size=512, hidden_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32, intermediate_size=256,
        moe_experts=4, moe_top_k=2, moe_intermediate_size=128,
        qk_norm=True, tie_embeddings=False, chat_template="plain",
    ),
    "tiny-oss": ModelConfig(
        name="tiny-oss", vocab_size=512, hidden_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32, intermediate_size=256,
        moe_experts=4, moe_top_k=2, moe_intermediate_size=128,
        moe_bias=True,
        attention_sink=True, sliding_window=8, sliding_pattern="alternate",
        tie_embeddings=False, activation="swiglu_oss", chat_template="plain",
    ),
    "tiny-lfm2": _lfm2_moe(
        "tiny-lfm2",
        ("conv", "conv", "attention", "conv", "conv", "conv"),
        h=128, nh=4, nkv=2, inter=256, experts=16, top_k=4, moe_inter=64,
        vocab=512, template="plain",
    ),
    # a chunk of 8 so that short test prompts cross chunk boundaries
    "tiny-granite": _granite_hybrid(
        "tiny-granite",
        ("mamba", "mamba", "attention", "mamba", "mamba", "attention",
         "mamba"),
        h=128, nh=4, nkv=2, inter=256, m_heads=8, m_head_dim=32,
        m_state=16, m_chunk=8, vocab=512, template="plain",
    ),
    # window 8 at a test's page size of 4, YaRN over an original 16, so
    # that a short test crosses the window, a page and a release
    "tiny-mellum2": _mellum2(
        "tiny-mellum2", ("swa", "swa", "swa", "attention"),
        h=128, nh=4, nkv=2, hd=32, inter=256, experts=8, top_k=2,
        moe_inter=64, window=8, rope_original=16, vocab=512,
        template="plain",
    ),
    # the published 7-block unit twice; 2 groups of B and C, 4 query
    # heads a KV head, 8 experts top-2 of which this chip holds 4 (the
    # ragged path: more than ops/moe.py's dense path takes)
    "tiny-nemotron-h": _nemotron_h(
        "tiny-nemotron-h", "MEMEM*E" * 2, h=128, nh=8, nkv=2, hd=32,
        m_heads=8, m_head_dim=32, m_state=16, m_groups=2, m_chunk=8,
        experts=8, top_k=2, moe_inter=48, shared_inter=96, held=4,
        first=0, vocab=512, template="plain",
    ),
    # a leading dense layer, then three routed layers; every latent
    # width small, unlike every other and unlike head_dim (8) and
    # num_kv_heads * head_dim (32): query rank 24, latent 40 + a rotary
    # key of 8 (a page row of 48), nope 16, v 20; 16 experts top-4 of
    # which this chip holds 4 (a rank of four)
    "tiny-joyai": _latent_moe(
        "tiny-joyai", 4, h=128, nh=4, q_rank=24, kv_rank=40, nope=16,
        rope=8, v_dim=20, inter=256, experts=16, top_k=4, moe_inter=48,
        held=4, first=0, vocab=512, template="plain",
    ),
    # the same under learned sparse attention, every width unlike
    # tiny-joyai's and each other's: query rank 28, latent 36 + a rotary
    # key of 8 (a page row of 44 in 128 lanes), nope 12, v 20 (unlike
    # nope), an indexer of 3 heads of 24 that keeps 8 positions (the
    # tests' prompts are 20-60 tokens: the selection bites)
    "tiny-glm-dsa": _latent_moe(
        "tiny-glm-dsa", 4, h=96, nh=4, q_rank=28, kv_rank=36, nope=12,
        rope=8, v_dim=20, inter=192, experts=16, top_k=4, moe_inter=40,
        held=4, first=0, vocab=512, theta=1_000_000.0, eps=1e-5,
        index=(3, 24, 8), peaked=1.5, template="plain",
    ),
    # a four-lane residual stream round latent attention under YaRN
    # (factor 8 over an original window of 32: the tests' 20-60 token
    # prompts lie on both sides of it), a dense layer and three routed
    # ones, every one of 8 experts held; widths unlike tiny-joyai's
    "tiny-xing-mhc": _latent_moe(
        "tiny-xing-mhc", 4, h=128, nh=4, q_rank=20, kv_rank=40, nope=12,
        rope=8, v_dim=16, inter=192, experts=8, top_k=2, moe_inter=48,
        vocab=512, theta=10_000.0, router_scale=2.0, expert_gain=0.5,
        yarn=(8.0, 32, 1.0, 1.0), hc_mult=4, template="plain",
    ),
    # one period and a half (GQA, 3 KDA, GQA, KDA); 4 delta-rule heads of
    # 16 (unlike the attention's 4 heads of 32 over 2 KV heads), pairs of
    # rank 8, a chunk of 8 so that short prompts cross chunk edges; 16
    # experts top-4 of which this chip holds 4
    "tiny-solar-kda": _solar_kda(
        "tiny-solar-kda", 6, h=128, nh=4, nkv=2, hd=32, k_heads=4,
        k_head_dim=16, k_rank=8, k_chunk=8, inter=256, experts=16,
        top_k=4, moe_inter=48, held=4, first=0, vocab=512,
        template="plain",
    ),
    # two periods and a layer (full, 3 window, full, 3 window, full): 4
    # query heads in a full layer and 6 in a window layer over 2 KV heads
    # (groups of 2 and 3), heads of 16 of which a full layer turns 8
    # under YaRN (factor 8 over an original 16), window 8 at a test's
    # page size of 4; a leading dense layer, then 16 experts top-3 of
    # which this chip holds 4, beside the gated shared expert
    "tiny-laguna": _laguna(
        "tiny-laguna", 9, h=64, nh=4, window_nh=6, nkv=2, hd=16, rotary=8,
        inter=128, experts=16, top_k=3, moe_inter=32, held=4, first=0,
        window=8, rope_original=16, factor=8.0, attention_factor=None,
        vocab=512, token_tile=16,
        template="plain",
    ),
    # eight layers by the same rule (0, 2, 4 Mamba-1; 1, 3 window; 5
    # full; 6 memory unit; 7 cross): 8 query and 4 KV heads of 8, so 4
    # differential heads over 2 KV pairs; window 8 at a test's page size
    # of 4; a scan chunk of 8 so that short prompts cross chunk edges
    "tiny-phi4flash": _phi4flash(
        "tiny-phi4flash", 8, h=64, nh=8, nkv=4, inter=128, d_state=4,
        dt_rank=4, window=8, chunk=8, vocab=512, template="plain",
    ),
    # Qwen3-MoE's layer at tiny widths under the block mask: blocks of 4,
    # 8 experts top-2 all held, three layers; id 300 is the mask (the
    # byte tokenizer sends 0-255 and its specials, never 300)
    "tiny-sdar": _sdar(
        "tiny-sdar",
        ModelConfig(
            name="tiny-sdar", vocab_size=512, hidden_size=128, num_layers=3,
            num_heads=4, num_kv_heads=2, head_dim=32, intermediate_size=64,
            moe_experts=8, moe_top_k=2, moe_intermediate_size=64,
            qk_norm=True, tie_embeddings=False, chat_template="plain",
        ),
        mask_id=300,
    ),
    "tiny-emb": ModelConfig(
        name="tiny-emb", vocab_size=512, hidden_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32, intermediate_size=256,
        qk_norm=True, head="embedding", chat_template="plain",
    ),
}
