"""A model whose blocks are ONE sublayer each and whose routed blocks
hold one rank's share of the experts (tiny-nemotron-h: the published
unit ``MEMEM*E`` twice; Mamba-2 with 2 groups of B and C, 4 query heads
a KV head, 8 experts top-2 of which 4 are held, a shared expert,
two-matrix relu^2 experts) on every path that walks the blocks, against
the plain float32 reference (``perfbench/reference/nemotron_h_moe.py``:
the recurrence one token at a time, the experts one at a time, no sort)
on seeded random weights.

What is compared is LOGITS (or the log-probability the program reports
for a token, against the reference's at that position). Tolerance 2e-4
of the largest logit: both sides compute in float32 and differ in
summation order only (measured: about 1e-6). bfloat16 PARAMETERS under
the same float32 program read 5e-3 and more
(``test_bfloat16_parameters_under_a_float32_configuration_fail``), and
the reference with one term changed 1e-2 and more.
"""

import dataclasses
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import nemotron_h_moe
from perfbench.sut import System
from sutro_tpu import telemetry
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import moe

TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-nemotron-h"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-nemotron-h-cpu.json").read_text()
)
from tests import window_buffers_common
PS, MP = 8, 16


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=20, seed=11,
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine())


@pytest.fixture(autouse=True)
def every_slot_free(runner):
    runner.pools.reset()


@pytest.fixture(scope="module")
def step(runner):
    """One decode step of given tokens through the cache, as
    ``perfbench/sut.py`` takes it: logits [B, V], the cache committed."""

    @jax.jit
    def jitted(params, cache, tok, past_len, page_table):
        logits, _, (k, v) = runner._trunk_decode(
            params, cache, tok[:, None], past_len[:, None], past_len, page_table
        )
        cache = write_kv(cache, k, v, page_table, past_len,
                         jnp.ones_like(past_len))
        return logits[:, 0].astype(jnp.float32), cache

    def run(tokens, positions, tables):
        logits, runner.cache = jitted(
            runner.params, runner.cache,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
            jnp.asarray(np.atleast_2d(tables), jnp.int32),
        )
        return np.asarray(logits)

    return run


def table_of(*pages):
    t = np.zeros((MP,), np.int32)
    t[: len(pages)] = pages
    return t


def sequence(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def want(runner, seq, positions, params=None, **kw):
    return np.asarray(nemotron_h_moe.logits_at(
        KEYS, runner.params if params is None else params, seq,
        list(positions), **kw
    ))


def err(got, wanted):
    return float(np.max(correctness.position_errors(got, wanted)))


# -- the shape of the thing ------------------------------------------------------

def test_the_preset_is_the_published_unit_twice_and_the_walk_scans_it():
    assert MCFG.one_sublayer and not MCFG.homogeneous
    assert MCFG.mixers == ("mamba", "none", "mamba", "none", "mamba",
                           "attention", "none") * 2
    assert MCFG.ffns == ("none", "moe", "none", "moe", "none", "none",
                         "moe") * 2
    assert transformer.layer_groups(MCFG) == [(0, 7, 2)]
    assert MCFG.mamba_groups >= 2 and MCFG.num_heads // MCFG.num_kv_heads >= 4
    assert (MCFG.moe_experts, MCFG.moe_top_k, MCFG.experts_held) == (8, 2, 4)
    for name in ("nemotron-3-nano-30b-a3b", "nemotron-3-nano-30b-a3b-l14-ep2"):
        cfg = MODEL_CONFIGS[name]
        groups = transformer.layer_groups(cfg)
        # the first group is the unit of seven, scanned
        assert groups[0][1] == 7 and groups[0][2] >= 2
        assert sum(p * r for _, p, r in groups) == cfg.num_layers
    cut = MODEL_CONFIGS["nemotron-3-nano-30b-a3b-l14-ep2"]
    assert (cut.num_mamba_layers, cut.num_attn_layers,
            cut.ffns.count("moe")) == (6, 2, 6)
    assert cut.num_heads // cut.num_kv_heads == 16


def test_a_block_has_one_norm_and_the_stacks_hold_the_held_experts(runner):
    layers = runner.params["layers"]
    assert set(layers) == {"mamba", "attn", "moe"}
    assert "mlp_norm" not in layers["mamba"] and "mlp_norm" not in layers["attn"]
    assert "attn_norm" not in layers["moe"] and "we_gate" not in layers["moe"]
    moe_l = layers["moe"]
    assert moe_l["router"].shape[-1] == MCFG.moe_experts
    assert "we_up" not in moe_l      # the first matrix lies output-major
    assert moe_l["we_up_t"].shape == moe_l["we_down"].shape
    assert moe_l["we_up_t"].shape[1] == MCFG.experts_held
    assert moe_l["shared_up"].shape[-1] == MCFG.moe_shared_intermediate_size


# -- (a) prefill, rows of different lengths in one padded batch ----------------

def test_prefill_alone_and_rows_of_different_lengths_in_one_padded_batch(runner):
    seq = sequence(0, 19)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner, seq, [18])[0]) < TOL
    rows = [sequence(1, 9), sequence(2, 17), sequence(3, 12)]
    tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(7, 8)])
    got = runner.prefill_batch(rows, tables)
    for g, row in zip(got, rows):
        assert err(g, want(runner, row, [len(row) - 1])[0]) < TOL


# -- (c) prefill, then single steps through the cache --------------------------

def _system(runner):
    sut = object.__new__(System)
    sut.ecfg, sut.engine_key = runner.ecfg, MCFG.name
    sut.engine = types.SimpleNamespace(
        _runner_cache={MCFG.name: (runner, None)}
    )
    return sut


def test_prefill_then_eight_decode_steps_as_the_benchmark_takes_them(runner):
    ids = np.stack([sequence(s, 27) for s in (4, 5, 6)])
    got = _system(runner).logits_through_cache(ids, 19, 8)
    assert got.shape == (3, 9, MCFG.vocab_size)
    for g, seq in zip(got, ids):
        assert err(g, want(runner, seq, range(18, 27))) < TOL


def test_bfloat16_parameters_under_a_float32_configuration_fail(runner):
    """The tolerance's teeth: the same float32 program on parameters
    rounded to bfloat16 is over it, against the reference on the
    parameters as they were."""
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
        if a.dtype == jnp.float32 and a.ndim >= 2 else a,
        runner.params,
    )
    low = ModelRunner(
        dataclasses.replace(MCFG, name="tiny-nemotron-h: rounded"), engine(),
        params=rounded,
    )
    ids = np.stack([sequence(s, 27) for s in (4, 5, 6)])
    got = _system(low).logits_through_cache(ids, 19, 8)
    errs = [err(g, want(runner, seq, range(18, 27)))
            for g, seq in zip(got, ids)]
    assert min(errs) > 5 * TOL


# -- (b) a prompt longer than prefill_chunk ------------------------------------

def test_chunked_prefill_across_chunks_that_are_no_multiple_of_the_scan_chunk(
    runner, step
):
    seq = sequence(7, 59)     # prefill chunks of 20, scan chunks of 8
    assert runner.ecfg.prefill_chunk % MCFG.mamba_chunk and len(seq) > 2 * 20
    table = table_of(*range(1, 9))
    got = runner.prefill(seq[:57], table)
    wanted = want(runner, seq, [56, 57, 58])
    assert err(got, wanted[0]) < TOL
    assert err(step([seq[57]], [57], table)[0], wanted[1]) < TOL
    assert err(step([seq[58]], [58], table)[0], wanted[2]) < TOL


# -- (d) fused and speculative windows -----------------------------------------

def test_a_fused_window_is_its_steps_one_at_a_time(runner, step):
    prompts = [sequence(8, 13), sequence(9, 21)]
    tables = np.stack([table_of(1, 2, 3, 4, 5), table_of(6, 7, 8, 9, 10)])
    tables4 = np.concatenate([tables, np.zeros((2, MP), np.int32)])
    first = np.argmax(runner.prefill_batch(prompts, tables), axis=-1)
    lens = np.array([13, 21, 0, 0], np.int32)
    last = np.array([first[0], first[1], 0, 0], np.int32)
    zeros, ones = np.zeros((4,), np.float32), np.ones((4,), np.float32)
    toks, logps = runner.decode_multi(
        last, lens, tables4, jax.random.PRNGKey(0), zeros, ones, 6
    )                                                   # greedy, 6 steps
    # the window's chunk carried the state's commit AND the routing
    # counts: a row of six numbers a step
    stats = runner.take_route_stats()
    assert stats.shape == (6, 6)
    assert np.all(stats[:, 4] == MCFG.experts_held)
    # every row of the padded batch routes: held + elsewhere = rows x top-k
    per_step = 4 * MCFG.moe_top_k * MCFG.ffns.count("moe")
    assert np.all(stats[:, 3] + stats[:, 5] == per_step)
    for b, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, [first[b]], toks[:, b]])
        n = len(prompt)
        ref = jax.nn.log_softmax(want(runner, seq, range(n, n + 6)), axis=-1)
        chosen = np.asarray(ref)[np.arange(6), toks[:, b]]
        assert np.max(np.abs(chosen - logps[:, b])) < 5e-4
        nxt = step([toks[-1, b]], [n + 6], tables[b])[0]
        seq2 = np.concatenate([seq, [0]])
        assert err(nxt, want(runner, seq2, [n + 6])[0]) < TOL


def test_under_the_kernels_a_window_commits_the_tokens_it_commits_without(
    monkeypatch,
):
    """``use_pallas`` sends every mamba layer's read of the committed
    state, each step of a fused window, to ops/pallas_ssm.py
    (interpreted here) and nothing the window commits changes."""
    from tests.test_pallas_ssm import window_with_and_without_the_kernel

    window_with_and_without_the_kernel(
        monkeypatch,
        lambda use_pallas: ModelRunner(MCFG, engine(use_pallas=use_pallas)),
        [sequence(8, 13), sequence(9, 21)],
        np.stack([table_of(1, 2, 3, 4, 5), table_of(6, 7, 8, 9, 10)]),
    )


def test_a_speculative_window_commits_any_accepted_prefix(runner, step):
    prompt = sequence(10, 14)
    table = table_of(1, 2, 3, 4)
    tables4 = np.concatenate([table[None], np.zeros((3, MP), np.int32)])
    first = int(np.argmax(runner.prefill(prompt, table)))
    zeros, ones = np.zeros((4,), np.float32), np.ones((4,), np.float32)
    toks, _, handle = runner.decode_window(
        np.array([first, 0, 0, 0], np.int32), np.array([14, 0, 0, 0], np.int32),
        tables4, jax.random.PRNGKey(0), zeros, ones, 4,
    )
    runner.commit_window(handle, np.array([2, 0, 0, 0], np.int32))
    seq = np.concatenate([prompt, [first, toks[0, 0], 7, 0]])
    got = step([7], [16], table)[0]
    assert err(got, want(runner, seq, [16])[0]) < TOL


# -- the fused window's state buffers: step-major, read where they lie ------------

@pytest.mark.parametrize("q0", [0, 3, 7])
def test_a_windows_step_is_the_chunk_form_from_the_same_state(runner, q0):
    """Step ``q0`` of a window reads the ``q0`` earlier tokens from the
    buffers (NaN at and past it) and keeps its own out of them."""
    window_buffers_common.a_windows_step_is_the_chunk_form(
        MCFG, runner.params, q0
    )


def test_a_fused_window_of_eight_is_eight_single_steps(runner, step):
    window_buffers_common.a_window_is_its_steps(runner, step, TOL)


@pytest.mark.parametrize("accepted", [0, 3, 8])
def test_a_speculative_window_of_eight_commits_what_its_accepted_steps_would(
    runner, step, accepted
):
    window_buffers_common.a_window_is_its_steps(runner, step, TOL, accepted)


# -- (e) verify with none, some and all of its inputs accepted -----------------

@pytest.mark.parametrize(
    "accepted", [[1, 3], [5, 0], [0, 5], [2, 2]],
    ids=["one-and-three", "all-and-none", "none-and-all", "two-and-two"],
)
def test_verify_with_a_part_of_its_inputs_accepted(runner, step, accepted):
    seqs = [sequence(11, 24), sequence(12, 24)]
    starts = [15, 11]
    tables = np.stack([table_of(1, 2, 3, 4), table_of(5, 6, 7, 8)])
    runner.prefill_batch([s[:n] for s, n in zip(seqs, starts)], tables)
    tables4 = np.concatenate([tables, np.zeros((2, MP), np.int32)])
    last = np.array([seqs[0][15], seqs[1][11], 0, 0], np.int32)
    drafts = np.zeros((4, 4), np.int32)
    drafts[0], drafts[1] = seqs[0][16:20], seqs[1][12:16]
    dlens = np.array([4, 4, 0, 0], np.int32)
    past = np.array(starts + [0, 0], np.int32)
    _, _, plain, plain_lp = runner.verify_candidates(
        last, drafts, dlens, np.zeros((4, 5, 2), np.int32),
        np.zeros((4, 5), np.int32), past, tables4,
    )
    for b in (0, 1):
        n = starts[b]
        ref = jax.nn.log_softmax(want(runner, seqs[b], range(n, n + 5)), -1)
        assert np.max(np.abs(np.max(np.asarray(ref), -1) - plain_lp[b])) < 5e-4
    runner.commit_verified(np.array(accepted + [0, 0], np.int32))
    for b in (0, 1):
        n = starts[b] + accepted[b]
        seq = np.concatenate([seqs[b][:n], [9, 0]])
        got = step([9], [n], tables[b])[0]
        assert err(got, want(runner, seq, [n])[0]) < TOL


# -- through the scheduler: tokens, spans, counters ----------------------------

def _reqs(tok, prompts, **kw):
    return [
        GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32), **kw)
        for i, p in enumerate(prompts)
    ]


def _counter(name):
    series = telemetry.REGISTRY.collect().get(name, {}).get("series", {})
    return sum(series.values())


def test_through_the_scheduler_greedy_tokens_are_the_references(runner):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    prompts = ["the first prompt, a little longer than a page",
               "a second", "and a third one of middling length"]
    telemetry.set_enabled(True)
    held0 = _counter("sutro_moe_routed_rows_total")
    away0 = _counter("sutro_moe_rows_elsewhere_total")
    b = ContinuousBatcher(runner, stop_ids=[])
    out = {}
    b.run(_reqs(tok, prompts, max_new_tokens=10, temperature=0.0),
          on_result=lambda r: out.__setitem__(r.row_id, r))
    for i, p in enumerate(prompts):
        ids = np.array(tok.encode(p), np.int32)
        seq = np.concatenate([ids, out[i].token_ids]).astype(np.int32)
        ref = want(runner, seq, range(len(ids) - 1, len(seq) - 1))
        assert list(np.argmax(ref, -1)) == list(out[i].token_ids)
    assert runner.pools.slots.in_use == 0
    # the spans say what this chip holds and what landed on it
    attrs = b._route_attrs["decode_window"]
    assert attrs["experts_held"] == MCFG.experts_held
    assert attrs["experts_touched"] <= MCFG.experts_held
    assert attrs["expert_rows_held"] > 0 and attrs["expert_rows_elsewhere"] > 0
    held = _counter("sutro_moe_routed_rows_total") - held0
    away = _counter("sutro_moe_rows_elsewhere_total") - away0
    assert held > 0 and away > 0
    # every row chose top-k experts a routed block, held here or not
    assert (held + away) % (MCFG.moe_top_k * MCFG.ffns.count("moe")) == 0


def test_a_held_share_is_one_chips_and_refuses_a_mesh():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(NotImplementedError, match="share of each layer"):
        ModelRunner(MCFG, engine(), mesh=mesh)


# -- the shares add up ----------------------------------------------------------

def _uncut():
    """tiny-nemotron-h holding EVERY expert, its parameters, and the
    reference's keys for it."""
    cfg = dataclasses.replace(MCFG, name="tiny-nemotron-h: uncut",
                              moe_experts_held=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    keys = dict(KEYS, n_routed_experts=cfg.moe_experts)
    return cfg, params, keys


@pytest.mark.parametrize("method", ["ragged", "dense"])
def test_the_shares_add_up_to_the_uncut_layer(method):
    """What share 0 (experts 0-3) and share 1 (experts 4-7) compute,
    with the shared expert counted ONCE, is what the uncut reference
    gives for the whole layer."""
    cfg, params, keys = _uncut()
    d = nemotron_h_moe.dims_of(keys)
    moe_l = params["layers"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 5, cfg.hidden_size))
    index = 2
    with jax.default_matmul_precision("highest"):
        whole, _ = nemotron_h_moe.routed_ffn(
            d, moe_l, index, x.reshape(15, -1), shared=True
        )
        only_shared = (
            nemotron_h_moe.relu2(x.reshape(15, -1) @ moe_l["shared_up"][index])
            @ moe_l["shared_down"][index]
        )
    half = cfg.moe_experts // 2
    total = np.zeros((3, 5, cfg.hidden_size), np.float32)
    counted = 0
    for first in (0, half):
        share_cfg = dataclasses.replace(
            cfg, moe_experts_held=half, moe_first_expert=first
        )
        lp = {k: v[index] for k, v in moe_l.items()}
        for name in ("we_up_t", "we_down"):
            lp[name] = lp[name][first : first + half]
        for name in ("shared_up", "shared_down"):
            lp.pop(name)          # counted once, below
        y, counts = moe.moe_mlp(
            x, lp["router"], None, lp["we_up_t"], lp["we_down"],
            top_k=cfg.moe_top_k, activation=cfg.activation, method=method,
            first_expert=first, route=transformer._router_form(share_cfg, lp),
            return_counts=True,
        )
        total += np.asarray(y)
        counted += int(counts[first : first + half].sum())
        assert int(counts.sum()) == 15 * cfg.moe_top_k   # over the router's E
    assert counted == 15 * cfg.moe_top_k     # each assignment on ONE share
    total += np.asarray(only_shared).reshape(total.shape)
    scale = float(np.abs(np.asarray(whole)).max())
    assert np.abs(total.reshape(15, -1) - np.asarray(whole)).max() < 1e-5 * scale


def test_a_share_through_the_whole_model_is_the_references_share():
    """The system told its share against the reference given the same
    share of the SAME uncut weights, and another share differs."""
    cfg, params, keys = _uncut()
    half = cfg.moe_experts // 2
    seq = sequence(30, 21)
    for first in (0, half):
        moe_l = dict(params["layers"]["moe"])
        for name in ("we_up_t", "we_down"):
            moe_l[name] = moe_l[name][:, first : first + half]
        cut = dict(params, layers=dict(params["layers"], moe=moe_l))
        share_cfg = dataclasses.replace(
            cfg, moe_experts_held=half, moe_first_expert=first
        )
        got, _, _ = transformer.forward(
            share_cfg, cut, jnp.asarray(seq[None]),
            jnp.arange(21, dtype=jnp.int32)[None], jnp.array([21], jnp.int32),
        )
        wanted = nemotron_h_moe.logits_at(
            keys, params, seq, range(21), experts=(first, half)
        )
        assert err(np.asarray(got[0]), np.asarray(wanted)) < TOL
        other = nemotron_h_moe.logits_at(
            keys, params, seq, range(21), experts=(half - first, half)
        )
        assert err(np.asarray(got[0]), np.asarray(other)) > 50 * TOL


# -- two-matrix relu^2 experts: the dense and the ragged path -------------------

@pytest.mark.parametrize("held,first", [(8, 0), (4, 0), (4, 4), (2, 3)])
def test_two_matrix_experts_dense_and_ragged_paths_agree(held, first):
    H, F, E, K = 32, 24, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(held * 10 + first), 5)
    x = jax.random.normal(ks[0], (2, 7, H))
    router = jax.random.normal(ks[1], (H, E))
    up = jax.random.normal(ks[2], (held, F, H)) * H ** -0.5
    down = jax.random.normal(ks[3], (held, F, H)) * F ** -0.5
    route = dict(score="sigmoid", select_bias=jax.random.normal(ks[4], (E,)) * 0.02,
                 renorm=True, scale=2.5, renorm_eps=1e-20)
    outs = [
        np.asarray(moe.moe_mlp(
            x, router, None, up, down, top_k=K, activation="relu2",
            method=m, first_expert=first, route=route,
        ))
        for m in ("dense", "ragged")
    ]
    assert np.abs(outs[0] - outs[1]).max() < 1e-5 * np.abs(outs[0]).max()
    # and by hand, an expert at a time
    xt = x.reshape(-1, H)
    s = jax.nn.sigmoid(xt @ router)
    top = jax.lax.top_k(s + route["select_bias"], K)[1]
    p = jnp.take_along_axis(s, top, -1)
    p = p / (p.sum(-1, keepdims=True) + 1e-20) * 2.5
    want_ = np.zeros((xt.shape[0], H), np.float32)
    for n in range(xt.shape[0]):
        for j in range(K):
            e = int(top[n, j]) - first
            if 0 <= e < held:
                hdn = jnp.square(jax.nn.relu(up[e] @ xt[n]))
                want_[n] += float(p[n, j]) * np.asarray(hdn @ down[e])
    assert np.abs(outs[1].reshape(-1, H) - want_).max() < 1e-4 * np.abs(want_).max()


def test_a_gated_activation_on_two_matrices_is_refused():
    x = jnp.ones((1, 2, 8))
    with pytest.raises(ValueError, match="two matrices"):
        moe.moe_mlp(x, jnp.ones((8, 4)), None, jnp.ones((4, 8, 8)),
                    jnp.ones((4, 8, 8)), top_k=2, activation="silu")


# -- the gated norm a group at a time -------------------------------------------

def test_the_gated_norm_is_taken_a_group_at_a_time(runner):
    """The reference with the norm over all of I (one group) is another
    model; the program agrees with the grouped one."""
    seq = sequence(40, 17)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner, seq, [16])[0]) < TOL
    assert err(got, want(runner, seq, [16], norm_groups=1)[0]) > 50 * TOL


def test_one_group_takes_the_norm_it_took_before_to_the_bit():
    """A model with ONE group (granite) computes the expression it
    computed before the grouping was written, the mean over all of I
    with no reshape: equal to the bit, eager and jitted; two groups are
    each half's own norm."""
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 11, 256)) * 3.0

    def before(y):
        return y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-5)

    one = transformer.grouped_rms(y, 1, 1e-5)
    assert np.array_equal(np.asarray(one), np.asarray(before(y)))
    jitted = jax.jit(transformer.grouped_rms, static_argnums=(1, 2))(y, 1, 1e-5)
    assert np.array_equal(np.asarray(jitted), np.asarray(jax.jit(before)(y)))
    two = np.asarray(transformer.grouped_rms(y, 2, 1e-5))
    halves = np.concatenate(
        [np.asarray(before(y[..., :128])), np.asarray(before(y[..., 128:]))], -1
    )
    assert np.allclose(two, halves, rtol=1e-6, atol=1e-6)
    assert np.abs(two - np.asarray(one)).max() > 1e-2
    assert MODEL_CONFIGS["tiny-granite"].mamba_groups == 1
    assert MODEL_CONFIGS["granite-4.0-h-micro"].mamba_groups == 1


def test_the_reference_with_one_term_changed_disagrees(runner):
    seq = sequence(41, 23)
    got = runner.prefill(seq, table_of(1, 2, 3))

    def changed(kind, **leaves):
        p = runner.params
        stack = dict(p["layers"][kind])
        for name, fn in leaves.items():
            stack[name] = fn(stack[name])
        return {**p, "layers": {**p["layers"], kind: stack}}

    cases = {
        "shared expert dropped": dict(shared=False),
        "selection bias dropped": dict(
            params=changed("moe", router_bias=jnp.zeros_like)),
        "D skip dropped": dict(params=changed("mamba", d_skip=jnp.zeros_like)),
        "conv bias dropped": dict(params=changed("mamba", b_conv=jnp.zeros_like)),
    }
    for name, kw in cases.items():
        assert err(got, want(runner, seq, [22], **kw)[0]) > 10 * TOL, name
    # the scale 2.5 and relu^2 are the configuration's
    keys = dict(KEYS, routed_scaling_factor=1.0)
    other = np.asarray(nemotron_h_moe.logits_at(keys, runner.params, seq, [22]))
    assert err(got, other[0]) > 10 * TOL
