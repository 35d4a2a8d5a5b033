"""``tiny-xing-mhc`` (a residual stream of four lanes mixed a token in
every sublayer, round YaRN-scaled latent attention, every expert held)
on every path that walks the layers, against the plain float32 reference
(``perfbench/reference/mhc_mla_moe.py``) on seeded random weights, and
so against one another. A hyper-connection keeps nothing between tokens:
every form the runner has must simply carry the lanes.

What is compared is LOGITS (or the log-probability the program reports
for a token). Tolerance 2e-4 of the largest logit: both sides compute in
float32 and differ in summation order, the Sinkhorn's reciprocals and
the absorbed form's algebra (measured about 1e-6;
tests/test_xing_mhc_reference.py has the reasons and the controls).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.xing_mhc_common import (
    MCFG, MP, engine, err, sequence, table_of, want,
)

TOL = 2e-4


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine())


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


# -- the shape of the thing ------------------------------------------------------

def test_the_presets_are_the_published_file_and_one_builder():
    assert MCFG.mixers == ("mla",) * 4 and MCFG.ffns == ("dense",) + ("moe",) * 3
    assert (MCFG.hc_mult, MCFG.hc_sublayers, MCFG.hc_sinkhorn_iters) == (4, 8, 20)
    full = MODEL_CONFIGS["xing4.0-29b-a4b"]
    cut = MODEL_CONFIGS["xing4.0-29b-a4b-l7"]
    assert dataclasses.replace(full, name=cut.name, num_layers=7,
                               layer_types=("mla",) * 7) == cut
    assert (full.num_layers, full.hidden_size, full.vocab_size) == (40, 3584, 131_072)
    assert full.ffns == ("dense",) * 2 + ("moe",) * 38
    assert (full.q_lora_rank, full.kv_lora_rank, full.page_width) == (768, 512, 640)
    assert (full.moe_experts, full.moe_top_k, full.experts_held) == (64, 4, 64)
    assert (full.router_scale, full.rope_theta) == (2.0, 10_000.0)
    assert (full.rope_scaling_factor, full.rope_original_max) == (64.0, 4096)
    assert (full.rope_attention_factor, full.rope_mscale_all_dim) == (1.0, 1.0)
    assert (full.hc_mult, full.hc_eps, full.hc_res_clamp) == (4, 1e-6, 30.0)
    assert cut.hc_sublayers == 14 and full.hc_sublayers == 80
    # the walk: the two dense layers one scan, the routed ones another
    assert transformer.layer_groups(cut) == [(0, 1, 2), (2, 1, 5)]
    # every other preset of the builder keeps one lane and no scaling
    joyai = MODEL_CONFIGS["joyai-llm-flash-ep16"]
    assert (joyai.hc_mult, joyai.hc_sublayers, joyai.rope_scaling_factor) == (1, 0, 0.0)
    assert joyai.router_scale == 2.5 and joyai.rope_attention_factor is None


def test_the_hyper_connections_ride_on_their_kinds_stack(runner):
    layers = runner.params["layers"]
    assert set(layers) == {"mla", "dense", "moe"}
    for kind, prefix, n in (("mla", "hc_mix_", 4), ("dense", "hc_ffn_", 1),
                            ("moe", "hc_ffn_", 3)):
        s = layers[kind]
        assert s[prefix + "phi"].shape == (n, 24, 4 * 128)
        assert s[prefix + "b"].shape == (n, 24) and s[prefix + "b"].dtype == jnp.float32
        assert s[prefix + "alpha"].shape == (n, 3)
    info = runner.device_info()
    assert (info["hc_mult"], info["hc_sublayers"]) == (4, 8)
    assert info["latent_layers"] == 4 and info["latent_row_width"] == 48
    # 2 n C elements a sublayer a token: a lane read once, written once
    assert runner.stream_bytes(10) == 10 * 8 * (2 * 4) * 128 * 4
    plain = ModelRunner.stream_bytes.__get__(
        type("R", (), {"mcfg": MODEL_CONFIGS["tiny-joyai"], "ecfg": runner.ecfg})()
    )
    assert plain(10) == 0


# -- prefill: alone, batched, chunked, a suffix --------------------------------

def test_prefill_alone_and_rows_of_different_lengths_in_one_padded_batch(runner):
    seq = sequence(0, 19)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner, seq, [18])[0]) < TOL
    rows = [sequence(1, 9), sequence(2, 17), sequence(3, 12)]
    tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(7, 8)])
    got = runner.prefill_batch(rows, tables)
    for g, row in zip(got, rows):
        assert err(g, want(runner, row, [len(row) - 1])[0]) < TOL


def test_chunked_prefill_then_single_steps_and_a_suffix_prefill(runner, step):
    seq = sequence(7, 59)     # prefill chunks of 20: absorbed over the pages
    table = table_of(*range(1, 9))
    got = runner.prefill(seq[:57], table)
    wanted = want(runner, seq, [56, 57, 58])
    assert err(got, wanted[0]) < TOL
    assert err(step([seq[57]], [57], table)[0], wanted[1]) < TOL
    assert err(step([seq[58]], [58], table)[0], wanted[2]) < TOL
    # a suffix: the first 24 tokens' pages stay, the rest again
    runner.prefill(seq[:24], table)
    got = runner.prefill_batch_at(
        [seq[24:40]], table[None], np.array([24], np.int32)
    )
    assert err(got[0], want(runner, seq, [39])[0]) < TOL


# -- fused and speculative windows, verify chunks --------------------------------

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
    for b, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, [first[b]], toks[:, b]])
        n = len(prompt)
        ref = jax.nn.log_softmax(want(runner, seq, range(n, n + 6)), axis=-1)
        chosen = np.asarray(ref)[np.arange(6), toks[:, b]]
        assert np.max(np.abs(chosen - logps[:, b])) < 5e-4
        nxt = step([toks[-1, b]], [n + 6], tables[b])[0]
        seq2 = np.concatenate([seq, [0]])
        assert err(nxt, want(runner, seq2, [n + 6])[0]) < TOL


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


@pytest.mark.parametrize(
    "accepted", [[1, 3], [5, 0]], ids=["one-and-three", "all-and-none"],
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


# -- through the scheduler: the wave, spans, counters ----------------------------

def _counter(name, key=None):
    series = telemetry.REGISTRY.collect().get(name, {}).get("series", {})
    return sum(v for k, v in series.items() if key is None or k == key)


def test_through_the_scheduler_tokens_spans_and_counters(runner):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    prompts = ["the first prompt, a little longer than a page",
               "a second", "and a third one of middling length",
               "a fourth", "a fifth that waits for a row", "the sixth"]
    telemetry.set_enabled(True)
    before = {k: _counter("sutro_hc_sublayers_total", k)
              for k in ("prefill", "decode")}
    needed0 = _counter("sutro_hc_stream_bytes_needed_total")
    b = ContinuousBatcher(runner, stop_ids=[])
    out = {}
    b.run(
        [GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32),
                    max_new_tokens=10, temperature=0.0)
         for i, p in enumerate(prompts)],
        on_result=lambda r: out.__setitem__(r.row_id, r),
    )
    # six rows through a batch of four: the admission wave, fused windows
    for i, p in enumerate(prompts):
        ids = np.array(tok.encode(p), np.int32)
        seq = np.concatenate([ids, out[i].token_ids]).astype(np.int32)
        ref = want(runner, seq, range(len(ids) - 1, len(seq) - 1))
        assert list(np.argmax(ref, -1)) == list(out[i].token_ids)
    attrs = b._tel_attrs["decode_window"]
    assert attrs["hc_stream_bytes"] == runner.stream_bytes(
        attrs["batch"] * attrs["steps"])
    # (the wave's resolve leaves the LAST prefill span: no tokens, no attr)
    assert "hc_stream_bytes" not in b._tel_attrs["prefill"]
    assert b._stream_attrs("prefill", 7) == {
        "hc_stream_bytes": runner.stream_bytes(7)}
    for form in ("prefill", "decode"):
        gained = _counter("sutro_hc_sublayers_total", form) - before[form]
        assert gained > 0 and gained % MCFG.hc_sublayers == 0
    prompt_tokens = sum(len(tok.encode(p)) for p in prompts)
    needed = _counter("sutro_hc_stream_bytes_needed_total") - needed0
    assert needed >= runner.stream_bytes(prompt_tokens + 6 * 9)


def test_one_lane_counts_no_stream():
    """A model of one lane: no attr, no count, no ``hc_`` scope."""
    r = ModelRunner(MODEL_CONFIGS["tiny-joyai"], engine(), num_pages=9)
    b = ContinuousBatcher(r, stop_ids=[])
    telemetry.set_enabled(True)
    before = _counter("sutro_hc_sublayers_total")
    assert b._stream_attrs("decode", 12, 3) == {}
    assert _counter("sutro_hc_sublayers_total") == before
    ids, pos = jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None]
    text = str(jax.make_jaxpr(
        lambda p: transformer.forward(r.mcfg, p, ids, pos, jnp.asarray([8]))[0]
    )(r.params).pretty_print(name_stack=True))
    assert "hc_" not in text and "mla_yarn" not in text and "mla_mixer" in text
    mine = ModelRunner(MCFG, engine(), num_pages=2)
    text = str(jax.make_jaxpr(
        lambda p: transformer.forward(MCFG, p, ids, pos, jnp.asarray([8]))[0]
    )(mine.params).pretty_print(name_stack=True))
    for scope in ("hc_coeff", "hc_sinkhorn", "hc_read", "hc_write", "mla_yarn"):
        assert scope in text


# -- what is not built is refused by name ----------------------------------------

def test_what_is_not_built_is_refused_by_name():
    scan = dataclasses.replace(
        MODEL_CONFIGS["tiny-dense"], name="lanes in one scan", hc_mult=4
    )
    with pytest.raises(NotImplementedError, match="hc_mult"):
        transformer.init_params(scan, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="hc_sinkhorn_iters"):
        transformer._check_mixed(dataclasses.replace(MCFG, hc_sinkhorn_iters=0))
    indexer = dataclasses.replace(
        MODEL_CONFIGS["tiny-glm-dsa"], name="yarn under an indexer",
        rope_scaling_factor=8.0, rope_original_max=32,
    )
    with pytest.raises(NotImplementedError, match="indexer"):
        transformer._check_mixed(indexer)
    halves = dataclasses.replace(MCFG, name="halves", rope_interleave=False)
    with pytest.raises(NotImplementedError, match="rope_interleave"):
        transformer._check_mixed(halves)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
    one_kind = dataclasses.replace(
        MODEL_CONFIGS["tiny-lfm2"], name="lanes under a mesh", hc_mult=2
    )
    with pytest.raises(NotImplementedError, match="hc_mult.*one chip"):
        ModelRunner(one_kind, engine(), mesh=mesh)
    from sutro_tpu.engine import weights

    with pytest.raises(NotImplementedError, match="hc_mult"):
        weights._load_mixed(MCFG, lambda *a: None, jnp.float32)
