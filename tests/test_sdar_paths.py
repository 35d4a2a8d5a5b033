"""The paths a model that generates by blocks takes through the runner
and the scheduler at ``tiny-sdar``, against one another: batched,
one-row and chunked prefill; one block and a window of blocks; pipelined
windows at lookahead 1 and 2 with rows admitted and released between
windows; every refusal by name; and that a causal model's programs are
what they were (``tiny-moe``: no block scope, no block counter, the
native host runtime still serves it)."""

import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.models.configs import MODEL_CONFIGS, REMASKING
from tests.sdar_common import (
    BK, MASK, MCFG, MP, engine, generate, runner, sequence,
)


def _pool_after(r, fill):
    """The K pages a fresh prefill path leaves for the rows' own pages."""
    fill(r)
    return np.asarray(r.cache.k_pages).copy()


def test_batched_one_row_and_chunked_prefill_write_the_same_cache():
    r = runner()
    rows = [sequence(20 + i, n) for i, n in enumerate((13, 8, 30))]
    tables = np.zeros((3, MP), np.int32)
    for i in range(3):
        tables[i, :5] = 1 + 5 * i + np.arange(5)
    whole = [r.whole_blocks(len(x)) for x in rows]
    assert whole == [12, 8, 28]

    def batched(r):
        r.prefill_batch(rows, tables)

    def one_by_one(r):          # 30 tokens > prefill_chunk 16: chunked
        for x, t in zip(rows, tables):
            r.prefill(x, t)

    def suffixes(r):            # the first 8 tokens, then the rest at 8
        r.prefill_batch([x[:8] for x in rows], tables)
        r.prefill_batch_at([x[8:] for x in rows], tables, [8, 8, 8])

    a, b, c = (_pool_after(r, f) for f in (batched, one_by_one, suffixes))
    for i, n in enumerate(whole):
        for page in range(-(-n // 8)):
            upto = min(8, n - page * 8)
            p = tables[i, page]
            np.testing.assert_allclose(a[:, p, :upto], b[:, p, :upto],
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(a[:, p, :upto], c[:, p, :upto],
                                       rtol=2e-5, atol=2e-5)


def _window(r, blocks, past, table, first, seed=0, **kw):
    import jax

    B = first.shape[0]
    toks, logps, turns = r.decode_block_async(
        first, np.ones((B,), bool), past, table, jax.random.PRNGKey(seed),
        np.zeros((B,), np.float32), np.ones((B,), np.float32), blocks, **kw,
    )
    return np.asarray(toks), np.asarray(logps), np.asarray(turns)


def test_one_block_twice_is_a_window_of_two_blocks():
    r = runner()
    B = r.ecfg.decode_batch_size
    rows = [sequence(30 + i, 8 + 4 * i) for i in range(B)]
    tables = np.zeros((B, MP), np.int32)
    for i in range(B):
        tables[i, :6] = 1 + 6 * i + np.arange(6)
    past = np.asarray([len(x) for x in rows], np.int32)
    first = np.full((B, BK), MASK, np.int32)
    first[1, :2] = [5, 6]                       # a row with two given tokens
    r.prefill_batch(rows, tables)
    two, lp2, turns2 = _window(r, 2, past, tables, first)
    r.prefill_batch(rows, tables)               # the same pages again
    a, lpa, ta = _window(r, 1, past, tables, first)
    masks = np.full((B, BK), MASK, np.int32)
    b, lpb, tb = _window(r, 1, past + BK, tables, masks)
    np.testing.assert_array_equal(two, np.concatenate([a, b]))
    np.testing.assert_allclose(lp2, np.concatenate([lpa, lpb]), atol=1e-5)
    assert two.shape == (2 * BK, B) and (two != MASK).all()
    assert two[0, 1] == 5 and two[1, 1] == 6    # the given tokens stand
    # blocks of 4 at the model's default 4 steps: 4 forwards a block, but
    # the row with two given tokens needs 2 and every row is waited for
    assert turns2.tolist() == [4, 4] and ta.tolist() == [4]
    # steps as an operand: 1 step fills a block in one forward
    _, _, t1 = _window(r, 1, past + 2 * BK, tables, masks,
                       steps=np.ones((B,), np.int32))
    assert t1.tolist() == [1]


@pytest.mark.parametrize("lookahead", [1, 2])
def test_pipelined_windows_admit_and_release_between_windows(lookahead):
    """Nine rows through four slots: rows finish at different windows,
    new rows take their slots, windows in flight for a released row are
    discarded. The tokens are what a batch of one row at a time gives
    (greedy: a row's tokens do not depend on its neighbours)."""
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    r = runner(decode_lookahead=lookahead)
    prompts = [sequence(50 + i, 5 + 3 * i) for i in range(9)]
    caps = [9, 22, 5, 14, 30, 7, 12, 18, 3]
    many = generate(ContinuousBatcher(r, stop_ids=[]), prompts, caps,
                    denoising_steps=2, remasking="low_confidence_static")
    for i in range(9):
        alone = generate(ContinuousBatcher(r, stop_ids=[]), [prompts[i]],
                         [caps[i]], denoising_steps=2,
                         remasking="low_confidence_static")[0]
        assert len(many[i].token_ids) == caps[i]
        assert many[i].token_ids == alone.token_ids, i


def test_the_windows_positions_add_up_and_are_counted():
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    if not telemetry.ENABLED:
        pytest.skip("telemetry is off")
    r = runner()
    before = {
        (name, key): telemetry.REGISTRY.collect()[name]["series"].get(key, 0.0)
        for name in ("sutro_block_tokens_total", "sutro_block_forwards_total",
                     "sutro_block_row_forwards_total")
        for key in ("accepted", "given", "lost", "denoise", "commit")
    }
    prompts = [sequence(70 + i, n) for i, n in enumerate((6, 9, 12))]
    res = generate(ContinuousBatcher(r, stop_ids=[]), prompts, [10, 11, 12],
                   denoising_steps=2, remasking="low_confidence_static")
    now = telemetry.REGISTRY.collect()

    def gained(name, key):
        return now[name]["series"].get(key, 0.0) - before[(name, key)]

    accepted = gained("sutro_block_tokens_total", "accepted")
    assert accepted == sum(len(x.token_ids) for x in res.values()) == 33
    assert gained("sutro_block_tokens_total", "given") == 2 + 1 + 0
    lost = gained("sutro_block_tokens_total", "lost")
    denoise = gained("sutro_block_forwards_total", "denoise")
    commit = gained("sutro_block_forwards_total", "commit")
    assert denoise == 2 * commit and commit >= 4
    rows = gained("sutro_block_row_forwards_total", "commit")
    # every position of every row of every window is one of the three
    assert accepted + 3 + lost == rows * BK


def test_what_a_block_model_does_not_do_is_refused_by_name():
    from sutro_tpu.engine.api import check_block_request
    from sutro_tpu.engine.runner import ModelRunner

    ok = {"temperature": 0.7, "denoising_steps": 2,
          "remasking": "low_confidence_static", "confidence_threshold": 0.8}
    check_block_request(MCFG, {}, ok)
    for payload, sampling, word in (
        ({"output_schema": {"type": "object"}}, {}, "output_schema"),
        ({"random_seed_per_input": True}, {}, "random_seed_per_input"),
        ({"stages": []}, {}, "stages"),
        ({}, {"presence_penalty": 0.5}, "presence_penalty"),
        ({}, {"frequency_penalty": 0.5}, "frequency_penalty"),
        ({}, {"repetition_penalty": 1.2}, "repetition_penalty"),
        ({}, {"denoising_steps": 5}, "denoising_steps"),
        ({}, {"denoising_steps": 0}, "denoising_steps"),
        ({}, {"remasking": "random"}, "remasking"),
        ({}, {"confidence_threshold": 1.5}, "confidence_threshold"),
    ):
        with pytest.raises(ValueError, match=word):
            check_block_request(MCFG, payload, sampling)
    # a causal model is asked nothing about blocks
    dense = MODEL_CONFIGS["tiny-dense"]
    check_block_request(dense, {"output_schema": {"type": "object"}},
                        {"presence_penalty": 0.5})
    for key in ("denoising_steps", "remasking", "confidence_threshold"):
        with pytest.raises(ValueError, match="block_length 1"):
            check_block_request(dense, {}, {key: ok[key]})
    assert REMASKING.index(MCFG.remasking) == 1
    # the runner: a setting that is not built raises at construction
    for kw, word in (
        (dict(interactive_slots=2), "interactive_slots"),
        (dict(kv_quantize="int8"), "kv_quantize"),
        (dict(quantize="int8"), "quantize"),
        (dict(kv_page_size=6, max_pages_per_seq=32), "kv_page_size"),
        (dict(prefill_chunk=18), "prefill_chunk"),
    ):
        with pytest.raises((NotImplementedError, ValueError), match=word):
            ModelRunner(MCFG, engine(**kw))
    import jax

    if jax.device_count() >= 2:
        with pytest.raises(NotImplementedError, match="mesh"):
            ModelRunner(MCFG, engine(tp=2))
    from sutro_tpu.engine.weights import _load_mixed

    with pytest.raises(NotImplementedError, match="sdar_moe"):
        _load_mixed(MCFG, None, None)


def test_a_shared_prefix_falls_back_and_is_counted():
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    r = runner()
    b = ContinuousBatcher(r, stop_ids=[])
    assert b.native is None and b._block == BK
    head = sequence(90, 24)
    prompts = [np.concatenate([head, sequence(91 + i, 3 + i)]) for i in range(3)]
    shared = generate(b, prompts, [6, 6, 6], denoising_steps=2,
                      remasking="low_confidence_static")
    for i, p in enumerate(prompts):
        alone = generate(ContinuousBatcher(r, stop_ids=[]), [p], [6],
                         denoising_steps=2,
                         remasking="low_confidence_static")[0]
        assert shared[i].token_ids == alone.token_ids
    assert b.prefill_tokens == sum(len(p) // BK * BK for p in prompts)


def test_a_causal_models_programs_name_no_block():
    import jax
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    for name in ("tiny-moe", "tiny-dense"):
        mcfg = MODEL_CONFIGS[name]
        assert mcfg.block_length == 1 and mcfg.homogeneous
        r = ModelRunner(mcfg, engine())
        B = r.ecfg.decode_batch_size
        z = jnp.zeros((B,), jnp.int32)
        text = str(jax.make_jaxpr(
            lambda p, c: ModelRunner._decode_multi_jit.__wrapped__(
                r, p, c, z, z, jnp.zeros((B, MP), jnp.int32),
                jax.random.PRNGKey(0), jnp.zeros((B,)), jnp.ones((B,)), 4, z,
            )[0]
        )(r.params, r.cache))
        for scope in ("bd_denoise", "bd_commit", "bd_confidence",
                      "bd_transfer"):
            assert scope not in text
        b = ContinuousBatcher(r, stop_ids=[])
        assert b._block == 1 and b._window_tokens() == r.ecfg.decode_multi_step
        res = generate(b, [sequence(1, 9)], [7])
        assert len(res[0].token_ids) == 7
    # a model that lists its layers' kinds walks them by kind
    assert not MCFG.homogeneous and MCFG.layer_types == ("attention",) * 3
