"""The contract of what decides ``correct`` for a run's numbers
(``perfbench/reference/README.md`` "The forced forward", "The kernels a
run is held to"), on the CPU at a tiny size:

1. who drives the decode: ``sut.logits_through_cache`` returns what the
   small runner's ``forced_logits`` gives where it has one, and is
   bit-equal to the harness's token-a-step loop where it has none;
2. which kernels a run is held to: a configuration's ``kernels`` key
   present and absent;
3. ``numbers.cap_quantile``: absent it is the maximum, present it holds
   ``cap`` at that quantile of a run's positions.
"""

import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import qwen3_dense
from tests.perfbench.routed_systems import NUMBERS
from tests.perfbench.test_reference import TOL, published_keys, system_around

N_PREFILL, N_DECODE = 21, 6          # crosses a page boundary (pages of 8)


@pytest.fixture(scope="module")
def tiny(tiny_ecfg):
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], tiny_ecfg)
    return runner, system_around(runner, tiny_ecfg, "tiny-dense")


def the_loop_as_it_stood(runner, ecfg, seq, n_prefill, n_decode):
    """The harness's decode as every PR until 56 ran it, kept as the
    reference the harness's own path is held bit-equal to."""
    import jax
    import jax.numpy as jnp

    from sutro_tpu.engine.kvcache import write_kv
    from sutro_tpu.engine.runner import ModelRunner

    MP = ecfg.max_pages_per_seq
    r = ModelRunner(runner.mcfg, ecfg, params=runner.params, num_pages=1 + MP)
    table = np.zeros((MP,), np.int32)
    n_pages = -(-(n_prefill + n_decode) // ecfg.kv_page_size)
    table[:n_pages] = np.arange(1, n_pages + 1)
    kv_chunk = r._chunk_for_table(table)

    @jax.jit
    def step(params, cache, tok, past_len, page_table):
        logits, _, (k, v) = r._trunk_decode(
            params, cache, tok, past_len[:, None], past_len, page_table,
            kv_chunk=kv_chunk,
        )
        cache = write_kv(cache, k, v, page_table, past_len,
                         jnp.ones((1,), jnp.int32), use_pallas=r.use_pallas,
                         kernel_mesh=r.kernel_mesh)
        return logits[0, 0].astype(jnp.float32), cache

    out = [np.asarray(r.prefill(seq[:n_prefill], table), np.float32)]
    cache = r.cache
    for j in range(n_decode):
        logits, cache = step(
            r.params, cache, jnp.asarray(seq[None, n_prefill + j: n_prefill + j + 1]),
            jnp.asarray([n_prefill + j], jnp.int32), jnp.asarray(table[None]),
        )
        out.append(np.asarray(logits))
    return np.stack(out)


@pytest.mark.parametrize("sequences", [None, 3], ids=["one", "several"])
def test_without_forced_logits_the_harness_drives_as_it_did(tiny, tiny_ecfg, sequences):
    runner, sut = tiny
    shape = (N_PREFILL + N_DECODE,) if sequences is None else (
        sequences, N_PREFILL + N_DECODE)
    ids = np.random.default_rng(11).integers(0, 256, shape).astype(np.int32)
    assert not hasattr(runner, "forced_logits")
    got = sut.logits_through_cache(ids, N_PREFILL, N_DECODE)
    assert sut.numbers_source == "harness"
    rows = ids[None] if sequences is None else ids
    want = np.stack([
        the_loop_as_it_stood(runner, tiny_ecfg, seq, N_PREFILL, N_DECODE)
        for seq in rows
    ])
    assert got.dtype == np.float32
    assert np.array_equal(got if sequences else got[None], want)
    # the engine's runner keeps its weights; only the small one let go
    assert runner.params is not None and runner.cache is not None


def test_forced_logits_is_taken_where_the_runner_has_it(tiny, monkeypatch):
    """A runner whose decode steps TWO tokens a call: the harness hands
    it each sequence and returns what it gave, untouched."""
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models import transformer

    runner, sut = tiny
    calls, small = [], []

    def forced_logits(self, seq, n_prefill, n_decode):
        assert self is not runner and seq.ndim == 1
        small.append(self)
        rows = []
        for lo in range(n_prefill - 1, n_prefill + n_decode, 2):
            hi = min(lo + 2, n_prefill + n_decode)
            calls.append((lo, hi))
            logits, _, _ = transformer.forward(
                self.mcfg, self.params, jnp.asarray(seq[None, :hi]),
                jnp.arange(hi)[None], jnp.asarray([hi]),
            )
            rows.append(np.asarray(logits[0, lo:hi], np.float32))
        # a marker no other path would produce
        return np.concatenate(rows) + np.float32(1000.0)

    monkeypatch.setattr(ModelRunner, "forced_logits", forced_logits, raising=False)
    ids = np.random.default_rng(12).integers(
        0, 256, (2, N_PREFILL + N_DECODE)).astype(np.int32)
    got = sut.logits_through_cache(ids, N_PREFILL, N_DECODE)
    assert sut.numbers_source == "forced_logits"
    assert got.shape == (2, 1 + N_DECODE, runner.mcfg.vocab_size)
    assert calls[:2] == [(20, 22), (22, 24)] and len(calls) == 2 * 4
    positions = list(range(N_PREFILL - 1, N_PREFILL + N_DECODE))
    for seq, rows in zip(ids, got):
        want = np.asarray(qwen3_dense.logits_at(
            published_keys(runner.mcfg), runner.params, seq, positions))
        assert correctness.position_errors(rows - 1000.0, want).max() < TOL
    # the small runner's pool and its hold on the weights were given back
    assert all(r.cache is None and r.params is None for r in small)
    assert runner.params is not None
    # one sequence in, one sequence's rows out
    one = sut.logits_through_cache(ids[0], N_PREFILL, N_DECODE)
    assert np.array_equal(one, got[0])


# -- which kernels a run is held to ------------------------------------------

def counts(lowered=0, interpreted=0, reference=0):
    return {"lowered": lowered, "interpreted": interpreted, "reference": reference}


ON_A_HEAD_64_MODEL = {
    "paged_decode": counts(reference=3), "flash_prefill": counts(reference=2),
    "kv_write": counts(lowered=4), "grouped_matmul": counts(lowered=6),
    "ssm_state_read": counts(), "kda_state_read": counts(),
}


def test_without_kernels_the_three_attention_kernels_are_held_and_no_other():
    dense = {"paged_decode": counts(2), "flash_prefill": counts(1),
             "kv_write": counts(3), "grouped_matmul": counts()}
    assert correctness.kernel_problems({"name": "c"}, dense) == []
    # a routed model's grouped product on XLA is a fact there, no problem
    routed = dict(dense, grouped_matmul=counts(reference=5))
    assert correctness.kernel_problems({"name": "c"}, routed) == []
    problems = correctness.kernel_problems({"name": "c"}, ON_A_HEAD_64_MODEL)
    assert len(problems) == 2
    assert all("was not lowered" in p for p in problems)
    assert "paged_decode" in problems[0] and "flash_prefill" in problems[1]
    interpreted = dict(dense, kv_write=counts(lowered=1, interpreted=1))
    assert len(correctness.kernel_problems({"name": "c"}, interpreted)) == 1
    # a program that counts none of them has lowered none of them
    assert len(correctness.kernel_problems({"name": "c"}, {})) == 3


def test_with_kernels_the_list_is_held_both_ways():
    cfg = {"name": "c", "kernels": ["kv_write", "grouped_matmul"],
           "engine": {"use_pallas": None}}
    assert correctness.kernel_problems(cfg, ON_A_HEAD_64_MODEL) == []
    # a listed kernel that was not lowered ...
    fell = dict(ON_A_HEAD_64_MODEL, grouped_matmul=counts(reference=6))
    problems = correctness.kernel_problems(cfg, fell)
    assert len(problems) == 1 and "grouped_matmul was not lowered" in problems[0]
    # ... or that the program does not count at all ...
    gone = {k: v for k, v in ON_A_HEAD_64_MODEL.items() if k != "kv_write"}
    problems = correctness.kernel_problems(cfg, gone)
    assert len(problems) == 1 and "kv_write was not lowered" in problems[0]
    # ... and an unlisted one that was: the list cannot go stale unseen
    grew = dict(ON_A_HEAD_64_MODEL, paged_decode=counts(lowered=1, reference=2))
    problems = correctness.kernel_problems(cfg, grew)
    assert len(problems) == 1 and "paged_decode was lowered" in problems[0]
    assert "do not list it" in problems[0]
    interpreted = dict(ON_A_HEAD_64_MODEL, ssm_state_read=counts(interpreted=1))
    assert len(correctness.kernel_problems(cfg, interpreted)) == 1
    # an empty list holds every count at nought
    assert len(correctness.kernel_problems(
        dict(cfg, kernels=[]), ON_A_HEAD_64_MODEL)) == 2


@pytest.mark.parametrize("bad", [
    {"kernels": "kv_write"}, {"kernels": ["kv_write", "kv_write"]},
    {"kernels": [""]}, {"kernels": [3]},
    {"kernels": ["kv_write"], "engine": {"use_pallas": False}},
], ids=str)
def test_a_kernels_key_that_says_nothing_is_refused(bad):
    with pytest.raises(ValueError):
        correctness.kernel_problems(dict({"name": "c"}, **bad), ON_A_HEAD_64_MODEL)


class Counted:
    """A dense system that gives the reference's own logits back and
    says which kernels it traced."""

    def __init__(self, cfg, paths, uses=True):
        self.cfg, self.paths, self.uses = cfg, paths, uses

    def serving_dtype(self): return "float32"
    def weights(self): return self.params
    def kernel_paths(self): return self.paths
    def uses_kernels(self): return self.uses

    def logits_through_cache(self, ids, n_prefill, n_decode):
        at = list(range(n_prefill - 1, n_prefill + n_decode))
        return np.asarray(qwen3_dense.logits_at(self.cfg, self.params, ids, at))


def test_numbers_reports_both_kinds_and_keeps_the_counts_as_facts(tiny):
    runner, _ = tiny
    keys = published_keys(runner.mcfg)
    sut = Counted(keys, ON_A_HEAD_64_MODEL)
    sut.params = runner.params
    problems, facts = correctness.numbers(sut, keys, 5)
    assert len(problems) == 2 and facts["kernel_paths"] == ON_A_HEAD_64_MODEL
    listed = dict(keys, kernels=["kv_write", "grouped_matmul"])
    assert correctness.numbers(sut, listed, 5)[0] == []
    stale = dict(keys, kernels=["kv_write"])
    problems, _ = correctness.numbers(sut, stale, 5)
    assert len(problems) == 1 and "grouped_matmul was lowered" in problems[0]
    # a run that does not use the kernels is held to none, as before
    sut.uses = False
    assert correctness.numbers(sut, keys, 5)[0] == []


def test_kernel_paths_carries_the_counts_outside_the_snapshot(tiny, monkeypatch):
    from sutro_tpu.ops import lowering

    _, sut = tiny
    paths = sut.kernel_paths()
    assert list(paths)[:3] == list(lowering.snapshot())
    assert list(correctness.DEFAULT_KERNELS) == list(lowering.KERNELS)
    assert paths["grouped_matmul"] == lowering.grouped_matmul_counts()
    for name in ("ssm_state_read", "kda_state_read", "kda_state_commit"):
        assert set(paths[name]) == set(lowering.PATHS)
    # a program without one of the accessors still runs
    monkeypatch.delattr(lowering, "kda_state_commit_counts")
    assert "kda_state_commit" not in sut.kernel_paths()
    assert "kda_state_read" in sut.kernel_paths()


def test_every_file_that_lists_kernels_lists_names_the_program_counts():
    import json
    from pathlib import Path

    from perfbench import sut

    known = set(correctness.DEFAULT_KERNELS) | set(sut.FURTHER_KERNELS)
    configs = Path(correctness.__file__).parent / "configs"
    listing = {}
    for f in sorted(configs.glob("*.json")):
        cfg = json.loads(f.read_text())
        if "kernels" in cfg:
            lowered, not_lowered = correctness.held_kernels(cfg, dict.fromkeys(known))
            assert set(lowered) <= known and set(lowered) | set(not_lowered) == known
            assert cfg["engine"]["use_pallas"] is None
            listing[cfg["name"]] = lowered
    # the two cells of 64-wide heads, whose attention calls fall to XLA
    assert listing["lfm2-24b-a2b-l10-v5e1"] == ["kv_write", "grouped_matmul"]
    assert listing["granite-4.0-h-micro-v5e1"] == ["kv_write", "ssm_state_read"]


# -- cap_quantile -------------------------------------------------------------

WHERE = [f"sequence {s} position {p}" for s in range(32) for p in range(9)]


def spec(**more):
    return dict(NUMBERS, sequences=32, quantile=0.1, cap=0.5, **more)


def errors(over_cap=0, value=0.6):
    errs = np.linspace(0.01, 0.3, len(WHERE))
    errs[: over_cap] = value
    return errs


def test_cap_quantile_absent_is_the_maximum():
    for errs in (errors(), errors(1), errors(5)):
        p0, f0 = correctness.routed_rule(errs, 0.06, "bfloat16", spec(), WHERE)
        p1, f1 = correctness.routed_rule(
            errs, 0.06, "bfloat16", spec(cap_quantile=1.0), WHERE)
        assert (p0, f0) == (p1, f1)
        assert f0["cap_quantile"] == 1.0
        assert f0["rel_err_cap_quantile"] == f0["rel_err_max"] == errs.max()
    # every position over the cap is named, as it always was
    problems, _ = correctness.routed_rule(errors(5), 0.06, "bfloat16", spec(), WHERE)
    assert len(problems) == 5 and all("(cap 0.5)" in p for p in problems)


def test_cap_quantile_holds_the_cap_at_that_quantile_of_the_positions():
    rule = spec(cap_quantile=0.99)
    # 288 positions: two of them over the cap are under the 0.99 quantile
    problems, facts = correctness.routed_rule(errors(2), 0.06, "bfloat16", rule, WHERE)
    assert problems == []
    assert facts["rel_err_max"] == 0.6 and facts["rel_err_cap_quantile"] < 0.5
    assert facts["rel_err_cap_quantile"] == float(np.quantile(errors(2), 0.99))
    # six of them are not, and the one problem says the largest and where
    problems, facts = correctness.routed_rule(errors(6), 0.06, "bfloat16", rule, WHERE)
    assert len(problems) == 1 and "0.99 quantile" in problems[0]
    assert "sequence 0 position 0" in problems[0]
    assert facts["rel_err_cap_quantile"] == 0.6
    # every position must still be finite, whatever the quantile forgives
    errs = errors()
    errs[7] = np.nan
    problems, _ = correctness.routed_rule(errs, 0.06, "bfloat16", rule, WHERE)
    assert problems == ["numbers: sequence 0 position 7: logits are not finite"]
    # and the low quantile is held as before
    problems, _ = correctness.routed_rule(errors() + 0.07, 0.06, "bfloat16", rule, WHERE)
    assert len(problems) == 1 and "0.1 quantile" in problems[0]


def test_compared_gives_each_number_beside_its_limit():
    _, facts = correctness.routed_rule(
        errors(2), 0.06, "bfloat16", spec(cap_quantile=0.99), WHERE)
    facts["tolerance"] = 0.06
    assert correctness.compared(facts) == {
        "rel_err_quantile": [facts["rel_err_quantile"], 0.06],
        "rel_err_cap_quantile": [facts["rel_err_cap_quantile"], 0.5],
    }
    dense = {"rel_err_prefill": 0.001, "rel_err_decode_max": 0.002, "tolerance": 0.02}
    assert correctness.compared(dense) == {
        "rel_err_prefill": [0.001, 0.02], "rel_err_decode_max": [0.002, 0.02]}
    assert correctness.compared({}) == {}
