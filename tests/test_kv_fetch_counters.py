"""What the decode kernel's one fetch schedule leaves around it: the two
names the benchmark still calls (``perfbench/sut.py``), and the counters
that say how much of the pool a decode dispatch fetched against what its
rows' tokens fill (``sutro_kv_pages_fetched_total`` /
``sutro_kv_pages_needed_total``, OBSERVABILITY.md)."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner, _pool_margin_pages
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.models.configs import MODEL_CONFIGS


def test_the_benchmark_seam_still_traces(tiny_runner, tiny_ecfg):
    """``_chunk_for_table(table)`` gives an int and ``_trunk_decode(...,
    kv_chunk=that)`` traces, as ``perfbench/sut.py``'s
    ``logits_through_cache`` uses them (a file this repo's program PRs
    may not edit)."""
    r = tiny_runner
    MP = tiny_ecfg.max_pages_per_seq
    table = np.zeros((MP,), np.int32)
    table[:2] = [1, 2]
    kv_chunk = r._chunk_for_table(table)
    assert isinstance(kv_chunk, int)
    # any table gives the same: one schedule serves every layout
    assert r._chunk_for_table(np.zeros((MP,), np.int32)) == kv_chunk
    assert r._chunk_for_table(table[::-1][None]) == kv_chunk

    @jax.jit
    def step(params, cache, tok, past_len, page_table):
        logits, _, (k, v) = r._trunk_decode(
            params, cache, tok, past_len[:, None], past_len,
            page_table, kv_chunk=kv_chunk,
        )
        cache = write_kv(
            cache, k, v, page_table, past_len,
            jnp.ones((1,), jnp.int32),
            use_pallas=r.use_pallas, kernel_mesh=r.kernel_mesh,
        )
        return logits[0, 0].astype(jnp.float32), cache

    logits, cache = jax.eval_shape(
        step, r.params, r.cache, jnp.zeros((1, 1), jnp.int32),
        jnp.asarray([9], jnp.int32), jnp.asarray(table[None]),
    )
    assert logits.shape == (r.mcfg.vocab_size,)
    assert cache.k_pages.shape == r.cache.k_pages.shape


def test_a_memory_bound_pool_hands_out_what_it_did():
    """The pages a pool that fills the device keeps back are the chunked
    schedule's slack, to the page, so that admission is what it was: 7
    of a one-chip 4B pool (128 KB pages), 15 of a tp=4 shard's (32 KB),
    3 at 256 KB; and the pool now holds exactly what it hands out."""
    assert _pool_margin_pages(16, 64 * 1024 * 2) == 7
    assert _pool_margin_pages(16, 64 * 256 * 2) == 15
    assert _pool_margin_pages(16, 128 * 1024 * 2) == 3
    assert _pool_margin_pages(6, 1 << 21) == 0


def _force_interpret(monkeypatch):
    """The engine's Pallas decode path on the CPU (tests/test_prefix_split.py
    does the same): kernels interpreted, shape gates opened for tiny
    heads."""
    from sutro_tpu.ops import pallas_flash, pallas_kv, pallas_paged

    monkeypatch.setattr(
        pallas_paged, "paged_decode_supported", lambda *a: True
    )
    monkeypatch.setattr(
        pallas_paged, "paged_decode_attention",
        functools.partial(
            pallas_paged.paged_decode_attention, interpret=True
        ),
    )
    monkeypatch.setattr(
        pallas_kv, "kv_write_pallas",
        functools.partial(pallas_kv.kv_write_pallas, interpret=True),
    )
    monkeypatch.setattr(
        pallas_flash, "flash_prefill_supported", lambda *a, **k: False
    )


def _pages(name: str) -> float:
    return telemetry.REGISTRY.collect()[name]["series"].get("", 0.0)


@pytest.mark.parametrize("path", ["kernel", "gathered"])
def test_pages_fetched_against_pages_needed(path, byte_tok, monkeypatch):
    """Over a tiny engine's whole job: the kernel's dispatches fetch what
    their rows need rounded up to pages, so ``needed <= fetched < needed
    + rows x layers x steps``; the gathered-page path (no kernel) fetches
    every row's whole table. The ``decode_window`` spans carry the same
    pages as attrs, and sum to the counters."""
    if not telemetry.ENABLED:
        pytest.skip("telemetry is off")
    kernel = path == "kernel"
    if kernel:
        _force_interpret(monkeypatch)
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=10, max_model_len=80,
        decode_batch_size=4, use_pallas=kernel, param_dtype="float32",
        activation_dtype="float32", decode_multi_step=4,
    )
    runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
    assert runner.num_pages == runner.alloc_pages
    calls = []
    count = runner._count_kv_pages

    def spy(past_len, page_table, steps, pfx):
        calls.append((len(past_len), steps))
        return count(past_len, page_table, steps, pfx)

    monkeypatch.setattr(runner, "_count_kv_pages", spy)
    b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
    f0 = _pages("sutro_kv_pages_fetched_total")
    n0 = _pages("sutro_kv_pages_needed_total")
    # the recorder is a bounded ring: pick this job's spans by time
    rec = telemetry.RECORDER
    started = time.monotonic() - rec.epoch_mono
    done = {}
    assert b.run(
        [
            GenRequest(
                row_id=i, prompt_ids=np.array(byte_tok.encode(t), np.int32),
                max_new_tokens=11, temperature=0.0,
            )
            for i, t in enumerate(["hello there", "a", "the third row is longer"])
        ],
        on_result=lambda r: done.__setitem__(r.row_id, r),
    ) == "completed"
    fetched = _pages("sutro_kv_pages_fetched_total") - f0
    needed = _pages("sutro_kv_pages_needed_total") - n0
    L = runner.mcfg.num_attn_layers
    row_layer_steps = sum(rows * steps * L for rows, steps in calls)
    assert calls and needed > 0
    if kernel:
        assert needed <= fetched < needed + row_layer_steps
    else:
        assert fetched == row_layer_steps * ecfg.max_pages_per_seq
        assert fetched > needed
    spans = [
        s for s in rec.snapshot()
        if s["name"] == "decode_window" and s["t0_s"] >= started
        and "kv_pages_fetched" in (s.get("attrs") or {})
    ]
    assert spans
    assert sum(s["attrs"]["kv_pages_fetched"] for s in spans) == (
        pytest.approx(fetched, abs=0.1 * len(spans))
    )
    assert sum(s["attrs"]["kv_pages_needed"] for s in spans) == (
        pytest.approx(needed, abs=0.1 * len(spans))
    )
