"""Streaming host pipeline at ~2k-row scale (stub runner, no device):
the chunked partial store + merge-on-read finalization must (a) yield
bit-identical, row-ordered results vs the in-memory assembly it
replaced, (b) keep row-granular flush/resume recovery, and (c) bound
peak materialized result rows by the chunk size."""

import time

import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.runner import next_bucket
from sutro_tpu.interfaces import JobStatus

N_ROWS = 2048
MAX_NEW = 12
CHUNK_ROWS = 256


class _StubRunner:
    """Device-free ModelRunner stand-in for the unconstrained pipelined
    path (mirrors benchmarks/profile_host_overhead._StubRunner)."""

    def __init__(self, ecfg, vocab):
        class _M:
            vocab_size = vocab

        self.ecfg = ecfg
        self.mcfg = _M()
        self.vocab = vocab
        self.sp = 1
        self.pp = 1
        self.num_pages = (
            1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq
        )
        self._rng = np.random.default_rng(0)

    def _logits(self, n, on_device):
        if not on_device:
            return np.zeros((n, self.vocab), np.float32)
        # what the scheduler asks for: the program's own row bucket,
        # and the routing counts (none)
        B = next_bucket(n, 1, 1 << 16)
        return np.zeros((B, self.vocab), np.float32), None

    def prefill_buckets(self, lengths):
        return [(len(lengths), max(lengths, default=1))]

    def prefill_batch(self, prompts, tables, on_device=False):
        return self._logits(len(prompts), on_device)

    def prefill_batch_at(self, rows, page_tables, starts, on_device=False):
        return self._logits(len(rows), on_device)

    def prefill(self, prompt, table, start=0, on_device=False):
        out = self._logits(1, on_device)
        return out if on_device else out[0]

    def merge_last(self, prev_last, refresh_mask, refresh_vals):
        return np.where(
            np.asarray(refresh_mask, bool),
            np.asarray(refresh_vals, np.int32),
            np.asarray(prev_last, np.int32),
        )

    def decode_multi_async(
        self, last, past_len, tables, rng, temp, top_p, steps,
        top_k=None, pfx=None,
    ):
        B = last.shape[0]
        toks = self._rng.integers(
            1, self.vocab, (steps, B), dtype=np.int64
        ).astype(np.int32)
        logps = np.full((steps, B), -1.0, np.float32)
        return toks, logps

    decode_multi = None  # force the pipelined async path

    def decode_step(
        self, last, past_len, tables, rng, temp, top_p,
        top_k=None, allowed=None, row_seeds=None, penalties=None,
        pfx=None,
    ):
        B = last.shape[0]
        toks = self._rng.integers(
            1, self.vocab, (B,), dtype=np.int64
        ).astype(np.int32)
        return toks, np.full((B,), -1.0, np.float32)


def _stub_ecfg():
    return EngineConfig(
        kv_page_size=16,
        max_pages_per_seq=8,
        decode_batch_size=64,
        max_model_len=128,
        use_pallas=False,
        param_dtype="float32",
        decode_multi_step=4,
        decode_lookahead=2,
        max_new_tokens=MAX_NEW,
    )


@pytest.fixture()
def stub_eng(tmp_path, monkeypatch):
    monkeypatch.setenv("SUTRO_HOME", str(tmp_path))
    monkeypatch.setenv("SUTRO_RESULT_CHUNK", str(CHUNK_ROWS))
    from sutro_tpu.engine.api import LocalEngine
    from sutro_tpu.engine.tokenizer import ByteTokenizer

    eng = LocalEngine(_stub_ecfg())

    def _get_runner(engine_key, mcfg):
        cached = eng._runner_cache.get(engine_key)
        if cached is not None:
            return cached
        runner = _StubRunner(eng.ecfg, vocab=mcfg.vocab_size)
        tok = ByteTokenizer(vocab_size=mcfg.vocab_size)
        eng._runner_cache[engine_key] = (runner, tok)
        return runner, tok

    eng._get_runner = _get_runner
    return eng


def _wait_terminal(eng, job_id, timeout=180):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if JobStatus(eng.job_status(job_id)).is_terminal():
            return JobStatus(eng.job_status(job_id))
        time.sleep(0.02)
    raise TimeoutError(job_id)


def _submit(eng, n_rows=N_ROWS):
    return eng.submit_batch_inference(
        {
            "model": "tiny-dense",
            "inputs": [f"review {i}: pretty good" for i in range(n_rows)],
            "system_prompt": "classify the sentiment",
            "sampling_params": {
                "max_new_tokens": MAX_NEW, "temperature": 0.7
            },
        }
    )


def test_streamed_results_bit_identical_to_in_memory_assembly(stub_eng):
    """results.parquet written by the merge-on-read streamed path must
    equal, bit for bit and in row order, what the old whole-job
    in-memory assembly produces from the same partial store."""
    job_id = _submit(stub_eng)
    assert _wait_terminal(stub_eng, job_id) == JobStatus.SUCCEEDED
    res = stub_eng.job_results(
        job_id, include_cumulative_logprobs=True
    )
    assert len(res["outputs"]) == N_ROWS
    assert all(o is not None for o in res["outputs"])

    # reference: the legacy assembly rule over the full partial store
    rows = stub_eng.jobs.read_partial(job_id)
    assert set(rows) == set(range(N_ROWS))
    df = stub_eng.jobs.read_results(job_id)
    assert list(df["row_id"]) == list(range(N_ROWS))  # row-ordered
    for i in range(N_ROWS):
        assert df["outputs"].iloc[i] == rows[i]["outputs"], i
        assert float(df["cumulative_logprobs"].iloc[i]) == float(
            rows[i]["cumulative_logprobs"]
        ), i
        assert int(df["gen_tokens"].iloc[i]) == int(
            rows[i]["gen_tokens"]
        ), i
        assert df["finish_reason"].iloc[i] == rows[i]["finish_reason"], i


def test_partial_flush_resume_stays_row_granular(stub_eng):
    """Cancel mid-run, then resume: rows already flushed to the chunked
    partial store are skipped (their bytes survive verbatim), the rest
    regenerate, and the final job is complete and ordered."""
    job_id = _submit(stub_eng)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if stub_eng.metrics.job(job_id).rows_completed >= CHUNK_ROWS:
            break
        time.sleep(0.005)
    stub_eng.cancel_job(job_id)
    status = _wait_terminal(stub_eng, job_id)
    if status == JobStatus.SUCCEEDED:
        pytest.skip("job raced to completion before cancel")
    deadline = time.monotonic() + 60
    while (
        stub_eng.job_status(job_id) == JobStatus.CANCELLING.value
        and time.monotonic() < deadline
    ):
        time.sleep(0.02)
    assert stub_eng.job_status(job_id) == JobStatus.CANCELLED.value

    flushed = {
        i: r
        for i, r in stub_eng.jobs.read_partial(job_id).items()
        if r.get("finish_reason") != "cancelled"
    }
    assert flushed, "cancel landed before any flush; nothing to verify"
    out = stub_eng.resume_job(job_id)
    assert out["resumed"] is True
    assert out["rows_already_done"] == len(flushed)
    assert _wait_terminal(stub_eng, job_id) == JobStatus.SUCCEEDED
    df = stub_eng.jobs.read_results(job_id)
    assert list(df["row_id"]) == list(range(N_ROWS))
    assert all(o is not None for o in df["outputs"])
    for i, r in flushed.items():
        # flushed rows were skipped, not regenerated
        assert df["outputs"].iloc[i] == r["outputs"], i


def test_peak_materialized_rows_bounded_by_chunk(stub_eng):
    """Neither the flush path nor finalization may materialize more
    than a chunk of result rows at once: flushes are bounded by the
    engine's flush batch, finalize buckets by SUTRO_RESULT_CHUNK."""
    from sutro_tpu.engine import api as api_mod

    peaks = {"flush": 0, "finalize": 0}
    jobs = stub_eng.jobs
    orig_flush = jobs.flush_partial
    orig_write = jobs.write_results_streamed

    def flush_spy(jid, rows):
        peaks["flush"] = max(peaks["flush"], len(rows))
        orig_flush(jid, rows)

    def write_spy(jid, num_rows, on_chunk=None):
        def chunk_spy(df):
            peaks["finalize"] = max(peaks["finalize"], len(df))
            if on_chunk is not None:
                on_chunk(df)

        orig_write(jid, num_rows, on_chunk=chunk_spy)

    jobs.flush_partial = flush_spy
    jobs.write_results_streamed = write_spy
    try:
        job_id = _submit(stub_eng)
        assert _wait_terminal(stub_eng, job_id) == JobStatus.SUCCEEDED
    finally:
        jobs.flush_partial = orig_flush
        jobs.write_results_streamed = orig_write

    assert 0 < peaks["flush"] <= api_mod._PARTIAL_FLUSH_EVERY
    assert 0 < peaks["finalize"] <= CHUNK_ROWS
    # the partial store is chunked on disk too — no monolithic file
    assert not (jobs._dir(job_id) / "partial.parquet").exists()
    assert len(jobs._partial_chunks(job_id)) >= N_ROWS // CHUNK_ROWS
