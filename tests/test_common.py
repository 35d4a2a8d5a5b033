"""Contract-layer tests: data prep, schema normalization, model catalog.

Models the reference's TestPrepareInputData / input-validation coverage
(/root/reference/tests/test_sdk.py:326-334, 787-804) but kept green —
SURVEY §4 notes the reference suite is stale by design.
"""

import pandas as pd
import pytest
from pydantic import BaseModel

from sutro_tpu.common import (
    MODEL_CATALOG,
    do_dataframe_column_concatenation,
    normalize_output_schema,
    prepare_input_data,
)
from sutro_tpu.models.configs import MODEL_CONFIGS


def test_list_passthrough():
    assert prepare_input_data(["a", "b", 3]) == ["a", "b", "3"]


def test_dataframe_requires_column():
    df = pd.DataFrame({"x": ["a", "b"]})
    with pytest.raises(ValueError, match="column"):
        prepare_input_data(df)


def test_dataframe_column():
    df = pd.DataFrame({"x": ["a", "b"], "y": [1, 2]})
    assert prepare_input_data(df, column="x") == ["a", "b"]


def test_column_concatenation_with_separators():
    df = pd.DataFrame({"title": ["t1", "t2"], "body": ["b1", "b2"]})
    out = do_dataframe_column_concatenation(df, ["title", ": ", "body"])
    assert out == ["t1: b1", "t2: b2"]


def test_dataset_id_passthrough():
    assert prepare_input_data("dataset-abc123") == "dataset-abc123"


def test_csv_and_parquet(tmp_path):
    df = pd.DataFrame({"c": ["r1", "r2"]})
    csv = tmp_path / "f.csv"
    df.to_csv(csv, index=False)
    assert prepare_input_data(str(csv), column="c") == ["r1", "r2"]
    pq = tmp_path / "f.parquet"
    df.to_parquet(pq)
    assert prepare_input_data(str(pq), column="c") == ["r1", "r2"]


def test_txt(tmp_path):
    p = tmp_path / "f.txt"
    p.write_text("l1\nl2\n\n")
    assert prepare_input_data(str(p)) == ["l1", "l2"]


def test_unsupported_input():
    with pytest.raises(ValueError):
        prepare_input_data(42)


def test_normalize_output_schema_pydantic():
    class S(BaseModel):
        sentiment: str
        score: int

    js = normalize_output_schema(S)
    assert js["properties"]["sentiment"]["type"] == "string"
    assert normalize_output_schema(None) is None
    assert normalize_output_schema({"type": "object"}) == {"type": "object"}
    with pytest.raises(ValueError):
        normalize_output_schema("not-a-schema")


def test_catalog_maps_to_engine_configs():
    # every public (non-Function) model resolves to a real engine config
    for name, meta in MODEL_CATALOG.items():
        assert meta["engine_key"] in MODEL_CONFIGS, name


def test_catalog_no_duplicates():
    # the reference's duplicate "llama-3.3-70b" literal is not reproduced
    names = list(MODEL_CATALOG)
    assert len(names) == len(set(names))


def _arm_cache_rule(monkeypatch):
    """Fresh latch, CPU opt-in (the suite runs on CPU, where the cache
    is otherwise off), and a recorder in place of jax.config.update so
    the suite's own session cache dir is never moved."""
    import jax

    from sutro_tpu.engine import config as cfgmod

    monkeypatch.setattr(cfgmod, "_CACHE_ENABLED", False)
    monkeypatch.setenv("SUTRO_COMPILE_CACHE", "1")
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    return cfgmod, updates


def test_compile_cache_optout(monkeypatch):
    """SUTRO_COMPILE_CACHE=0 disables: nothing is configured."""
    cfgmod, updates = _arm_cache_rule(monkeypatch)
    monkeypatch.setenv("SUTRO_COMPILE_CACHE", "0")
    cfgmod.enable_compile_cache()
    assert cfgmod._CACHE_ENABLED is False
    assert updates == {}


def test_compile_cache_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set => the program sets no cache
    directory in code (JAX binds the variable itself at import)."""
    cfgmod, updates = _arm_cache_rule(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    cfgmod.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in updates


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch, tmp_path):
    """Unset => one fixed directory inside the checkout, the same
    whatever SUTRO_HOME says: the path is part of the cache key, so a
    cache that follows a temp SUTRO_HOME never hits."""
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    seen = []
    for home in ("home-a", "home-b"):
        cfgmod, updates = _arm_cache_rule(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("SUTRO_HOME", str(tmp_path / home))
        cfgmod.enable_compile_cache()
        seen.append(updates["jax_compilation_cache_dir"])
    assert seen == [str(repo / ".xla_cache")] * 2
    assert (repo / ".xla_cache").is_dir()


def test_metrics_bus_conflates_slow_subscribers():
    """The progress bus must hold O(1) pending state per subscriber: a
    consumer that never drains cannot accumulate an unbounded queue,
    and when it finally reads it sees the LATEST progress plus the
    monotonically merged token totals, then the finish sentinel."""
    from sutro_tpu.engine.metrics import JobMetrics

    jm = JobMetrics()
    it = jm.subscribe()
    first = next(it)  # snapshot
    assert first == {"update_type": "progress", "result": 0}
    # thousands of producer updates while the consumer sleeps
    for i in range(5000):
        jm.progress(i)
        jm.tokens({"output_tokens": i})
    jm.tokens({"input_tokens": 77})
    sub = jm._subscribers[0]
    assert sub.progress == 4999  # conflated, not queued
    assert sub.tokens["output_tokens"] == 4999
    assert sub.tokens["input_tokens"] == 77  # partials merged
    jm.finish()
    updates = list(it)
    kinds = [u["update_type"] for u in updates]
    assert kinds.count("progress") == 1
    assert updates[kinds.index("progress")]["result"] == 4999


def test_metrics_bus_final_update_beats_sentinel():
    """A progress update published just before finish must still be
    delivered — pending state drains before the done flag is honored."""
    from sutro_tpu.engine.metrics import JobMetrics

    jm = JobMetrics()
    it = jm.subscribe()
    next(it)
    jm.progress(41)
    jm.progress(42)
    jm.finish()
    updates = list(it)
    assert {"update_type": "progress", "result": 42} in updates


def test_batched_progress_rule():
    from sutro_tpu.engine.metrics import BatchedProgress, JobMetrics

    jm = JobMetrics()
    seen = []
    orig = jm.progress
    jm.progress = lambda n: (seen.append(n), orig(n))
    bp = BatchedProgress(jm, every_rows=10)
    for i in range(25):
        bp.update(i)
    bp.flush(25)
    assert seen == [9, 19, 25]  # one publish per 10 rows + terminal
