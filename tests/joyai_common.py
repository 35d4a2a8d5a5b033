"""What the tiny-joyai tests share: the preset, the reference's keys for
it, a small engine configuration and the comparison the benchmark makes
(``max |system - reference| / max |reference|`` a position)."""

import json
import types
from pathlib import Path

import numpy as np

from perfbench import correctness
from perfbench.reference import mla_moe
from perfbench.sut import System
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.models.configs import MODEL_CONFIGS

#: both sides compute in float32 and differ in summation order, and in
#: the absorbed form by the order of two products (measured: 1e-6)
TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-joyai"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-joyai-cpu.json").read_text()
)
PS, MP = 8, 16


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=20, seed=11,
    )
    base.update(kw)
    return EngineConfig(**base)


def table_of(*pages):
    t = np.zeros((MP,), np.int32)
    t[: len(pages)] = pages
    return t


def sequence(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def want(params, seq, positions, keys=KEYS, **kw):
    return np.asarray(mla_moe.logits_at(keys, params, seq, list(positions), **kw))


def err(got, wanted):
    return float(np.max(correctness.position_errors(got, wanted)))


def system_of(runner):
    """The benchmark's own door onto a runner (perfbench/sut.py)."""
    sut = object.__new__(System)
    sut.ecfg, sut.engine_key = runner.ecfg, runner.mcfg.name
    sut.engine = types.SimpleNamespace(
        _runner_cache={runner.mcfg.name: (runner, None)}
    )
    return sut
