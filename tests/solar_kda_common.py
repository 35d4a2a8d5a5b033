"""What the two files of tests of ``tiny-solar-kda`` share: the preset,
the reference's keys for it, the engine settings, sequences, page
tables, the reference's logits and the error both are read by."""

import json
from pathlib import Path

import numpy as np

from perfbench import correctness
from perfbench.reference import kda_gqa_moe
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.models.configs import MODEL_CONFIGS

MCFG = MODEL_CONFIGS["tiny-solar-kda"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-solar-kda-cpu.json").read_text()
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


def want(runner, seq, positions, params=None, **kw):
    return np.asarray(kda_gqa_moe.logits_at(
        KEYS, runner.params if params is None else params, seq,
        list(positions), **kw
    ))


def err(got, wanted):
    return float(np.max(correctness.position_errors(got, wanted)))
