"""What the two files of tests of ``tiny-xing-mhc`` share: the preset,
the reference's keys for it, the engine settings, sequences, page
tables, the reference's logits and the error both are read by."""

import json
from pathlib import Path

import numpy as np

from perfbench import correctness
from perfbench.reference import mhc_mla_moe
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.models.configs import MODEL_CONFIGS

MCFG = MODEL_CONFIGS["tiny-xing-mhc"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-xing-mhc-cpu.json").read_text()
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
    """The reference's logits at ``positions`` of ``seq``. The reference
    is one causal forward with no cache and no expert capacity: what
    follows a position cannot reach it, so ``seq`` is padded to a
    multiple of 64 and the tests' two dozen lengths compile two
    programs, not two dozen."""
    seq = np.asarray(seq, np.int32)
    padded = np.zeros((-len(seq) // 64 * -64,), np.int32)
    padded[: len(seq)] = seq
    return np.asarray(mhc_mla_moe.logits_at(
        KEYS, runner.params if params is None else params, padded,
        list(positions), **kw
    ))


def err(got, wanted):
    return float(np.max(correctness.position_errors(got, wanted)))
