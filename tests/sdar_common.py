"""What the files of tests of ``tiny-sdar`` share: the preset, the
reference's keys for it, the engine settings, sequences, a cached runner
a setting (compiled programs are per runner), the reference's logits and
the error both are read by."""

import functools
import json
from pathlib import Path

import numpy as np

from perfbench import correctness
from perfbench.reference import sdar_moe
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.models.configs import MODEL_CONFIGS

MCFG = MODEL_CONFIGS["tiny-sdar"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-sdar-cpu.json").read_text()
)
BK, MASK = MCFG.block_length, MCFG.mask_token_id
PS, MP = 8, 16


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=16, seed=11,
    )
    base.update(kw)
    return EngineConfig(**base)


@functools.lru_cache(maxsize=None)
def runner(**kw):
    """One runner a setting for the whole file: its programs compile
    once."""
    from sutro_tpu.engine.runner import ModelRunner

    return ModelRunner(MCFG, engine(**kw))


def sequence(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def want(params, seq, positions, control=None, n_prefill=0):
    """The reference's logits at ``positions`` of ``seq`` (a control:
    ``sdar_moe.control_logits``)."""
    if control is None:
        return np.asarray(sdar_moe.logits_at(KEYS, params, seq, list(positions)))
    return np.asarray(sdar_moe.control_logits(
        KEYS, params, seq, n_prefill, list(positions), control
    ))


def err(got, wanted):
    return float(np.max(correctness.position_errors(got, wanted)))


def generate(batcher, prompts, caps, on_result=None, **sampling):
    """``{row: GenResult}`` of the prompts through a batcher."""
    from sutro_tpu.engine.scheduler import GenRequest

    res = {}
    sampling.setdefault("temperature", 0.0)
    batcher.run(
        [GenRequest(row_id=i, prompt_ids=np.asarray(p, np.int32),
                    max_new_tokens=int(c), **sampling)
         for i, (p, c) in enumerate(zip(prompts, caps))],
        on_result=lambda r: res.__setitem__(r.row_id, r),
    )
    return res
