"""What the tiny-glm-dsa tests share: the preset, the reference's keys
for it, a small engine configuration and the comparison the benchmark
makes (``max |system - reference| / max |reference|`` a position)."""

import functools
import json
from pathlib import Path

import numpy as np

from perfbench import correctness
from perfbench.reference import dsa_moe
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.joyai_common import (  # noqa: F401
    MP, PS, err, sequence, system_of, table_of,
)
from tests.joyai_common import engine as _engine

#: both sides compute in float32 and differ in summation order, and in
#: the absorbed form by the order of two products (measured: 2e-6); a
#: selection that differed by one position would read 1e-2 and more
TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-glm-dsa"]
TOPK = MCFG.index_topk
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-glm-dsa-cpu.json").read_text()
)
#: the same small engine, prompts chunked at 24 (three pages)
engine = functools.partial(_engine, prefill_chunk=24)


def want(params, seq, positions, keys=KEYS, **kw):
    return np.asarray(dsa_moe.logits_at(keys, params, seq, list(positions), **kw))
