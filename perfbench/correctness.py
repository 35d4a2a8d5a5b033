"""What decides ``correct``: three checks, each independent of the seed,
of timing and of which requests shared a batch.

1. accounting: every job that ended ended SUCCEEDED with one clean row
   an input inside its token budget; every chat that ended has a finish
   reason the API defines and 1..max_tokens tokens (checked per job and
   per chat by the generators, collected here);
2. schemas: every row that says its schema completed parses and
   validates (``generators/batch_jobs.py``); rows that ended on
   ``length`` are counted and reported, not parsed;
3. numbers: the system's logits, prefill then decode steps through its
   paged cache, against the plain float32 reference on the same weights,
   and every Pallas kernel on the path lowered, none interpreted.

Never here: equality of tokens or text between two requests, paths or
runs; whether a chat overlapped a job; any latency. A request that
failed is in ``failed``.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Any, Dict, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
N_PREFILL, N_DECODE = 192, 8


def accounting(log) -> Tuple[List[str], Dict[str, int]]:
    """Problems found by the generators, plus the chats' own rules."""
    problems: List[str] = []
    facts = {"jobs_ended": 0, "jobs_cancelled": 0, "length_rows": 0,
             "chats_ended": 0}
    for job in log.jobs:
        if job["status"] == "CANCELLED":
            facts["jobs_cancelled"] += 1
        elif job["status"] is not None:
            facts["jobs_ended"] += 1
        facts["length_rows"] += job["length_rows"]
        problems.extend(job["problems"])
    for c in log.chats:
        if c["done"] is None or c["error"] is not None:
            continue  # failed or unfinished: not an output to judge
        facts["chats_ended"] += 1
        where = f"chat {c['trace_id']}"
        reason = c["finish_reason"]
        if reason is None or reason == "cancelled" or str(reason).startswith("error"):
            problems.append(f"{where}: finish_reason {reason!r}")
        n = c["tokens"]
        if n is None or not 1 <= int(n) <= int(c["max_tokens"]):
            problems.append(
                f"{where}: completion tokens {n} outside [1, {c['max_tokens']}]"
            )
    return problems, facts


def numbers(sut, cfg: Dict[str, Any], seed: int) -> Tuple[List[str], Dict[str, Any]]:
    """Check 3. Returns (problems, facts); the facts carry the measured
    errors so a run's earlier lines show how close the system came."""
    reference = importlib.import_module(
        "perfbench.reference." + cfg.get("reference", "qwen3_dense")
    )
    tol_table = json.loads((HERE / "reference" / "tolerance.json").read_text())
    dtype = sut.serving_dtype()
    tol = float(tol_table[dtype])
    rng = np.random.default_rng([int(seed), 0x1095])
    # byte-range ids: what the tokenizer produces from text
    ids = rng.integers(0, 256, N_PREFILL + N_DECODE).astype(np.int32)
    got = sut.logits_through_cache(ids, N_PREFILL, N_DECODE)
    positions = list(range(N_PREFILL - 1, N_PREFILL + N_DECODE))
    want = np.asarray(
        reference.logits_at(cfg, sut.weights(), ids, positions), np.float32
    )
    problems: List[str] = []
    errs = []
    for j, pos in enumerate(positions):
        scale = float(np.abs(want[j]).max())
        err = float(np.abs(got[j] - want[j]).max()) / max(scale, 1e-30)
        errs.append(err)
        if not np.isfinite(err) or err > tol:
            kind = "prefill" if j == 0 else f"decode step {j}"
            problems.append(
                f"numbers: {kind} (position {pos}) logits differ from the "
                f"float32 reference by {err:.4g} of its largest magnitude "
                f"(limit {tol} for {dtype})"
            )
    facts = {"rel_err_prefill": errs[0], "rel_err_decode_max": max(errs[1:]),
             "tolerance": tol, "dtype": dtype}
    paths = sut.kernel_paths()
    facts["kernel_paths"] = paths
    if sut.uses_kernels():
        for name, p in paths.items():
            if p["lowered"] <= 0 or p["interpreted"] > 0:
                problems.append(
                    f"numbers: kernel {name} was not lowered for the device: {p}"
                )
    return problems, facts
