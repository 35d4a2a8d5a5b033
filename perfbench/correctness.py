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
   paged cache, against the plain float32 reference on the same weights
   (position by position for a dense model, by ``routed_rule`` for one
   that routes), and the Pallas kernels of the model's path lowered
   (``held_kernels``), none interpreted.

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


def position_errors(got, want) -> np.ndarray:
    """``max |system - reference| / max |reference|`` over the
    vocabulary, one number a position (leading axes kept)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.maximum(np.abs(want).max(axis=-1).astype(np.float64), 1e-30)
    return np.abs(got - want).max(axis=-1).astype(np.float64) / scale


def elementwise_rule(errs, tol: float, dtype: str, positions) -> List[str]:
    """Every position under the dtype's tolerance: the rule of a model
    whose every operation is continuous in its inputs."""
    problems = []
    for j, (err, pos) in enumerate(zip(errs, positions)):
        if not np.isfinite(err) or err > tol:
            kind = "prefill" if j == 0 else f"decode step {j}"
            problems.append(
                f"numbers: {kind} (position {pos}) logits differ from the "
                f"float32 reference by {err:.4g} of its largest magnitude "
                f"(limit {tol} for {dtype})"
            )
    return problems


# what a configuration file's ``numbers`` key may ask of the routed rule:
# no file can switch the check off
ROUTED_KEYS = {"sequences", "quantile", "cap", "why"}
ROUTED_OPTIONAL = {"cap_quantile"}
MIN_SEQUENCES, QUANTILE_RANGE, MAX_CAP = 4, (0.05, 0.5), 0.5
# ``cap`` held at a quantile of a run's positions in place of their
# maximum: a maximum over hundreds of positions has no largest value over
# seeds, so a limit sized from N seeds fails a correct system about once
# in N. Never under 0.99 (three positions in 288 at the least), and only
# where a run has the positions for that quantile to mean something
MIN_CAP_QUANTILE, CAP_QUANTILE_SEQUENCES = 0.99, 32


def routed_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The routed rule's parameters from the configuration's own file,
    refused where they ask for more room than this module allows."""
    spec = cfg.get("numbers")
    name = cfg.get("name")
    if (not isinstance(spec, dict) or not ROUTED_KEYS <= set(spec)
            or not set(spec) <= ROUTED_KEYS | ROUTED_OPTIONAL):
        raise ValueError(
            f"configuration {name!r} routes and needs a 'numbers' key with "
            f"{sorted(ROUTED_KEYS)} and at most {sorted(ROUTED_OPTIONAL)} more"
        )
    sequences, quantile, cap = spec["sequences"], spec["quantile"], spec["cap"]
    lo, hi = QUANTILE_RANGE
    if not isinstance(sequences, int) or sequences < MIN_SEQUENCES:
        raise ValueError(f"{name!r}: numbers.sequences must be an int >= {MIN_SEQUENCES}")
    if not isinstance(quantile, float) or not lo <= quantile <= hi:
        raise ValueError(f"{name!r}: numbers.quantile must be within {lo}..{hi}")
    if not isinstance(cap, float) or not 0.0 < cap <= MAX_CAP:
        raise ValueError(f"{name!r}: numbers.cap must be in (0, {MAX_CAP}]")
    if "cap_quantile" in spec:
        at = spec["cap_quantile"]
        if not isinstance(at, float) or not MIN_CAP_QUANTILE <= at <= 1.0:
            raise ValueError(
                f"{name!r}: numbers.cap_quantile must be within "
                f"{MIN_CAP_QUANTILE}..1.0"
            )
        if at < 1.0 and sequences < CAP_QUANTILE_SEQUENCES:
            raise ValueError(
                f"{name!r}: numbers.cap_quantile under 1.0 needs "
                f"numbers.sequences >= {CAP_QUANTILE_SEQUENCES}"
            )
    if not isinstance(spec["why"], str) or not spec["why"].strip():
        raise ValueError(f"{name!r}: numbers.why must say where the values come from")
    return spec


def routed_rule(errs, tol: float, dtype: str, spec: Dict[str, Any],
                where) -> Tuple[List[str], Dict[str, Any]]:
    """The rule of a model that routes (``reference/README.md``): the
    ``quantile`` of the errors over all positions, which is the error of
    the positions whose routing no rounding flipped, is held to the SAME
    tolerance as a dense model's every position; every position, the
    flipped ones too, is under ``cap``, or where the file states a
    ``cap_quantile`` under 1.0 that quantile of them is, and every
    position is finite. ``where[i]`` names position i."""
    errs = np.asarray(errs, np.float64).ravel()
    problems = []
    quantile, cap = float(spec["quantile"]), float(spec["cap"])
    cap_quantile = float(spec.get("cap_quantile", 1.0))
    finite = np.isfinite(errs)
    for i in np.flatnonzero(~finite):
        problems.append(f"numbers: {where[i]}: logits are not finite")
    read = float(np.quantile(errs[finite], quantile)) if finite.any() else float("inf")
    if read > tol:
        problems.append(
            f"numbers: the {quantile} quantile of {errs.size} positions' "
            f"errors against the float32 reference is {read:.4g} of the "
            f"largest logit (limit {tol} for {dtype})"
        )
    worst = int(np.argmax(np.where(finite, errs, np.inf)))
    if cap_quantile >= 1.0:
        capped = float(errs[worst])
        for i in np.flatnonzero(finite & (errs > cap)):
            problems.append(
                f"numbers: {where[i]}: logits differ from the float32 reference "
                f"by {errs[i]:.4g} of its largest magnitude (cap {cap})"
            )
    else:
        capped = (float(np.quantile(errs[finite], cap_quantile))
                  if finite.any() else float("inf"))
        if capped > cap:
            problems.append(
                f"numbers: the {cap_quantile} quantile of {errs.size} "
                f"positions' errors against the float32 reference is "
                f"{capped:.4g} of the largest logit (cap {cap}; the largest, "
                f"{errs[worst]:.4g}, at {where[worst]})"
            )
    facts = {
        "rule": "routed", "positions": int(errs.size), "quantile": quantile,
        "cap": cap, "cap_quantile": cap_quantile,
        "rel_err_quantile": read, "rel_err_cap_quantile": capped,
        "rel_err_max": float(errs[worst]), "worst": where[worst],
        "share_over_tolerance": float(np.mean(~finite | (errs > tol))),
    }
    return problems, facts


# the names the kernel clause holds where a configuration states no
# ``kernels``: the three attention kernels, which every model of heads
# the kernels take lowers. What only some models have (a grouped product,
# a state read, a state commit) is counted beside them and held only by a
# file that lists it
DEFAULT_KERNELS = ("paged_decode", "flash_prefill", "kv_write")


def held_kernels(cfg: Dict[str, Any], paths: Dict[str, Dict[str, int]]
                 ) -> Tuple[List[str], List[str]]:
    """(names that must read ``lowered > 0``, names that must read
    ``lowered == 0``) for a run that uses the kernels; neither may read
    ``interpreted > 0``. A configuration's ``kernels`` key lists the
    names its model's path lowers on the chip (a call's own shape gate
    picks its path, so a model of 64-wide heads lowers no attention
    kernel): every name listed is held lowered and every other name
    counted is held NOT lowered, so the list cannot go stale unseen.
    Without the key: ``DEFAULT_KERNELS`` lowered, nothing else held."""
    listed = cfg.get("kernels")
    if listed is None:
        return list(DEFAULT_KERNELS), []
    name = cfg.get("name")
    if (not isinstance(listed, list) or len(set(listed)) != len(listed)
            or not all(isinstance(k, str) and k for k in listed)):
        raise ValueError(f"{name!r}: kernels must be a list of distinct names")
    if (cfg.get("engine") or {}).get("use_pallas") is False:
        raise ValueError(
            f"{name!r}: kernels lists what the path lowers, and the engine "
            "settings switch the kernels off"
        )
    return list(listed), [k for k in paths if k not in listed]


def kernel_problems(cfg: Dict[str, Any], paths: Dict[str, Dict[str, int]]
                    ) -> List[str]:
    """The kernel clause of check 3, for a run that uses the kernels."""
    lowered, not_lowered = held_kernels(cfg, paths)
    problems = []
    for name in lowered:
        p = paths.get(name)
        if p is None or p["lowered"] <= 0 or p["interpreted"] > 0:
            problems.append(
                f"numbers: kernel {name} was not lowered for the device: {p}"
            )
    for name in not_lowered:
        p = paths[name]
        if p["lowered"] > 0 or p["interpreted"] > 0:
            problems.append(
                f"numbers: kernel {name} was lowered and the configuration's "
                f"kernels do not list it: {p}"
            )
    return problems


def numbers(sut, cfg: Dict[str, Any], seed: int) -> Tuple[List[str], Dict[str, Any]]:
    """Check 3. Returns (problems, facts); the facts carry the measured
    errors so a run's earlier lines show how close the system came. The
    rule is the reference family's: a module that says ``ROUTED`` is
    held by ``routed_rule`` over several sequences, any other position
    by position over one."""
    reference = importlib.import_module(
        "perfbench.reference." + cfg.get("reference", "qwen3_dense")
    )
    tol_table = json.loads((HERE / "reference" / "tolerance.json").read_text())
    dtype = sut.serving_dtype()
    tol = float(tol_table[dtype])
    rng = np.random.default_rng([int(seed), 0x1095])
    positions = list(range(N_PREFILL - 1, N_PREFILL + N_DECODE))
    routed = bool(getattr(reference, "ROUTED", False))
    spec = routed_spec(cfg) if routed else None
    # byte-range ids: what the tokenizer produces from text
    ids = rng.integers(
        0, 256, (spec["sequences"] if routed else 1, N_PREFILL + N_DECODE)
    ).astype(np.int32)
    if not routed:
        got = sut.logits_through_cache(ids[0], N_PREFILL, N_DECODE)
        want = reference.logits_at(cfg, sut.weights(), ids[0], positions)
        errs = [float(e) for e in position_errors(got, want)]
        problems = elementwise_rule(errs, tol, dtype, positions)
        facts = {"rel_err_prefill": errs[0], "rel_err_decode_max": max(errs[1:]),
                 "tolerance": tol, "dtype": dtype}
    else:
        got = sut.logits_through_cache(ids, N_PREFILL, N_DECODE)
        want, ties = [], []
        for seq in ids:
            w, t = reference.logits_and_near_ties(cfg, sut.weights(), seq, positions)
            want.append(np.asarray(w, np.float32))
            ties.append(np.asarray(t))
        errs = position_errors(got, np.stack(want))
        where = [f"sequence {s} position {pos}"
                 for s in range(len(ids)) for pos in positions]
        problems, facts = routed_rule(errs, tol, dtype, spec, where)
        ties = np.stack(ties).ravel()
        facts.update(
            tolerance=tol, dtype=dtype, sequences=len(ids),
            near_tie_margin=float(reference.TIE_MARGIN),
            near_ties_mean=float(ties.mean()),
            near_ties_at_worst=int(ties[where.index(facts["worst"])]),
        )
    paths = sut.kernel_paths()
    facts["kernel_paths"] = paths
    if sut.uses_kernels():
        problems.extend(kernel_problems(cfg, paths))
    return problems, facts


def compared(facts: Dict[str, Any]) -> Dict[str, List[float]]:
    """Each number the numbers check compared, beside its limit, from
    the check's own facts: ``{name: [number, limit]}``."""
    if not facts:
        return {}
    if facts.get("rule") == "routed":
        return {
            "rel_err_quantile": [facts["rel_err_quantile"], facts["tolerance"]],
            "rel_err_cap_quantile": [facts["rel_err_cap_quantile"], facts["cap"]],
        }
    return {
        "rel_err_prefill": [facts["rel_err_prefill"], facts["tolerance"]],
        "rel_err_decode_max": [facts["rel_err_decode_max"], facts["tolerance"]],
    }
