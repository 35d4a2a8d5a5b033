"""Decode-loop microbenchmark: a bare ``ModelRunner`` at full decode
batch on the accelerator (paged KV, batched sampling, fused windows).

NOT the system's benchmark: it bypasses ``LocalEngine``, the scheduler
and the HTTP daemon (ROADMAP 1.1 builds the benchmark; ``chip_smoke.py``
is the check that the main path runs on the chip). It measures on a TPU
only — without one it exits non-zero rather than time a stand-in model
on the CPU under a device metric's name.

Prints ONE JSON line: metric, value, unit, the device as JAX reports it,
and both device counts (the runner's mesh span — the per-chip divisor —
and every device the host has).

Env knobs: SUTRO_BENCH_MODEL, SUTRO_BENCH_BATCH, SUTRO_BENCH_STEPS,
SUTRO_BENCH_PROMPT, SUTRO_BENCH_MULTI (decode steps fused per device
program; 1 = per-token dispatch), SUTRO_BENCH_QUANT,
SUTRO_BENCH_KV_QUANT.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def main() -> None:
    from sutro_tpu.engine.softdeadline import arm_from_env

    arm_from_env()
    import jax

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    model_key = os.environ.get("SUTRO_BENCH_MODEL", "qwen3-0.6b")
    B = int(os.environ.get("SUTRO_BENCH_BATCH", "64"))
    steps = int(os.environ.get("SUTRO_BENCH_STEPS", "128"))
    prompt_len = int(os.environ.get("SUTRO_BENCH_PROMPT", "128"))
    multi = int(os.environ.get("SUTRO_BENCH_MULTI", "16"))

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; JAX found platform {platform!r}"
        )
    steps = -(-steps // multi) * multi  # whole windows

    mcfg = MODEL_CONFIGS[model_key]
    ecfg = EngineConfig(
        kv_page_size=64,
        max_pages_per_seq=(prompt_len + steps) // 64 + 2,
        decode_batch_size=B,
        max_model_len=prompt_len + steps + 64,
        param_dtype="bfloat16",
        use_pallas=None,
        # weight-only int8 (ops/quant.py) — lets 8B-class models fit a
        # single v5e chip (SUTRO_BENCH_QUANT=int8)
        quantize=os.environ.get("SUTRO_BENCH_QUANT") or None,
        # int8 KV cache (kvcache.py): halves decode HBM traffic
        # (SUTRO_BENCH_KV_QUANT=int8)
        kv_quantize=os.environ.get("SUTRO_BENCH_KV_QUANT") or None,
    )
    runner = ModelRunner(mcfg, ecfg)
    MP = ecfg.max_pages_per_seq
    PS = ecfg.kv_page_size

    # fill every slot with a prompt
    rng = np.random.default_rng(0)
    pages_per_seq = (prompt_len + steps) // PS + 1
    tables = np.zeros((B, MP), np.int32)
    next_page = 1
    for b in range(B):
        tables[b, :pages_per_seq] = np.arange(
            next_page, next_page + pages_per_seq
        )
        next_page += pages_per_seq
    prompt = rng.integers(0, min(mcfg.vocab_size, 50000), prompt_len).astype(
        np.int32
    )
    pbs = ecfg.prefill_batch_size
    if prompt_len > ecfg.prefill_chunk:
        # long prompts: per-row chunked prefill (bounded transients)
        runner.prefill(prompt, tables[0])  # warmup/compile
        t_prefill0 = time.monotonic()
        for b in range(1, B):
            runner.prefill(prompt, tables[b])
        t_prefill = time.monotonic() - t_prefill0
        prefill_tok_s = (B - 1) * prompt_len / max(t_prefill, 1e-9)
    else:
        # warm the batched-prefill compile outside the timed window
        pbs = min(pbs, B)
        runner.prefill_batch([prompt] * pbs, tables[:pbs])
        t_prefill0 = time.monotonic()
        timed_rows = 0
        if B > pbs:
            for off in range(pbs, B, pbs):
                group = list(range(off, min(off + pbs, B)))
                runner.prefill_batch([prompt] * len(group), tables[group])
                timed_rows += len(group)
        else:  # whole batch fit the warmup group: time a steady rerun
            runner.prefill_batch([prompt] * pbs, tables[:pbs])
            timed_rows = pbs
        t_prefill = time.monotonic() - t_prefill0
        prefill_tok_s = timed_rows * prompt_len / max(t_prefill, 1e-9)

    last = rng.integers(0, 256, B).astype(np.int32)
    past_len = np.full((B,), prompt_len, np.int32)
    temp = np.full((B,), 0.7, np.float32)
    top_p = np.full((B,), 0.95, np.float32)

    # warmup (compile)
    if multi > 1:
        toks_w, _ = runner.decode_multi_async(
            last, past_len, tables, jax.random.PRNGKey(0), temp, top_p,
            multi,
        )
        past_len += multi
        last = toks_w[-1]
        jax.block_until_ready(toks_w)
    else:
        toks, _ = runner.decode_step(
            last, past_len, tables, jax.random.PRNGKey(0), temp, top_p
        )
        past_len += 1
        last = toks.astype(np.int32)

    t0 = time.monotonic()
    if multi > 1:
        # pipelined windows: chain each window off the previous one's
        # device-resident last-token row, fetching window i-1's tokens
        # while window i computes — exactly the scheduler's pipelined
        # path (decode_lookahead=2), so the host<->device round trip
        # overlaps device compute on both the dispatch and fetch side
        prev = None
        for i in range(steps // multi):
            toks_w, _ = runner.decode_multi_async(
                last, past_len, tables, jax.random.PRNGKey(i + 1),
                temp, top_p, multi,
            )
            past_len += multi
            last = toks_w[-1]
            if prev is not None:
                np.asarray(prev)  # host-side consume, one window behind
            prev = toks_w
        np.asarray(prev)
    else:
        for i in range(steps):
            toks, _ = runner.decode_step(
                last, past_len, tables, jax.random.PRNGKey(i + 1), temp,
                top_p,
            )
            past_len += 1
            last = toks.astype(np.int32)
    dt = time.monotonic() - t0

    # the devices the runner's mesh spans, not every device on the host
    n_chips = runner.n_devices
    decode_tok_s = B * steps / dt
    value = decode_tok_s / n_chips

    # self-grading vs the hardware roofline: every captured number
    # carries its analytic denominator
    from sutro_tpu.engine import roofline

    device_kind = jax.devices()[0].device_kind
    grade = roofline.grade_decode(
        value,
        batch=B,
        bytes_per_step=roofline.decode_bytes_per_step(
            param_bytes=roofline.param_bytes_of(runner.params),
            batch=B,
            avg_ctx=prompt_len + steps / 2,
            num_layers=mcfg.num_layers,
            kv_heads=mcfg.num_kv_heads,
            head_dim=mcfg.head_dim,
            kv_dtype_bytes=1 if ecfg.kv_quantize == "int8" else 2,
        ),
        device_kind=device_kind,
    )
    grade.update(
        roofline.grade_prefill(
            # MFU is per chip: prefill_tok_s aggregates all devices
            prefill_tok_s / n_chips,
            n_params=roofline.param_count_of(runner.params),
            device_kind=device_kind,
        )
    )

    print(
        json.dumps(
            {
                "metric": f"decode tokens/sec/chip ({model_key}, bs{B})",
                "value": round(value, 2),
                "unit": "tok/s/chip",
                "platform": platform,
                "device_kind": device_kind,
                "n_chips": n_chips,
                "host_devices": jax.device_count(),
                "quant": ecfg.quantize or "none",
                "kv_quant": ecfg.kv_quantize or "none",
                "steps": steps,
                "prompt_len": prompt_len,
                "prefill_tok_s": round(prefill_tok_s, 1),
                **grade,
            }
        )
    )


if __name__ == "__main__":
    main()
