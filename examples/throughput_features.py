"""Round-4 throughput features on one templated job + an interactive
job co-batched alongside it.

Every row of a templated job (classify/extract) shares its system
prompt, so the engine:
- prefills the shared prefix ONCE (prefix_cache, on by default) and
  shares its KV pages read-only across rows;
- optionally computes the prefix's DECODE attention once per step for
  the whole batch (prefix_split, Hydragen-style carry injection —
  Pallas path, chip-A/B gated);
- stores the KV cache int8 with per-token scales (kv_quantize) for
  2x page capacity / half the decode HBM traffic;
- co-batches a small interactive job into the SAME decode batch
  without preempting the big job's slots.
"""

import pandas as pd

from _common import example_client


def main() -> None:
    so, model, _ = example_client(
        __doc__,
        engine_config=dict(
            kv_quantize="int8",      # int8 KV cache
            # prefix_split=True,     # flip after the chip A/B
        ),
    )
    reviews = pd.DataFrame(
        {"review_text": [f"review {i}: works great" for i in range(64)]}
    )
    big = so.classify(
        reviews,
        column="review_text",
        classes=["positive", "negative", "neutral"],
        model=model,
        job_priority=1,
    )
    print(big.head())
    # an interactive priority-0 submit rides the same decode batch as
    # a running priority-1 job (co-batching: no preemption,
    # ~single-job latency)
    jid = so.infer(
        ["summarize: the device is reliable"],
        model=model,
        job_priority=0,
    )
    print(so.await_job_completion(jid))


if __name__ == "__main__":
    main()
