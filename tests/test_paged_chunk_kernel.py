"""A chunk of several tokens over a paged past where the pages lie
(``ops/pallas_chunk.paged_chunk_attention``, interpret mode on the CPU)
against ``chunk_attention``'s gathered path on the same operands: ONE
parametrised test through ``chunk_attention(use_pallas=True)`` itself,
so that every case also says which path the dispatch took and what it
counted. Chunks of 2, 17 and 256 tokens; groups of 1, 3 and 4 query
heads a KV head; rows with no past, a past that ends inside a page, on a
page's edge and at the table's end; ``valid_len`` under ``T`` and padding
rows (nothing valid, start 0, a table of zeros: the garbage page);
tables that share their first pages and tables of scattered pages; a
sliding window; float32 and bfloat16 operands at the tolerances
``flash_prefill``'s tests hold it to; and every refusal of the gate,
which lands on the gather and counts ``reference``. Then a constrained
job on the CPU whose tokens with the path on are the gathered path's.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops import lowering, pallas_chunk
from sutro_tpu.ops.attention import chunk_attention

L, LAYER, PS, MP = 3, 1, 16, 20
# no past, inside a page, a page's edge, the table full, several steps
# of pages ending off an edge, and a padding row
PAST = [0, 5, 2 * PS, MP * PS, 150, 0]


def _valid(T):
    # whole, whole, one short, whole, ONE token, a padding row
    return [T, T, max(T - 1, 1), T, 1, 0]


@dataclasses.dataclass(frozen=True)
class Case:
    T: int = 17
    G: int = 4
    dtype: str = "float32"
    Dh: int = 128
    tables: str = "scattered"      # or "shared": a prefix group's
    window: int = 0
    kernel: bool = True            # False: the gate refuses the call
    extra: str = ""                # the operand the gate refuses

    @property
    def id(self):
        parts = [f"T{self.T}", f"G{self.G}", self.dtype, self.tables]
        if self.window:
            parts.append(f"window{self.window}")
        if self.Dh != 128:
            parts.append(f"head{self.Dh}")
        return "-".join(parts + ([self.extra] if self.extra else []))


CASES = [
    Case(T=T, G=G, dtype=dtype)
    for T in (2, 17, 256) for G in (1, 4) for dtype in ("float32", "bfloat16")
] + [
    Case(tables="shared"), Case(tables="shared", dtype="bfloat16"),
    Case(T=256, tables="shared"),
    Case(G=3), Case(window=6), Case(T=256, window=40),
    # what the gate refuses: the gather, counted ``reference``
    Case(Dh=64, kernel=False),
    Case(kernel=False, extra="scales"),
    Case(kernel=False, extra="sink"),
    Case(kernel=False, extra="live_window"),
    Case(kernel=False, extra="mesh"),
    Case(kernel=False, extra="window_buffer"),
]


def _operands(c: Case):
    rng = np.random.default_rng([60, c.T, c.G, c.Dh])
    dtype = jnp.dtype(c.dtype)
    B, KVH = len(PAST), 2
    NH, KD = KVH * c.G, KVH * c.Dh
    q = jnp.asarray(rng.standard_normal((B, c.T, NH, c.Dh)), dtype)
    k = jnp.asarray(rng.standard_normal((B, c.T, KVH, c.Dh)), dtype)
    v = jnp.asarray(rng.standard_normal((B, c.T, KVH, c.Dh)), dtype)
    NP = 1 + B * MP
    kp = rng.standard_normal((L, NP, PS, KD))
    vp = rng.standard_normal((L, NP, PS, KD))
    table = 1 + rng.permutation(B * MP).reshape(B, MP).astype(np.int32)
    if c.tables == "shared":
        table[:, :2] = table[0, :2]     # a prefix group's first pages
    table[-1] = 0                       # the padding row: the garbage page
    past_len = jnp.asarray(PAST, jnp.int32)
    ops = dict(
        positions=past_len[:, None] + jnp.arange(c.T, dtype=jnp.int32)[None],
        valid_len=jnp.asarray(_valid(c.T), jnp.int32),
        past_k_pages=jnp.asarray(kp, dtype), past_v_pages=jnp.asarray(vp, dtype),
        layer=jnp.asarray(LAYER, jnp.int32), page_table=jnp.asarray(table),
        past_len=past_len, window=jnp.asarray(c.window, jnp.int32),
    )
    if c.extra == "scales":
        ops["past_k_pages"] = jnp.asarray(np.round(kp * 20), jnp.int8)
        ops["past_v_pages"] = jnp.asarray(np.round(vp * 20), jnp.int8)
        ops["past_k_scale"] = jnp.full((L, NP, PS), 0.05, jnp.float32)
        ops["past_v_scale"] = jnp.full((L, NP, PS), 0.05, jnp.float32)
    elif c.extra == "sink":
        ops["sink"] = jnp.asarray(rng.standard_normal((NH,)), jnp.float32)
    elif c.extra == "live_window":
        ops["live_window"] = 16
        ops["window"] = jnp.asarray(16, jnp.int32)
    elif c.extra == "mesh":
        ops["kernel_mesh"] = jax.sharding.Mesh(
            np.array(jax.devices()[:1]), ("model",)
        )
    elif c.extra == "window_buffer":
        ops["win_k"] = jnp.asarray(rng.standard_normal((B, 4, KD)), dtype)
        ops["win_v"] = jnp.asarray(rng.standard_normal((B, 4, KD)), dtype)
        ops["win_len"] = jnp.asarray(2, jnp.int32)
        ops["positions"] = ops["positions"] + 2
    return q, k, v, ops


@pytest.fixture
def interpreted_chunk(monkeypatch):
    """The chunk kernel on the CPU: the same call, interpreted, and
    traced anew (the counts are a trace's)."""
    pallas_chunk.paged_chunk_attention.clear_cache()
    monkeypatch.setattr(
        pallas_chunk, "paged_chunk_attention",
        functools.partial(pallas_chunk.paged_chunk_attention, interpret=True),
    )


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.id)
def test_a_paged_chunk_is_the_gathered_chunk(case, interpreted_chunk):
    q, k, v, ops = _operands(case)
    chunk0 = lowering.paged_chunk_counts()
    decode0 = lowering.snapshot()["paged_decode"]
    got = chunk_attention(q, k, v, use_pallas=True, **ops)
    chunk1 = lowering.paged_chunk_counts()
    decode1 = lowering.snapshot()["paged_decode"]
    took = {p: chunk1[p] - chunk0[p] for p in lowering.PATHS}
    want = chunk_attention(q, k, v, **ops)
    assert got.shape == want.shape == q.shape and got.dtype == q.dtype
    # rows that have something valid, at their valid queries: the rest
    # is a bucket's padding, which nobody reads
    valid = np.asarray(_valid(case.T))
    read = np.arange(case.T)[None] < valid[:, None]
    assert np.isfinite(np.asarray(got, np.float32)).all()
    if not case.kernel:
        # the gather, as it was, counted as it was and under the new name
        assert took == dict(lowered=0, interpreted=0, reference=1)
        assert decode1["reference"] == decode0["reference"] + 1
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    assert took == dict(lowered=0, interpreted=1, reference=0)
    assert decode1 == decode0
    tol = 2e-5 if case.dtype == "float32" else 0.03
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[read], np.asarray(want, np.float32)[read],
        rtol=tol, atol=tol,
    )


def test_the_tile_rule_at_the_classify_cells_shapes():
    """qwen3-4b's heads (32 over 8 of 128) on pages of 64: a verify
    forward's 17 tokens are ONE tile of 32 (128 query rows a KV head), a
    prefill chunk's 256 four tiles of 64; both take 4 pages a key step;
    and nothing is accepted that ``VMEM_LIMIT_BYTES`` does not hold."""
    sizes = dict(pool_bytes=2, io_bytes=2)
    assert pallas_chunk.chunk_tiles(17, 32, 8, 128, 64, **sizes) == (32, 32, 32, 4)
    assert pallas_chunk.chunk_tiles(256, 32, 8, 128, 64, **sizes) == (64, 256, 256, 4)
    assert pallas_chunk.chunk_tiles(512, 32, 8, 128, 64, **sizes) == (64, 512, 256, 4)
    # 64 heads with K/V of their own: a page of 1 MB, refused
    assert pallas_chunk.chunk_tiles(512, 64, 64, 128, 64, **sizes) is None
    for T, NH, KVH, Dh, PS_ in ((17, 32, 8, 128, 64), (2048, 32, 8, 128, 64),
                                (300, 16, 16, 256, 128), (2, 8, 1, 128, 16)):
        tiles = pallas_chunk.chunk_tiles(T, NH, KVH, Dh, PS_, **sizes)
        assert tiles is not None, (T, NH, KVH, Dh, PS_)
        TQ, Tp, BK, pages = tiles
        assert Tp >= T and Tp % TQ == 0 and Tp % BK == 0 and TQ % 16 == 0
        assert pallas_chunk.chunk_vmem_bytes(
            TQ, Tp, BK, pages, NH, KVH, Dh, PS_, **sizes
        ) <= pallas_chunk.VMEM_LIMIT_BYTES


SCHEMA = {
    "type": "object",
    "properties": {
        "classification_result": {
            "type": "string", "enum": ["positive", "negative"],
        },
    },
    "required": ["classification_result"],
}


def test_a_constrained_jobs_tokens_are_the_gathered_paths(
    byte_tok, monkeypatch, interpreted_chunk
):
    """A schema job on the CPU, kernels interpreted, heads of 128: its
    forced runs go through verify forwards (``[B, C]`` over the paged
    past). With the chunk kernel on, the rows' tokens are what the
    gather gives them."""
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.constrain import schema_constraint_factory
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
    from sutro_tpu.models.configs import MODEL_CONFIGS
    from tests.test_prefix_split import _force_interpret

    _force_interpret(monkeypatch)
    mcfg = dataclasses.replace(
        MODEL_CONFIGS["tiny-dense"], name="tiny-dense-h128", head_dim=128
    )

    def run():
        ecfg = EngineConfig(
            kv_page_size=8, max_pages_per_seq=24, max_model_len=192,
            decode_batch_size=4, use_pallas=True, param_dtype="float32",
            activation_dtype="float32", decode_multi_step=8,
            constrain_fastforward=16,
        )
        factory = schema_constraint_factory(SCHEMA, byte_tok)
        b = ContinuousBatcher(
            ModelRunner(mcfg, ecfg), stop_ids=byte_tok.stop_ids()
        )
        res = {}
        assert b.run(
            [
                GenRequest(
                    row_id=i, prompt_ids=np.array(byte_tok.encode(t), np.int32),
                    max_new_tokens=60, temperature=0.0, constraint=factory(),
                )
                for i, t in enumerate(["first row", "second", "third one"])
            ],
            on_result=lambda r: res.__setitem__(r.row_id, r),
        ) == "completed"
        assert b.ff_forced > 0, "the schema's scaffold never fast-forwarded"
        return {i: (tuple(r.token_ids), r.finish_reason) for i, r in res.items()}

    before = lowering.paged_chunk_counts()
    on = run()
    mid = lowering.paged_chunk_counts()
    assert mid["interpreted"] > before["interpreted"]
    assert mid["reference"] == before["reference"]
    monkeypatch.setattr(pallas_chunk, "paged_chunk_supported", lambda *a, **k: False)
    jax.clear_caches()      # the runner's programs are traced anew
    off = run()
    after = lowering.paged_chunk_counts()
    assert after["reference"] > mid["reference"]
    assert after["interpreted"] == mid["interpreted"]
    assert on == off
