"""Which implementation each Pallas dispatch site took, counted while
tracing.

``use_pallas=True`` does not by itself mean a kernel ran: the shape
gates in ops/attention.py send unsupported calls to the jnp reference,
and tests run the kernels in interpret mode. A run on the chip must be
able to say which of the three happened, so every kernel wrapper and
every gate that falls through records it here:

- ``lowered``: the kernel body was traced with ``interpret=False`` — it
  goes to Mosaic when the program compiles;
- ``interpreted``: traced with ``interpret=True`` (CPU tests);
- ``reference``: ``use_pallas=True`` was asked for and the jnp / XLA
  path ran instead (by a shape gate: a chunk of T>1 tokens over a paged
  past that ops/pallas_chunk.py's gate refuses gathers the pages, and
  is counted under ``paged_decode`` here and by ``paged_chunk_counts``).

Counts are per TRACE, not per execution — jit caches traces, so a count
says "this path was built into a program at least that many times",
which is what a bring-up check needs (chip_smoke.py, `sutro engine
info`). Process-wide like the jit caches it mirrors.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import jax

KERNELS = ("paged_decode", "flash_prefill", "kv_write")
PATHS = ("lowered", "interpreted", "reference")

#: counted like a kernel of ``KERNELS`` but read by its own accessor
#: (``grouped_matmul_counts``): a routed model's alone
GROUPED = "grouped_matmul"
#: likewise (``ssm_state_read_counts``): a Mamba-2 model's alone
SSM_STATE_READ = "ssm_state_read"

#: likewise (``kda_state_read_counts`` / ``kda_state_commit_counts``): a
#: delta-rule model's alone. The read is ``ssm_state_read``'s body with a
#: head a group, counted under its own name
KDA_STATE_READ = "kda_state_read"
KDA_STATE_COMMIT = "kda_state_commit"
#: likewise (``paged_chunk_counts``): a chunk of several tokens over a
#: paged past (ops/pallas_chunk.py), which a job that is one prefill and
#: single decode steps never traces
PAGED_CHUNK = "paged_chunk"

#: the PARTS of a step program: the outermost ``jax.named_scope`` of
#: every op a step issues, so that a profiler trace's optimized HLO says
#: which part of the model each device op belongs to (OBSERVABILITY.md
#: "Parts of a step"; ``perfbench/trace_parts.py`` reads them back and
#: keeps its own copy of this tuple, which a test holds equal). The
#: scopes of a kind (``attn_mixer``, ``moe_ffn``, ``kda_commit``, ...)
#: nest inside their part. What a step loop does outside every part
#: (token buffers, counters, a scan's carry) stays unnamed and is
#: measured as such (``device_unnamed_share``).
PARTS = ("embed", "mixer", "ffn", "cache", "head", "sample")


@contextlib.contextmanager
def part(name: str):
    """The scope of one of ``PARTS``: a context manager, or a decorator
    of a function whose whole body is that part (a new scope a call: a
    ``jax.named_scope`` object used as a decorator keeps ONE saved name
    stack, which a function that calls itself, ``kvcache.write_kv``,
    would overwrite and leak). Metadata only: the compiled program is
    the same instructions. A scope is debug info, which the persistent
    compile cache's key leaves out: a program cached before a scope
    moved keeps its old names until the cache is cleared
    (OBSERVABILITY.md "Parts of a step")."""
    assert name in PARTS, name
    with jax.named_scope(name):
        yield


_lock = threading.Lock()
_counts: Dict[str, Dict[str, int]] = {
    k: dict.fromkeys(PATHS, 0)
    for k in KERNELS + (
        GROUPED, SSM_STATE_READ, KDA_STATE_READ, KDA_STATE_COMMIT,
        PAGED_CHUNK,
    )
}
_xla_decode = 0
#: traces of a routed layer's combine (ops/moe.combine), by path
MOE_COMBINE = ("unpermuted", "scattered")
_combine: Dict[str, int] = dict.fromkeys(MOE_COMBINE, 0)
#: traces of a latent layer's attention (ops/attention.latent_attention),
#: by form
LATENT_FORMS = ("expanded", "absorbed")
_latent: Dict[str, int] = dict.fromkeys(LATENT_FORMS, 0)
#: traces of a latent layer's attention under an indexer's selection
#: (ops/sparse_attention.py), by how the selection is applied
SPARSE_FORMS = ("gathered", "masked")
_sparse: Dict[str, int] = dict.fromkeys(SPARSE_FORMS, 0)
#: traces of a delta-rule layer (models/transformer.kda_mixer), by form
KDA_FORMS = ("chunked", "pending")
_kda: Dict[str, int] = dict.fromkeys(KDA_FORMS, 0)

MAMBA1_FORMS = ("chunked", "pending", "window")
_mamba1: Dict[str, int] = dict.fromkeys(MAMBA1_FORMS, 0)


#: traces of the paged decode kernel by the rows a grid step takes
_decode_rows: Dict[int, int] = {}


#: traces of a kernel's calls by heads and FORM, under ``"<kernel>@<heads>
#: <key>=<value> ..."``: the flash body's GQA calls (their tile
#: schedule) and the paged decode kernel's calls under a selection
_forms: Dict[str, int] = {}


#: traces of the attention kernels by the QUERY HEADS of the call, for a
#: model whose layer kinds differ in them (``ModelConfig.heads_of``):
#: ``{"paged_decode@72": {"lowered": 1, ...}}``
_by_heads: Dict[str, Dict[str, object]] = {}


def _count_heads(kernel: str, heads: int, path: str, gate: str = "") -> None:
    """Under ``_lock``."""
    if not heads:
        return
    entry = _by_heads.setdefault(
        f"{kernel}@{heads}", dict.fromkeys(PATHS, 0)
    )
    entry[path] += 1
    if gate:
        entry["gate"] = gate


def record_kernel(
    kernel: str, *, interpret: bool, rows: int = 0, heads: int = 0,
    form: Optional[Dict[str, str]] = None,
) -> None:
    """Called from a kernel wrapper's traced body. ``rows``: the rows a
    grid step of the paged decode kernel takes in this trace; ``heads``:
    the call's query heads (an attention kernel's); ``form``: the tile
    schedule a GQA call of the flash body took (``flash_prefill_counts``),
    ``{"select": "keep"}`` of a paged decode call under a row's selection
    (``paged_decode_forms``)."""
    path = "interpreted" if interpret else "lowered"
    with _lock:
        _counts[kernel][path] += 1
        if rows:
            _decode_rows[rows] = _decode_rows.get(rows, 0) + 1
        _count_heads(kernel, heads, path)
        if form:
            key = f"{kernel}@{heads} " + " ".join(
                f"{k}={v}" for k, v in form.items()
            )
            _forms[key] = _forms.get(key, 0) + 1


def _forms_of(kernel: str) -> Dict[str, int]:
    with _lock:
        return dict(sorted(
            (k, n) for k, n in _forms.items() if k.startswith(kernel + "@")
        ))


def flash_prefill_counts() -> Dict[str, int]:
    """Traces of the flash body's GQA calls (lowered or interpreted) by
    the call's query heads and the FORM its tile schedule took
    (``ops/pallas_flash.flash_prefill``): ``"flash_prefill@72
    tile=512x512 walk=window operands=bfloat16": 1``. ``tile``: the
    query and key blocks' sides (``gqa_tiles``); ``walk``: ``window``
    (a layer KIND's static window: the key axis of the grid is the
    window's blocks), ``causal`` (no window: the tiles under the
    diagonal) or ``dynamic`` (the window a runtime scalar of a
    homogeneous scan's layer: the causal half, a tile outside the window
    skipped a step at a time); ``operands``: the dtype the MXU is fed.
    A latent caller's calls (``latent_flash``: its own square blocks)
    are not among them. A count of its own, outside ``snapshot()``'s
    keys, for the reason ``grouped_matmul_counts`` has one."""
    return _forms_of("flash_prefill")


def paged_decode_forms() -> Dict[str, int]:
    """Traces of the paged decode kernel (lowered or interpreted) under
    a row's SELECTION, by the call's query heads: ``"paged_decode@64
    select=keep": 1`` (``ops/sparse_attention.selected_decode`` under
    ``use_pallas``: the latent variant with the indexer's selection as
    one more operand). The dense calls of the same program (the branch
    a dispatch at or under ``index_topk`` takes) are in ``snapshot()``
    and ``kernel_heads_counts()`` with it and NOT here: a selecting
    program reads ``select=keep`` at least once, and a selecting step
    whose shape the kernel's gate refused reads ``reference`` with the
    gate in ``kernel_heads_counts()``."""
    return _forms_of("paged_decode")


def paged_decode_rows_per_step() -> Dict[int, int]:
    """Traces of the paged decode kernel (lowered or interpreted), by
    the rows a grid step takes (``ops/pallas_paged.rows_per_step``: 8,
    4, 2 or 1 by the call's batch and widths): ``{8: 3}`` says three
    programs were built whose kernel takes eight rows a step. A chip
    run reads here which schedule its programs got."""
    with _lock:
        return dict(sorted(_decode_rows.items()))


def record_reference(kernel: str, heads: int = 0, gate: str = "") -> None:
    """Called where a ``use_pallas=True`` call takes the jnp/XLA path.
    ``heads``: the call's query heads; ``gate``: what sent it there."""
    with _lock:
        _counts[kernel]["reference"] += 1
        _count_heads(kernel, heads, "reference", gate)


def kernel_heads_counts() -> Dict[str, Dict[str, object]]:
    """Traces of the attention kernels (``paged_decode``,
    ``flash_prefill``, ``paged_chunk``) by the call's QUERY HEADS, under
    ``"<kernel>@<heads>"``: ``lowered`` / ``interpreted`` (the Pallas
    body) and ``reference`` (a ``use_pallas=True`` call that went to
    XLA, with ``gate`` naming what sent the last such call there). A
    model whose layer kinds differ in their heads (72 in its window
    layers, 48 in its full ones) reads here that BOTH counts took the
    kernel; ``snapshot()`` adds them up. The K/V write has no query
    heads (both kinds keep the same KV heads) and is counted a pool in
    ``snapshot()["kv_write"]`` alone. A count of its own, outside
    ``snapshot()``'s keys, for the reason ``grouped_matmul_counts`` has
    one."""
    with _lock:
        return {k: dict(v) for k, v in sorted(_by_heads.items())}


def record_xla_decode() -> None:
    """Called from ``ops/attention.paged_decode_xla``'s traced body."""
    global _xla_decode
    with _lock:
        _xla_decode += 1


def record_latent(form: str) -> None:
    """Called from ``ops/attention.latent_attention``'s traced body."""
    with _lock:
        _latent[form] += 1


def record_moe_combine(path: str) -> None:
    """Called from ``ops/moe.combine``'s traced body."""
    with _lock:
        _combine[path] += 1


def moe_combine_counts() -> Dict[str, int]:
    """Traces of a routed layer's combine on the ragged path (the
    experts' sorted rows summed back a token), by path: ``unpermuted``
    (``ops/moe.combine``: the rows gathered back by the inverse of the
    layer's one sort and reduced over ``top_k`` in float32; a capped
    share traces it twice, once a ``lax.cond`` branch) and ``scattered``
    (a row scatter-add: no call is left that takes it since the
    measurement of PR 44, PERF.md section 6, so 0 says that none came
    back). A count of its own for the reason ``grouped_matmul_counts``
    has one."""
    with _lock:
        return dict(_combine)


def record_sparse(form: str) -> None:
    """Called from ops/sparse_attention.py's traced bodies."""
    with _lock:
        _sparse[form] += 1


def sparse_attention_counts() -> Dict[str, int]:
    """Traces of learned sparse attention, by form: ``gathered`` (one
    decode step: one query over its selected rows, fetched by position
    in plain XLA or, under ``use_pallas``, attended where they lie by
    the paged kernel's latent variant under the selection:
    ``snapshot()["paged_decode"]`` says which, ``lowered`` or
    ``reference``, and ``paged_decode_forms`` tells the selecting call
    from the dense branch's) and ``masked`` (a chunk of queries: the dense products under
    the selection's mask, in XLA or, for a prefill under ``use_pallas``,
    in the flash kernel: ``snapshot()["flash_prefill"]`` says which,
    ``lowered`` or ``reference``). A dispatch whose rows are all at or under
    ``index_topk`` takes the dense latent paths and counts there
    (``latent_counts``, ``snapshot``)."""
    with _lock:
        return dict(_sparse)


def latent_counts() -> Dict[str, int]:
    """Traces of a latent layer's attention, by form: ``expanded`` (a
    chunk with no past: K and V a head from the chunk's own rows) and
    ``absorbed`` (over the latent pages: the up-projection folded into
    the query and the output), whichever path computed them: under
    ``use_pallas`` ``snapshot()`` says beside it whether a call took a
    kernel (``paged_decode`` / ``flash_prefill`` lowered) or the XLA
    form (``reference``). A count of its own for the reason
    ``grouped_matmul_counts`` has one."""
    with _lock:
        return dict(_latent)


def xla_decode_count() -> int:
    """Traces of the XLA paged-decode path (``paged_decode_xla``): the
    decode attention of every call the Pallas kernel does not take. A
    count of its own, not a path of ``KERNELS``: it is no kernel, and
    it runs by design wherever ``use_pallas`` is off."""
    with _lock:
        return _xla_decode


def grouped_matmul_counts() -> Dict[str, int]:
    """Traces of the routed experts' grouped product, by path:
    ``ops/pallas_gmm.grouped_matmul``'s body (``lowered`` /
    ``interpreted``) and ``ops/moe._grouped`` where a ``use_pallas=True``
    call stays on ``jax.lax.ragged_dot`` (``reference``). A count of its
    own, not a name in ``KERNELS`` or a key of ``snapshot()``: a dense
    model runs no routed layer, and a bring-up check that holds every
    key of ``snapshot()`` to ``lowered > 0`` (chip_smoke.py, the
    benchmark's numbers check) must keep passing there."""
    with _lock:
        return dict(_counts[GROUPED])


def ssm_state_read_counts() -> Dict[str, int]:
    """Traces of a Mamba-2 layer's read of its committed state in a
    chunk that does not advance it (``models/transformer.ssd_pending``:
    a decode step, a window's step, a verify chunk), by path:
    ``ops/pallas_ssm.ssm_state_read``'s body (``lowered`` /
    ``interpreted``) and a ``use_pallas=True`` call that stays on the
    XLA expression (``reference``: a mesh, a pool or a chunk off the
    kernel's static gate). A count of its own for the reason
    ``grouped_matmul_counts`` has one: a model without a Mamba layer
    never reads such a state."""
    with _lock:
        return dict(_counts[SSM_STATE_READ])


def record_kda(form: str) -> None:
    """Called from ``models/transformer.kda_mixer``'s traced body."""
    with _lock:
        _kda[form] += 1


def kda_counts() -> Dict[str, int]:
    """Traces of a delta-rule (kda) layer, by form: ``chunked`` (a
    prefill: the chunk form from the slot's state to its end) and
    ``pending`` (a decode step, a verify chunk, a fused window's step:
    the committed state read in place and not advanced)."""
    with _lock:
        return dict(_kda)


def record_mamba1(form: str) -> None:
    """Called from ``models/transformer.mamba1_mixer``'s traced body."""
    with _lock:
        _mamba1[form] += 1


def mamba1_counts() -> Dict[str, int]:
    """Traces of a Mamba-1 layer, by form, all three plain XLA:
    ``chunked`` (a prefill: an associative scan inside a chunk of
    tokens, the state handed from chunk to chunk), ``pending`` (a single
    decode step, a verify chunk: the row's slot gathered and stepped a
    token at a time, nothing written) and ``window`` (a fused window's
    step: the state the scan carries, stepped once)."""
    with _lock:
        return dict(_mamba1)


def kda_state_read_counts() -> Dict[str, int]:
    """Traces of a delta-rule layer's two products against its committed
    state in a chunk that does not advance it
    (``models/transformer.kda_state_read``), by path:
    ``ops/pallas_ssm.ssm_state_read``'s body with a head a group
    (``lowered`` / ``interpreted``) and a ``use_pallas=True`` call that
    stays on the XLA expression (``reference``). A count of its own for
    the reason ``grouped_matmul_counts`` has one."""
    with _lock:
        return dict(_counts[KDA_STATE_READ])


def kda_state_commit_counts() -> Dict[str, int]:
    """Traces of a delta-rule layer's commit of a chunk's accepted
    tokens from their ``(g, k, u)`` (``engine/kvcache.write_state``), by
    path: ``ops/pallas_ssm.kda_state_commit``'s body (a row's slot
    streamed in and out once, in place) and a ``use_pallas=True`` call
    that stays on the gather, product and scatter (``reference``)."""
    with _lock:
        return dict(_counts[KDA_STATE_COMMIT])


def paged_chunk_counts() -> Dict[str, int]:
    """Traces of a chunk of several tokens over a PAGED past (a verify
    forward, the suffix of a job's rows over its shared prefix, a chunk
    of a chunked prefill; ``ops/attention.chunk_attention`` with
    ``T > 1`` under the causal mask), by path:
    ``ops/pallas_chunk.paged_chunk_attention``'s body, which reads the
    row's pages where they lie (``lowered`` / ``interpreted``), and a
    ``use_pallas=True`` call its gate refuses, which gathers the row's
    whole table (``reference``; counted under ``paged_decode`` in
    ``snapshot()`` too, as it always was). A count of its own for the
    reason ``grouped_matmul_counts`` has one: a job of whole-prompt
    prefills and single decode steps never builds such a program."""
    with _lock:
        return dict(_counts[PAGED_CHUNK])


def snapshot() -> Dict[str, Dict[str, int]]:
    with _lock:
        return {k: dict(_counts[k]) for k in KERNELS}


def shard_over_model(mesh, fn, operands: dict, specs: dict, out_specs):
    """Call a Pallas wrapper ``fn(**operands)`` once per shard of the
    mesh's ``model`` axis (heads / the fused KV axis), or bare when
    ``mesh`` is None. XLA cannot partition a Mosaic call ("Mosaic
    kernels cannot be automatically partitioned"), and the kernels are
    independent per KV head, so tensor parallelism runs them as a
    shard_map with no collective inside. ``specs`` holds a
    PartitionSpec per possible operand; only those present are used."""
    if mesh is None:
        return fn(**operands)
    return jax.shard_map(
        lambda ops: fn(**ops),
        mesh=mesh,
        in_specs=({k: specs[k] for k in operands},),
        out_specs=out_specs,
        check_vma=False,
    )(operands)
