"""Test bootstrap: force CPU with 8 virtual devices so TP/DP/EP sharding
logic runs multi-device in CI without TPUs (SURVEY §4 'lesson for the
build'). Must run before jax is imported anywhere."""

import functools
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# tests must neither populate a persistent cache under the real
# ~/.sutro nor latch the process-global cache dir to a pytest tmp
# SUTRO_HOME that gets deleted at teardown (engine/config.py
# enable_compile_cache; its own tests monkeypatch this off)
os.environ.setdefault("SUTRO_COMPILE_CACHE", "0")
# ... but the suite still wants compiled-program sharing: every
# ModelRunner builds fresh jit closures, so the scheduler+pallas
# region recompiles identical tiny-model programs dozens of times.
# A session-private cache dir is safe where enable_compile_cache's
# CPU opt-out is not — the SIGILL hazard there is CROSS-process
# (host-feature detection can differ between processes); here the
# one pytest process that wrote an entry is the only reader.
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_xla_cache_dir = tempfile.mkdtemp(prefix="sutro-test-xla-cache-")
atexit.register(shutil.rmtree, _xla_cache_dir, ignore_errors=True)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# threshold 0 so the sub-second tiny-model compiles actually persist
# (the 2.0 s production floor in enable_compile_cache would keep the
# cache empty for every program this suite builds)
jax.config.update("jax_compilation_cache_dir", _xla_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from sutro_tpu.engine.config import EngineConfig  # noqa: E402
from sutro_tpu.engine.tokenizer import ByteTokenizer  # noqa: E402
from sutro_tpu.models.configs import MODEL_CONFIGS  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.devices()[:8]


@pytest.fixture(scope="session")
def mesh_ecfg():
    """Tiny engine config for multi-device sharding tests."""
    return EngineConfig(
        kv_page_size=8, max_pages_per_seq=8, decode_batch_size=4,
        max_model_len=64, use_pallas=False, param_dtype="float32",
    )


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


@pytest.fixture
def interpreted(monkeypatch):
    """The runner's ``use_pallas`` path on the CPU: the same calls,
    interpreted."""
    from sutro_tpu.ops import pallas_flash, pallas_gmm, pallas_kv, pallas_paged

    for mod, name in (
        (pallas_paged, "paged_decode_attention"),
        (pallas_flash, "flash_prefill"),
        (pallas_kv, "row_write_pallas"),
        (pallas_gmm, "grouped_matmul"),     # the routed experts' product
    ):
        monkeypatch.setattr(
            mod, name, functools.partial(getattr(mod, name), interpret=True)
        )


@pytest.fixture
def paged_ring(monkeypatch):
    """``paged_ring(ring_pages, group_pages)`` gives the paged decode
    kernel a fetch ring of that size for one test, so that tiny pages
    wrap the ring and split rows into several groups as real ones do
    (``ops/pallas_paged.ring_shape`` would give them 32 slots). The
    size is read while the kernel is traced: the traces go before and
    after."""
    from sutro_tpu.ops import pallas_paged

    def set_ring(ring_pages: int, group_pages: int) -> None:
        monkeypatch.setattr(
            pallas_paged, "ring_shape",
            lambda *a, **k: (ring_pages, group_pages),
        )
        pallas_paged.paged_decode_attention.clear_cache()

    yield set_ring
    pallas_paged.paged_decode_attention.clear_cache()


@pytest.fixture(scope="session")
def tiny_ecfg() -> EngineConfig:
    return EngineConfig(
        kv_page_size=8,
        max_pages_per_seq=16,
        decode_batch_size=4,
        max_model_len=128,
        use_pallas=False,
        param_dtype="float32",
        activation_dtype="float32",
    )


@pytest.fixture(scope="session")
def byte_tok() -> ByteTokenizer:
    return ByteTokenizer(vocab_size=MODEL_CONFIGS["tiny-dense"].vocab_size)


@pytest.fixture(scope="session")
def tiny_runner(tiny_ecfg):
    from sutro_tpu.engine.runner import ModelRunner

    return ModelRunner(MODEL_CONFIGS["tiny-dense"], tiny_ecfg)


@pytest.fixture(scope="session")
def live_engine(tmp_path_factory):
    """ONE compiled tiny engine + HTTP daemon shared by test_sdk.py and
    test_serving.py (tier-1 wall time: two engine builds -> one). The
    geometry is the union of what both suites need: interactive tier on,
    batch defaults matching the old sdk fixture. Tests that mutate
    engine state must restore it (they do — see test_serving.py's
    drain/disable tests)."""
    mp = pytest.MonkeyPatch()
    home = tmp_path_factory.mktemp("shared-live-home")
    mp.setenv("SUTRO_HOME", str(home))
    from sutro_tpu.engine.api import LocalEngine
    from sutro_tpu.server import start_server_thread

    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=16, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", max_new_tokens=16,
        interactive_slots=2,
    )
    engine = LocalEngine(ecfg)
    server, thread, url = start_server_thread(engine)
    yield engine, url, str(home)
    from sutro_tpu.engine import faults

    faults.clear()
    server.shutdown()
    engine.close(timeout=10)
    mp.undo()


def make_requests(tok, texts, **kw):
    from sutro_tpu.engine.scheduler import GenRequest

    return [
        GenRequest(
            row_id=i, prompt_ids=np.array(tok.encode(t), np.int32), **kw
        )
        for i, t in enumerate(texts)
    ]


def free_low_port() -> int:
    """A port OUTSIDE the kernel's ephemeral range (32768+ on this
    host): bind-port-0 hands back an ephemeral port that any outgoing
    TCP connection on the box (background probes, other tests) can be
    assigned as its SOURCE port between our close() and the engine's
    bind — an observed EADDRINUSE flake once the suite ran with no
    retries. Low-range ports are never auto-assigned to clients, so
    the only residual race is another caller, made unlikely by
    randomization."""
    import random
    import socket

    for _ in range(64):
        cand = random.randrange(20000, 31000)
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", cand))
            except OSError:
                continue
            return cand
    raise RuntimeError("no free low-range port found")


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs_between_modules():
    """Release each test module's compiled programs when it ends.

    One process compiling the whole suite segfaults inside XLA:CPU's
    ``backend_compile_and_load`` — measured at this commit's parent and
    at this commit alike: a single-process tier-1 run died at
    test_pallas_kernels.py::test_flash_prefill_matches_reference (~56%,
    427 tests in), a test that passes alone and in any small subset.
    With the jit caches cleared per module the same run completes (777
    tests). The suite's persistent compile cache (above) keeps the
    session-scoped fixtures' recompiles cheap."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()
