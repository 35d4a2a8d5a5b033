"""The system under test, behind the one door the benchmark uses.

Everything perfbench takes from the program is taken here: the engine
(``get_engine``), a batch job's submit / progress stream / results, an
online chat through the gateway, the telemetry registry, the flight
recorder, the trace store, the kernel-lowering counts, and the runner's
own weights and jitted model for the logits check. Generators, readers
and ``run.py`` import nothing of ``sutro_tpu`` themselves, so a change of
the program's interfaces is repaired in this one file.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

REPO = Path(__file__).resolve().parent.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# kernels counted by an accessor of their own, ``lowering.<name>_counts``
FURTHER_KERNELS = ("grouped_matmul", "ssm_state_read", "kda_state_read",
                   "kda_state_commit")


class NoDevice(SystemExit):
    """The run may not start: wrong platform or too few chips."""


class Refused(Exception):
    """A chat the system would not admit (counted in ``failed``)."""


class System:
    def __init__(self, cfg: Dict[str, Any], seed: int, rehearsal: bool):
        self.cfg = cfg
        # hermetic state: jobs and results go under TMPDIR, never ~/.sutro
        self.home = tempfile.mkdtemp(prefix="perfbench-home-")
        os.environ["SUTRO_HOME"] = self.home
        if str(REPO) not in sys.path:
            sys.path.insert(0, str(REPO))
        try:
            import jax
            import sutro_tpu  # noqa: F401  (the program must be here)
        except ImportError as e:
            raise NoDevice(
                f"perfbench: the program is not importable here: {e}"
            )
        self.jax = jax
        devices = jax.devices()
        self.platform = devices[0].platform
        self.device_kind = str(devices[0].device_kind)
        self.device_count = len(devices)
        chips = int(cfg["chips"])
        if rehearsal:
            if self.platform != "cpu":
                raise NoDevice("perfbench: --cpu-rehearsal is for a CPU")
        elif self.platform != "tpu" or self.device_count < chips:
            raise NoDevice(
                f"perfbench: JAX found {self.device_count} x "
                f"{self.platform!r} ({self.device_kind}); this cell needs "
                f"{chips} TPU chip(s). It only runs on the chip."
            )
        self.compiles: List[Tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)

        from sutro_tpu.engine.api import get_engine
        from sutro_tpu.engine.config import EngineConfig

        settings = dict(cfg["engine"])
        settings["seed"] = int(seed) % (2**31 - 1)
        self.ecfg = EngineConfig(**settings)
        self.model = cfg["model"]
        self.engine_key = cfg["engine_key"]
        self.engine = get_engine(self.ecfg)

    # -- facts -------------------------------------------------------------

    def _on_compile(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append(
                (time.monotonic(), str(kw.get("fun_name", "?")), float(secs))
            )

    def runner(self):
        """The engine's resident runner (built by the first job)."""
        return self.engine._runner_cache[self.engine_key][0]

    def n_chips(self) -> int:
        """Chips the runner's mesh spans: THE per-chip divisor."""
        return int(self.runner().n_devices)

    def decode_batch(self) -> int:
        return int(self.ecfg.decode_batch_size)

    def mesh_devices(self) -> list:
        mesh = self.runner().mesh
        return list(mesh.devices.flat) if mesh is not None else [
            self.jax.devices()[0]
        ]

    def memory_peak_bytes(self) -> int:
        peak = 0
        for dev in self.mesh_devices():
            stats = dev.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use") or 0))
        return peak

    def kernel_paths(self) -> Dict[str, Dict[str, int]]:
        """Traces by path (``lowered`` / ``interpreted`` / ``reference``)
        of every kernel the program counts: the names of
        ``lowering.snapshot()`` first, then the counts that live outside
        it because only some models have the layer (a program without
        one of these accessors still runs)."""
        from sutro_tpu.ops import lowering

        paths = lowering.snapshot()
        for name in FURTHER_KERNELS:
            counts = getattr(lowering, name + "_counts", None)
            if counts is not None:
                paths.setdefault(name, dict(counts()))
        return paths

    def uses_kernels(self) -> bool:
        return bool(self.runner().use_pallas)

    def serving_dtype(self) -> str:
        return str(self.ecfg.param_dtype)

    # -- batch jobs --------------------------------------------------------

    def submit_job(
        self, inputs: List[str], *, sampling: Dict[str, Any],
        system_prompt: Optional[str], output_schema: Optional[Dict],
        name: str,
    ) -> str:
        payload: Dict[str, Any] = {
            "model": self.model, "inputs": inputs,
            "sampling_params": dict(sampling), "name": name,
        }
        if system_prompt:
            payload["system_prompt"] = system_prompt
        if output_schema:
            payload["output_schema"] = output_schema
        return self.engine.submit_batch_inference(payload)

    def job_updates(self, job_id: str) -> Iterator[Dict[str, Any]]:
        """The NDJSON progress protocol: ``progress`` and ``tokens``
        updates until the job ends."""
        return self.engine.stream_job_progress(job_id)

    def job_status(self, job_id: str) -> str:
        return self.engine.job_status(job_id)

    def job_token_cap(self, job_id: str, asked: int) -> int:
        """The cap the job ran under: the engine raises ``asked`` to the
        shortest output its schema accepts, so a row may hold more
        tokens than the client asked for."""
        params = self.engine.get_job(job_id).get("sampling_params") or {}
        return max(int(params.get("max_new_tokens") or asked), int(asked))

    def job_failure(self, job_id: str) -> Any:
        return self.engine.get_job(job_id).get("failure_reason")

    def job_rows(self, job_id: str) -> List[Dict[str, Any]]:
        """One dict a result row: output, finish_reason, gen_tokens,
        error (None for a clean row)."""
        df = self.engine.jobs.read_results(job_id)
        rows = []
        for rec in df.to_dict("records"):
            err = rec.get("error")
            if isinstance(err, float) and err != err:
                err = None
            tokens = rec.get("gen_tokens")
            rows.append({
                "row_id": int(rec["row_id"]),
                "output": rec.get("outputs"),
                "finish_reason": rec.get("finish_reason"),
                "gen_tokens": None if tokens is None or tokens != tokens
                else int(tokens),
                "error": err,
            })
        return rows

    def cancel_job(self, job_id: str) -> None:
        self.engine.cancel_job(job_id)

    # -- online chats ------------------------------------------------------

    def chat(self, body: Dict[str, Any], trace_id: str):
        """Admit one chat through the gateway (what the HTTP handler
        does after parsing) and return its event iterator: ``("token",
        id, logp)`` events, then ``("done", result)`` or ``("error",
        msg)``; None on heartbeat gaps. Raises ``Refused``."""
        from sutro_tpu.serving import openai as oai
        from sutro_tpu.serving.gateway import GatewayRejected

        gw = self.engine.gateway
        if gw is None:
            raise Refused("the online tier is off (interactive_slots=0)")
        try:
            sreq = oai.parse_request(body, chat=True)
            ir = gw.submit(sreq, trace_id=trace_id)
        except (oai.BadServingRequest, GatewayRejected) as e:
            raise Refused(str(e)) from e
        return ir.channel

    def gateway_ttft_s(self, trace_id: str) -> Optional[float]:
        """The gateway's own time to first token for a finished chat,
        from the trace store's ``finish`` event; None once the bounded
        store has dropped the trace."""
        from sutro_tpu import telemetry

        doc = telemetry.TRACES.doc(trace_id)
        if not doc:
            return None
        for span in doc.get("spans", []):
            if span["name"] == "finish":
                return (span.get("attrs") or {}).get("ttft_s")
        return None

    # -- counters and spans ------------------------------------------------

    def registry(self) -> Dict[str, Dict[str, Any]]:
        from sutro_tpu import telemetry

        return telemetry.REGISTRY.collect()

    def recorder_spans(self) -> List[Tuple[str, float, float, Dict]]:
        """Flight-recorder spans as (name, start, end, attrs) on the
        ``time.monotonic()`` clock."""
        from sutro_tpu import telemetry

        rec = telemetry.RECORDER
        out = []
        for s in rec.snapshot():
            t0 = rec.epoch_mono + s["t0_s"]
            out.append((s["name"], t0, t0 + s["dur_s"], s.get("attrs") or {}))
        return out

    # -- the system's logits for the numbers check ---------------------------

    def logits_through_cache(
        self, ids, n_prefill: int, n_decode: int
    ):
        """The system's logits at position ``n_prefill - 1`` and at each
        of the next ``n_decode`` positions, every fed token the GIVEN one
        (no sampling): float32 ``[1 + n_decode, V]``; for ``ids`` of
        several sequences ``[S, T]``, ``[S, 1 + n_decode, V]``. A second
        runner shares the engine's weights and mesh and has a small pool
        of its own (``1 + max_pages_per_seq`` pages), so no page the
        engine holds is touched; it lives for this call only, and its
        pool is given back before the call returns.

        Who drives the decode (``reference/README.md`` "The forced
        forward"): where that runner offers ``forced_logits(seq,
        n_prefill, n_decode)`` the program does, a sequence a call, in
        whatever unit its decode steps (a token, a block, a verified
        run), and what it returns is returned. Where it does not, the
        harness does: the runner's own prefill program, then ``n_decode``
        single-token decode steps through the paged cache, every
        sequence through the same jitted step (one compile however
        many). ``self.numbers_source`` says which."""
        import jax
        import numpy as np

        from sutro_tpu.engine.runner import ModelRunner

        base = self.runner()
        MP = self.ecfg.max_pages_per_seq
        r = ModelRunner(
            base.mcfg, self.ecfg, params=base.params, num_pages=1 + MP,
            mesh=base.mesh,
        )
        ids = np.asarray(ids, np.int32)
        forced = getattr(r, "forced_logits", None)
        self.numbers_source = "harness" if forced is None else "forced_logits"
        try:
            if forced is None:
                one = self._harness_decode(r, n_prefill, n_decode)
            else:
                def one(seq):
                    return np.asarray(
                        forced(seq, n_prefill, n_decode), np.float32
                    )
            if ids.ndim == 1:
                return one(ids)
            return np.stack([one(seq) for seq in ids])
        finally:
            # the jitted methods' caches keep the runner alive (it is
            # their static argument): hand its pool back by hand, and
            # drop its hold on the engine's weights
            for leaf in jax.tree_util.tree_leaves(r.cache):
                if not leaf.is_deleted():
                    leaf.delete()
            r.cache = r.params = None

    def _harness_decode(self, r, n_prefill: int, n_decode: int):
        """``one(seq)`` of ``logits_through_cache`` where the harness
        drives the decode: a token a step."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from sutro_tpu.engine.kvcache import write_kv

        MP = self.ecfg.max_pages_per_seq
        table = np.zeros((MP,), np.int32)
        n_pages = -(-(n_prefill + n_decode) // self.ecfg.kv_page_size)
        table[:n_pages] = np.arange(1, n_pages + 1)
        kv_chunk = r._chunk_for_table(table)

        @jax.jit
        def step(params, cache, tok, past_len, page_table):
            logits, _, (k, v) = r._trunk_decode(
                params, cache, tok, past_len[:, None], past_len,
                page_table, kv_chunk=kv_chunk,
            )
            cache = write_kv(
                cache, k, v, page_table, past_len,
                jnp.ones((1,), jnp.int32),
                use_pallas=r.use_pallas, kernel_mesh=r.kernel_mesh,
            )
            return logits[0, 0].astype(jnp.float32), cache

        table_dev = jnp.asarray(table[None], jnp.int32)

        def one(seq):
            # each sequence overwrites the same pages from position 0
            out = [np.asarray(r.prefill(seq[:n_prefill], table), np.float32)]
            cache = r.cache
            for j in range(n_decode):
                logits, cache = step(
                    r.params, cache,
                    jnp.asarray(seq[None, n_prefill + j : n_prefill + j + 1]),
                    jnp.asarray([n_prefill + j], jnp.int32), table_dev,
                )
                out.append(np.asarray(logits))
            return np.stack(out)

        return one

    def weights(self):
        return self.runner().params

    def weight_count(self) -> int:
        """Parameters the runner serves (logical sizes, however sharded)."""
        leaves = self.jax.tree_util.tree_leaves(self.weights())
        return sum(int(x.size) for x in leaves)

    # -- the end -------------------------------------------------------------

    def close(self) -> None:
        import shutil

        gw = self.engine.gateway
        if gw is not None:
            gw.begin_drain()
            gw.cancel_all()
        self.engine.close(timeout=20)
        shutil.rmtree(self.home, ignore_errors=True)


def config_field_names() -> set:
    """Engine settings a configuration file may carry."""
    from sutro_tpu.engine.config import EngineConfig

    return {f.name for f in dataclasses.fields(EngineConfig)}
