"""Two-process multi-host smoke: the mesh layer's DCN story, executed.

parallel/mesh.py claims the same mesh spans all hosts after
``init_distributed()`` and that the outermost ``data`` axis is the one
that crosses hosts (SURVEY §5.8). This test runs it for real: two OS
processes, each with 4 virtual CPU devices, form one 8-device dp=2/tp=4
mesh and run collectives whose ``data``-axis hop crosses the process
boundary (tests/multihost_child.py). Everything the engine needs from
multi-host — distributed init, global array construction, cross-host
psum — executes, not just compiles.
"""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


from tests.conftest import free_low_port as _free_port


def test_two_process_mesh_collectives():
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(pid)
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get(
            "PYTHONPATH", ""
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(REPO / "tests" / "multihost_child.py")],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
        )
    # collect BOTH before asserting: an early assert would leak the
    # sibling blocked in jax.distributed.initialize
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"MULTIHOST_OK process={pid}" in out, out
