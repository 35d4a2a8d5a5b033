"""sutro_tpu.engine.softdeadline: the clean-unwind deadline.

Each case runs a small subprocess (no jax import — the module is pure
stdlib) and asserts the exit discipline time-boxed scripts and
``sutro serve`` rely on: rc=124 on deadline/TERM with a CLEAN unwind
(atexit-visible), teardown never aborted by the re-signal loop, and
inherited-SIG_IGN dispositions overridden (non-interactive shells
launch children with SIGINT ignored)."""

import signal
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def run_child(body: str, timeout: int = 60, preexec=None):
    code = (
        f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
        "import atexit\n"
        "atexit.register(lambda: print('ATEXIT-RAN', flush=True))\n"
        + body
    )
    return subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout,
        preexec_fn=preexec,
    )


def test_deadline_interrupts_blocking_sleep_cleanly():
    r = run_child(
        "from sutro_tpu.engine.softdeadline import arm\n"
        "arm(1, 30)\n"
        "import time; time.sleep(60)\n"
        "print('NOT REACHED')\n"
    )
    assert r.returncode == 124, (r.returncode, r.stderr)
    assert "NOT REACHED" not in r.stdout
    # clean unwind: atexit hooks ran (a SIGKILL/os._exit path skips them)
    assert "ATEXIT-RAN" in r.stdout, (r.stdout, r.stderr)
    assert "clean unwind to exit 124" in r.stderr


def test_sigterm_takes_clean_path():
    r = run_child(
        "from sutro_tpu.engine.softdeadline import arm\n"
        "arm(300)\n"
        "import os, signal, threading, time\n"
        "threading.Timer(1, lambda: os.kill(os.getpid(),"
        " signal.SIGTERM)).start()\n"
        "time.sleep(60)\n"
    )
    assert r.returncode == 124, (r.returncode, r.stderr)
    assert "ATEXIT-RAN" in r.stdout


def test_normal_exit_unaffected():
    r = run_child(
        "from sutro_tpu.engine.softdeadline import arm\n"
        "arm(300)\n"
        "print('done')\n"
    )
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "done" in r.stdout


def test_inherited_sigint_ignore_is_overridden():
    # non-interactive shells launch async-list children with SIGINT
    # ignored; Python preserves SIG_IGN, which would make the
    # watchdog's interrupt a silent no-op without arm()'s own handler
    def ignore_int():
        signal.signal(signal.SIGINT, signal.SIG_IGN)

    r = run_child(
        "import signal\n"
        "assert signal.getsignal(signal.SIGINT) is signal.SIG_IGN\n"
        "from sutro_tpu.engine.softdeadline import arm\n"
        "arm(1, 30)\n"
        "import time; time.sleep(60)\n"
        "print('NOT REACHED')\n",
        preexec=ignore_int,
    )
    assert r.returncode == 124, (r.returncode, r.stderr)
    assert "ATEXIT-RAN" in r.stdout


def test_slow_finally_teardown_not_aborted():
    # teardown longer than the 15s re-signal cadence must complete:
    # the watchdog stops re-signalling once the interrupt is delivered
    r = run_child(
        "from sutro_tpu.engine.softdeadline import arm\n"
        "arm(1, 40)\n"
        "import time\n"
        "try:\n"
        "    time.sleep(60)\n"
        "finally:\n"
        "    for _ in range(18): time.sleep(1)\n"
        "    print('TEARDOWN-DONE', flush=True)\n"
    )
    assert r.returncode == 124, (r.returncode, r.stderr)
    assert "TEARDOWN-DONE" in r.stdout, (r.stdout, r.stderr)


def test_second_sigint_during_teardown_is_swallowed():
    # _sigint must be idempotent after delivery: a re-signal (or stray
    # ^C) landing INSIDE a finally-block teardown must not raise a
    # second SystemExit and abort the cleanup the clean exit protects
    r = run_child(
        "from sutro_tpu.engine import softdeadline as sd\n"
        "sd.arm(1, 40)\n"
        "import os, signal, time\n"
        "try:\n"
        "    time.sleep(60)\n"
        "finally:\n"
        "    time.sleep(0.2)\n"
        "    os.kill(os.getpid(), signal.SIGINT)  # mid-teardown\n"
        "    time.sleep(0.5)\n"
        "    print('TEARDOWN-DONE', flush=True)\n"
    )
    assert r.returncode == 124, (r.returncode, r.stderr)
    assert "TEARDOWN-DONE" in r.stdout, (r.stdout, r.stderr)
    assert "ATEXIT-RAN" in r.stdout


def test_env_arming_and_bad_grace_fallback():
    r = run_child(
        "import os\n"
        "os.environ['SUTRO_SOFT_DEADLINE_S'] = '1'\n"
        "os.environ['SUTRO_SOFT_GRACE_S'] = 'not-a-number'\n"
        "from sutro_tpu.engine.softdeadline import arm_from_env\n"
        "arm_from_env()\n"
        "import time; time.sleep(60)\n"
    )
    assert r.returncode == 124, (r.returncode, r.stderr)
