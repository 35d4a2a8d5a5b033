"""The entry points name what exists: a script that is deleted takes
its make/CI line with it, and CI calls no target the Makefile lacks."""
import glob
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
ENTRY_POINTS = ["Makefile", ".github/ci.sh", ".github/run_tests_chunked.sh"]


def _commands(rel: str) -> str:
    """The file without its comment lines."""
    lines = (REPO / rel).read_text().splitlines()
    return "\n".join(x for x in lines if not x.lstrip().startswith("#"))


@pytest.mark.parametrize("rel", ENTRY_POINTS)
def test_every_script_an_entry_point_runs_exists(rel):
    paths = re.findall(r"[\w./*-]+\.py\b", _commands(rel))
    # ci.sh itself runs make targets and the chunked runner, no script
    assert paths or rel == ".github/ci.sh", f"{rel}: the pattern went stale"
    missing = [p for p in paths if not glob.glob(str(REPO / p))]
    assert not missing, f"{rel} runs files that are not in the tree: {missing}"


def test_every_make_target_ci_calls_is_in_the_makefile():
    called = re.findall(r"^\s*make\s+([\w-]+)", _commands(".github/ci.sh"), re.M)
    targets = set(re.findall(r"^([\w-]+):", _commands("Makefile"), re.M))
    assert called, "ci.sh calls no make target: the pattern went stale"
    assert not set(called) - targets, sorted(set(called) - targets)
