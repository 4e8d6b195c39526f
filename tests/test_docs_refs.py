"""The docs cross-reference gate, run as a tier-1 test so dangling
markdown/anchor citations fail locally, not just in CI."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKER = os.path.join(REPO, "tools", "check_docs_refs.py")


def test_no_dangling_docs_references():
    r = subprocess.run(
        [sys.executable, CHECKER], capture_output=True, text=True, cwd=REPO
    )
    assert r.returncode == 0, f"\n{r.stdout}{r.stderr}"


def test_required_experiment_anchors_exist():
    """The anchors the codebase cites must stay present (§Perf,
    §Perf/kernel, §Serve in EXPERIMENTS.md; §Faults in ARCHITECTURE.md)
    — belt and braces on top of the generic scan."""
    with open(os.path.join(REPO, "EXPERIMENTS.md"), encoding="utf-8") as f:
        experiments = f.read()
    for anchor in ("§Perf", "§Perf/kernel", "§Serve"):
        assert any(
            ln.startswith("#") and anchor in ln
            for ln in experiments.splitlines()
        ), f"EXPERIMENTS.md lost its {anchor} heading"
    with open(os.path.join(REPO, "ARCHITECTURE.md"), encoding="utf-8") as f:
        assert any(
            ln.startswith("#") and "§Faults" in ln
            for ln in f.read().splitlines()
        ), "ARCHITECTURE.md lost its §Faults heading"
