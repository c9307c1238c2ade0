"""The examples/ scripts must stay runnable — each is executed as a
subprocess exactly as the README tells users to run them (they
self-configure the virtual 8-device CPU pod)."""

import subprocess
import sys
from pathlib import Path

import pytest

EXAMPLES = sorted((Path(__file__).parent.parent / "examples").glob("*.py"))
# conftest.py placed the suite's persistent compilation cache in the
# environment, so the subprocesses inherit it and repeat runs skip the
# example models' compiles too


@pytest.mark.parametrize("script", EXAMPLES, ids=lambda p: p.name)
def test_example_runs(script):
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, f"{script.name} failed:\n{r.stdout}\n{r.stderr}"
    assert r.stdout.strip(), f"{script.name} printed nothing"


def test_examples_exist():
    assert len(EXAMPLES) >= 3
