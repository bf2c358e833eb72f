"""The entry points refuse to measure without a TPU: exit code 2, no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
SEED = str(2 ** 33)
COMMANDS = {
    "run": ["bench/run.py", "--workload", CELL["name"], "--seed", SEED,
            "--seconds", "1", "--trace", "0"],
    "control": ["bench/control.py", "--workload", CELL["name"], "--seconds", "1",
                "--seeds", SEED],
}


@pytest.mark.parametrize("name", COMMANDS)
def test_exits_nonzero_without_tpu(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, *COMMANDS[name]], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
