"""The command refuses to run without a TPU: non-zero exit, no result."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("workload", ["qwen2-0.5b.batch-decode",
                                      "qwen3-1.7b.long-prompt"])
def test_exits_nonzero_without_tpu(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        workload, "--seed", str(2**31 + 3), "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
