"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import subprocess
import sys

from chipbench import spec


def test_run_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "resnet8-facade-32", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout
    assert p.stdout.strip() == ""
