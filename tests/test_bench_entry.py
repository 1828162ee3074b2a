"""bench.py never reports a CPU run under a device metric's name.

The probe/retry/CPU-fallback supervisor is gone: one process, the
device JAX finds. What is left to pin without a chip is the refusal —
no accelerator and no `JAX_PLATFORMS=cpu` by name means a non-zero exit
and no result line. (A CPU run that was asked for by name is the
BENCH_SMOKE path, `make bench-smoke`.)
"""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_no_accelerator_and_no_cpu_request_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env={**env, "BENCH_SMOKE": "1", "PYTHONPATH": str(REPO)},
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO,
    )
    assert proc.returncode != 0
    assert "vs_baseline" not in proc.stdout and "{" not in proc.stdout
    assert "no accelerator" in proc.stderr
