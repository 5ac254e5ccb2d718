"""Helpers of the benchmark's own tests (run with
`python -m pytest perf/tests -q`; they are not part of tier-1)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(argv, code=None, timeout=300, root=ROOT):
    """Run perf/run.py of the checkout at `root` (or `code`, a -c script
    that ends in runner.main) in a fresh process.  Returns (returncode,
    last stdout line parsed as JSON or None, stderr)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable] + (["-c", code] if code else
                              [os.path.join(root, "perf", "run.py")])
    proc = subprocess.run(cmd + list(argv), cwd=root, env=env, text=True,
                          capture_output=True, timeout=timeout)
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    return proc.returncode, last, proc.stderr
