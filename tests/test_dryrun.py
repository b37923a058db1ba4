"""Dry-run harness smoke: one real cell through the full path (512 forced
host devices, production mesh, lower+compile+analyze) in a subprocess so
the main test process keeps its 1-device view."""
import json
import os
import subprocess
import sys

import pytest

from suite_support import LIMIT_S


def _run(args):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # dryrun sets its own, first thing
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-m", "repro.launch.dryrun"] + args,
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       timeout=LIMIT_S)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


@pytest.mark.slow
def test_dryrun_decode_cell_single_pod():
    out = _run(["--arch", "whisper-base", "--shape", "decode_32k",
                "--mesh", "single"])
    rec = json.loads([l for l in out.splitlines() if l.startswith("{")][0])
    assert rec["status"] == "ok"
    assert rec["devices"] == 256
    ro = rec["roofline"]
    assert ro["t_memory_s"] > 0 and ro["bottleneck"] in (
        "compute", "memory", "collective")
    assert rec["cost"]["flops_per_dev"] > 0
    assert rec["memory"]["argument_bytes"] > 0


@pytest.mark.slow
def test_dryrun_skip_policy():
    out = _run(["--arch", "deepseek-coder-33b", "--shape", "long_500k",
                "--mesh", "single"])
    rec = json.loads([l for l in out.splitlines() if l.startswith("{")][0])
    assert rec["status"] == "skipped"
    assert "sub-quadratic" in rec["reason"]
