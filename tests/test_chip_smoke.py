"""chip_smoke.py's contract off the chip: it refuses to run without an
accelerator or outside a checkout, and its tiny CPU dry run — the run
the on-chip-measurement guide asks for before spending chip time —
passes end to end (slow: it starts a real cluster)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=60, **env):
    full = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    full.update(env)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=full,
        capture_output=True, text=True, timeout=timeout,
    )


def test_full_size_run_refuses_a_pinned_cpu():
    out = _run([], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == "", "no result may be printed without an accelerator"
    assert "needs the TPU" in out.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run([], cwd=str(tmp_path), script=alone, JAX_PLATFORMS="")
    assert out.returncode != 0
    assert out.stdout == ""
    assert "kwok_tpu/ is not beside this script" in out.stderr


@pytest.mark.slow
def test_tiny_dry_run_on_the_cpu(tmp_path):
    out = _run(
        ["--nodes", "10", "--pods-per-node", "20", "--delete-pods", "20",
         "--soa-pods", "4096", "--soa-nodes", "64", "--parity-rows", "2048",
         "--macro-ticks", "3", "--out", str(tmp_path)],
        timeout=600, JAX_PLATFORMS="cpu",
    )
    assert out.returncode == 0, out.stderr[-4000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    # the count is conftest's virtual 8-device platform, inherited
    assert last["ok"] is True and set(last) == {"ok", "device"}
    assert (last["device"]["platform"], last["device"]["kind"]) == ("cpu", "cpu")
    assert "reduced sizes: dry run" in out.stdout
    summary = json.load(open(tmp_path / "summary.json"))
    assert summary["deployed"]["kwok_pid_gone"] is True
    assert summary["deployed"]["kwok_metrics"]["tick_errors"] == 0
    assert summary["soa"]["parity"]["feature_parity_rows"] > 0
