"""The harness end to end on the CPU at reduced sizes: the run's check
passes on the program as it is, and the command itself refuses to print
a result without a chip."""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from bench import run
from bench.tests.cells import ROOT_DIR, patch_program, small_cell


@pytest.mark.parametrize("name,loop,farm_knobs", [
    ("qwen3-gen-batch", "closed", {"max_batch": 1, "max_inflight": 1}),
    ("minicpm2b-gen-long", "closed", {"max_batch": 1, "max_inflight": 1}),
    ("qwen3-chat-open", "open", {"max_batch": 4}),
])
def test_run_is_correct_at_reduced_size(monkeypatch, name, loop, farm_knobs):
    cell = small_cell(name, loop=loop, farm=farm_knobs)
    patch_program(monkeypatch, cell)
    res = run.run_cell(cell, 2**31 + 7, 1.0, False, jax.devices()[:1], 0.0)
    assert res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] == pytest.approx(0, abs=1e-4)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s"}


def test_command_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-gen-batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT_DIR, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_farm_across_four_host_devices():
    """The 4-chip cell on 4 CPU devices (run in a child so that the
    device count can be set): 8 services pulling from one repository,
    every one of them serving checked answers."""
    code = (
        "import jax\n"
        "from bench import run\n"
        "from bench.tests.cells import patch_program, small_cell\n"
        "import pytest\n"
        "mp = pytest.MonkeyPatch()\n"
        "cell = small_cell('qwen3-farm-4chip')\n"
        "assert cell.chips == 4\n"
        "patch_program(mp, cell)\n"
        "res = run.run_cell(cell, 2**31 + 3, 1.0, False, jax.devices()[:4],"
        " 0.0)\n"
        "assert res['correct'], res['check']\n"
        "assert res['check']['idle_services']['value'] == 0\n"
        "print('OK', res['check']['idle_services'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT_DIR, os.path.join(
                   ROOT_DIR, "src")]))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT_DIR, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "OK" in p.stdout
