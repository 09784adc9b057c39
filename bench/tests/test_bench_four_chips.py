"""The four-chip cell's path, rehearsed on four virtual CPU devices.

Each case runs in a child process, because the device count is fixed when
JAX starts. The child lends the CPU a peak, as the one-chip rehearsal does.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = """
import json, sys
sys.path.insert(0, {root!r})
sys.path.insert(1, {root!r} + "/src")
import jax
from bench import roofline, run
from bench.tests.test_bench_harness import SEED, tiny_cell
from repro.launch import compile_cache

compile_cache.enable_compilation_cache = lambda: None
roofline.PEAKS["cpu"] = {{"hbm_bytes_per_s": 1e11}}
if {drop_exchange}:
    # the exchange between chips left out: every psum hands back its input
    jax.lax.psum = lambda x, axis_name, **_: x
assert len(jax.devices()) == 4
print(json.dumps(run.run(tiny_cell(4), seed=SEED, seconds=1.0,
                         traced={traced})))
"""


def _child(drop_exchange: bool, traced: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), drop_exchange=drop_exchange,
                        traced=traced)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("traced", [False, True])
def test_four_chip_run_is_correct(traced):
    result = _child(drop_exchange=False, traced=traced)
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4


def test_four_chips_without_their_exchange_are_not_correct():
    result = _child(drop_exchange=True, traced=False)
    assert result["correct"] is False, result["checks"]
