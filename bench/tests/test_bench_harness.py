"""The harness, rehearsed on the CPU at a tiny size.

``run.run`` is the test-only entry: it skips ``main``'s look for a TPU and
drives the rest of a run. The faults are planted under the timed path, and
each must turn ``correct`` false.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from bench import roofline, run, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 7   # more than 32 signed bits hold


def tiny_cell(chips: int = 1) -> run.Cell:
    config = json.loads((ROOT / "bench/configs/web-stanford.json").read_text())
    # at this size FORA's budget is under the lane cap for every source;
    # the headroom sizes the lanes to the cap, so every answer meets it
    config.update(name="tiny", n=3_000, m=24_000, max_in_degree=700,
                  graph_seed=3, devices=chips, walk_safety=64.0)
    return run.Cell("web-stanford.uniform", chips, config,
                    traffic.load_mix(ROOT / "bench/mixes/uniform.json"),
                    BENCH["end_to_end"], BENCH["per_layer"])


@pytest.fixture
def quiet_cache(monkeypatch):
    """Keep the persistent compilation cache as the test process has it."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compilation_cache",
                        lambda: None)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="unknown cell"):
        run.load_cell("no-such.cell", BENCH)


def test_unknown_metric_is_an_error():
    bench = json.loads(json.dumps(BENCH))
    bench["per_layer"].append(dict(bench["per_layer"][0],
                                   name="no.such_metric"))
    with pytest.raises(KeyError, match="unknown per-layer metric"):
        run.load_cell("web-stanford.uniform", bench)
    bench = json.loads(json.dumps(BENCH))
    bench["end_to_end"].append(dict(bench["end_to_end"][0], name="no_such"))
    with pytest.raises(KeyError, match="unknown end-to-end metric"):
        run.load_cell("web-stanford.uniform", bench)


def test_unknown_source_kind_is_an_error(tmp_path):
    mix = json.loads((ROOT / "bench/mixes/uniform.json").read_text())
    mix["sources"] = {"kind": "no_such_kind"}
    (tmp_path / "mix.json").write_text(json.dumps(mix))
    with pytest.raises(KeyError, match="unknown source kind"):
        traffic.load_mix(tmp_path / "mix.json")


def test_a_mix_draws_its_job_from_its_own_seed():
    mix = traffic.load_mix(ROOT / "bench/mixes/uniform.json")
    degree = np.ones(500, np.int64)
    job = traffic.job_sources(mix, 500, degree)
    assert job.shape == (mix["queries"],) and 0 <= job.min() <= job.max() < 500
    assert np.array_equal(job, traffic.job_sources(mix, 500, degree))
    other = traffic.job_sources(dict(mix, job_seed=mix["job_seed"] + 1), 500,
                                degree)
    assert not np.array_equal(job, other)
    assert sorted(traffic.order(mix, SEED)) == list(range(mix["queries"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    found = run.load_cell(cell, BENCH)
    assert found.config["devices"] == found.chips
    assert found.config["name"] in cell
    assert {m["name"] for m in found.end_to_end} >= {"setup_s"}
    assert found.per_layer


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "web-stanford.uniform",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("traced", [False, True])
def test_cpu_rehearsal_of_a_run(quiet_cache, monkeypatch, traced):
    # the peaks table knows chips only; the rehearsal lends the CPU one
    monkeypatch.setitem(roofline.PEAKS, "cpu", {"hbm_bytes_per_s": 1e11})
    result = run.run(tiny_cell(), seed=SEED, seconds=1.0, traced=traced)
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    if traced:
        assert result["device"]["window_s"] > 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(result["metrics"]) == {"answers_per_s", "answer_p95_s",
                                          "setup_s"}
        assert all(m["value"] > 0 for m in result["metrics"].values())


def _unchanged(res, sources):
    """The fused step hands back its initial state: all mass in the
    residual, none pushed or walked."""
    import jax.numpy as jnp

    pi = np.zeros(res.pi.shape, np.float32)
    pi[np.arange(len(sources)), sources] = 1.0
    return res._replace(pi=jnp.asarray(pi),
                        residual_mass=jnp.ones(len(sources), jnp.float32))


def _altered(res, sources):
    """An answer altered where it is produced: the source's own estimate
    halved."""
    import jax.numpy as jnp

    pi = np.array(res.pi)
    pi[np.arange(len(sources)), sources] *= 0.5
    return res._replace(pi=jnp.asarray(pi))


@pytest.mark.parametrize("fault", [_unchanged, _altered])
def test_a_broken_answer_is_not_correct(quiet_cache, monkeypatch, fault):
    from repro.ppr import executor as module

    orig = module.fora_fused

    def broken(dg, sources, *args, **kwargs):
        return fault(orig(dg, sources, *args, **kwargs),
                     np.asarray(sources).reshape(-1))

    monkeypatch.setattr(module, "fora_fused", broken)
    result = run.run(tiny_cell(), seed=SEED, seconds=0.5, traced=False)
    assert result["correct"] is False, result["checks"]


def test_fewer_walks_than_the_budget_are_not_correct(quiet_cache,
                                                     monkeypatch):
    """Every answer's walks drawn on 64 lanes, under FORA's budget: the
    rows stay unbiased, so only the budget count can see it."""
    from repro.ppr import executor as module

    orig = module.fora_fused

    def few(dg, sources, *args, **kwargs):
        return orig(dg, sources, *args, **dict(kwargs, num_walks=64))

    monkeypatch.setattr(module, "fora_fused", few)
    cell = tiny_cell()
    cell.config["n"] = 2_950      # a shape no other test compiled the step for
    result = run.run(cell, seed=SEED + 2, seconds=0.5, traced=False)
    assert result["checks"]["walks_short"]["value"] >= 1, result["checks"]
    assert result["correct"] is False


def test_walks_left_out_are_not_correct(quiet_cache, monkeypatch):
    """The walk phase returns nothing: the answer is the push alone."""
    import importlib

    import jax.numpy as jnp

    fora = importlib.import_module("repro.ppr.fora")

    def no_walks(edge_dst, out_offsets, out_degree, residual, key, *,
                 n, **_):
        return jnp.zeros((n,), residual.dtype)

    monkeypatch.setattr(fora, "residual_walks", no_walks)
    cell = tiny_cell()
    cell.config["n"] = 2_900      # a shape no other test compiled the step for
    result = run.run(cell, seed=SEED + 1, seconds=0.5, traced=False)
    assert result["correct"] is False, result["checks"]
