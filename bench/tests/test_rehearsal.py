"""Each cell end to end on the CPU, at a tiny size, in interpret mode.

The harness's look for a chip is skipped (``require_tpu=False``); each mix
is cut to a few coflows and three backlog shapes, so that a request takes
well under a second.
"""
import json
import os
import subprocess
import sys

import pytest

import run
from yardstick import registry

TINY = {"fb2010-n150-k4.oneshot-m60": 2, "paper-n16-k3.oneshot-m100": 6}


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    orig = registry.traffic

    def cut(cell):
        return lambda name: dict(orig(name), coflows=TINY[cell], shapes=3)
    return lambda cell: monkeypatch.setattr(registry, "traffic", cut(cell))


def test_every_piece_is_found_by_name():
    bench = registry.load_benchmark()
    for c in bench["configs"]:
        cfg = registry.config(c["name"])
        assert registry.ROOT / c["file"] == \
            registry.BENCH / "configs" / f"{c['name']}.json"
        registry.reference(cfg["reference"])
        assert set(cfg["limits"]) == {"choices_differing",
                                      "referee_violations", "wcct_rel_gap"}
    for w in bench["workloads"]:
        registry.driver(registry.traffic(w["traffic"])["driver"])
        assert registry.metrics_of(w["name"], "end_to_end")
        assert registry.metrics_of(w["name"], "per_layer")
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]).read)


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_and_is_correct(tiny, cell, traced):
    tiny(cell)
    line = run.run_cell(cell, 2**33 + 17, 0.5, traced, require_tpu=False)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    names = {m["name"] for m in registry.metrics_of(
        cell, "per_layer" if traced else "end_to_end")}
    got = set(line["metrics"])
    if traced:
        # the device readers find nothing on the CPU and say nothing
        assert got == names - {"kernel.device_ns_per_flow",
                               "coflow_assign_roofline", "device.idle_pct"}
        # set-up served every shape, so nothing compiled in the window
        assert line["metrics"]["assign.compiles_per_request"]["value"] == 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "busy_s" in line["device"] and "window_s" in line["device"]
    else:
        assert got == names
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    json.dumps(line)


def test_command_refuses_without_a_chip():
    root = registry.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper-n16-k3.oneshot-m100", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr


def test_benchmark_alone_prints_no_result(tmp_path):
    """A directory with only ``BENCHMARK.json`` and the benchmark's files
    (no program) ends non-zero and prints nothing on standard output."""
    import shutil

    root = registry.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            "run.run_cell('paper-n16-k3.oneshot-m100', 1, 0.5, False, "
            "require_tpu=False)")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr


def test_every_seed_serves_the_same_shapes_in_its_own_order():
    cfg = registry.config("paper-n16-k3")
    mix = dict(registry.traffic("oneshot-m100"), coflows=6, shapes=4)
    reqs = registry.driver("oneshot_closed").Requests(cfg, mix)

    def cycles(seed):
        counts = [reqs.window(seed, r).n_flows for r in range(8)]
        return counts[:4], counts[4:]
    sizes = sorted(reqs.warmup(k).n_flows for k in range(4))
    a, b = cycles(2**33 + 1), cycles(5)
    assert all(sorted(c) == sizes for c in a + b)
    assert a != b
    w = reqs.window(5, 0)
    assert reqs.window(5, 0).demands.tolist() == w.demands.tolist()
    assert reqs.window(6, 0).demands.tolist() != w.demands.tolist()
