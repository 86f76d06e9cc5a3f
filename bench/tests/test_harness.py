"""The harness on the CPU at a small size, with its look for a chip
skipped: a cell, a configuration and a metric added as files only; the
controls and planted faults come out not correct; the command refuses
to run without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import devtrace
import harness
import work
import repro.distributed.gram as gram
from plain import BENCH

REPO = os.path.dirname(BENCH)
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout's benchmark with one configuration, two cells and one
    metric more, added the way a later change adds them: new files and
    new entries in BENCHMARK.json."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(BENCH, "configs", "nws96.json")) as f:
        tiny = json.load(f)
    tiny["dataset"].update(n_graphs=16, n_nodes=24)
    (bench / "configs" / "tiny.json").write_text(json.dumps(tiny))
    shutil.copy(os.path.join(FIXTURES, "blocks_in_window.py"),
                bench / "metrics")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "self-test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["n_graphs", "n_nodes"],
                            "why": "self-test"})
    spec["workloads"] += [
        {"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1,
         "why": "self-test"} for t in ("lowrank", "gram-tile")]
    spec["per_layer"].append(
        {"name": "blocks_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "driver",
         "moves": "pairs_per_s", "workloads": ["tiny.lowrank"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def run(root, workload, seed=2**31 + 7, traced=False, overrides=None):
    cell = harness.load_cell(workload, root)
    return harness.run_cell(cell, seed, 1.0, traced, jax.devices()[:1],
                            time.perf_counter(), overrides, root=root)


def test_clean_run_is_correct(root):
    r = run(root, "tiny.lowrank")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"pairs_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["max_rel_err"]["value"] < \
        r["checks"]["max_rel_err"]["limit"]


def test_added_cell_and_metric_are_found_by_name(root, monkeypatch):
    # the CPU trace has no TPU plane: stand a recorded one in for it
    record = {"devices": {"/device:TPU:0": [["fusion.1", 10, 60]]},
              "spans": [["bench.window", 0, 100]]}
    monkeypatch.setattr(devtrace, "read_xplane", lambda path: record)
    monkeypatch.setitem(work.PEAKS, "cpu", work.PEAKS["TPU v5 lite"])
    r = run(root, "tiny.lowrank", traced=True)
    assert r["correct"]
    assert r["metrics"]["blocks_in_window"]["value"] > 0
    assert r["metrics"]["device_idle_share"]["value"] == pytest.approx(40)
    assert "xmv_roofline" not in r["metrics"]      # not this cell's
    assert r["device"]["busy_s"] == pytest.approx(60e-9)
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload,control", [
    ("tiny.lowrank", "loose-tol"),
    ("tiny.gram-tile", "bf16-packs"),
])
def test_control_is_not_correct(root, workload, control):
    cell = harness.load_cell(workload, root)
    controls = {**cell.config["controls"],
                **cell.traffic.get("controls", {})}
    r = run(root, workload, overrides=controls[control])
    assert not r["correct"]
    assert r["checks"]["max_rel_err"]["value"] > \
        r["checks"]["max_rel_err"]["limit"]


def _altered(out):
    out["values"] = out["values"].copy()
    out["values"][0] *= 1.001             # one answer per block
    return out


def _misplaced(out):
    out["values"] = out["values"][::-1].copy()     # answers swapped
    return out


def _unhealthy(out):
    out["status"] = out["status"].copy()
    out["status"][0] |= 8                 # a PCG guard tripped
    return out


@pytest.mark.parametrize("fault", [_altered, _misplaced, _unhealthy])
def test_planted_fault_is_not_correct(root, monkeypatch, fault):
    solve = gram.solve_pair_block

    def broken(*args, **kw):
        return fault(solve(*args, **kw))

    monkeypatch.setattr(gram, "solve_pair_block", broken)
    r = run(root, "tiny.lowrank")
    assert not r["correct"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nws96.lowrank",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _command(REPO)
    assert p.returncode == 3 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _command(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
