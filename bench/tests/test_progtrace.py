"""The reduction of the program's own spans and counters: on a hand-made
record, on the records of short windows of both cells recorded on a TPU
v5e chip, and through the harness on the CPU at a small size."""
import gzip
import hashlib
import json
import os
import shutil
import time
import types

import jax
import pytest

import devtrace
import harness
import progtrace
import work
from plain import BENCH, load_module

REPO = os.path.dirname(BENCH)
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NEW = ("batch_ms_per_block", "pack_ms_per_block", "host_syncs_per_block",
       "h2d_kb_per_block", "idle_attributed_share", "matvec_pairs_per_pair",
       "xmv_lowrank_busy_share")


def _fixture(name: str) -> dict:
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        return json.load(f)


def test_reduce_hand_made_record():
    record = {
        "devices": {"/device:TPU:0": [[100, 50, 1],
                                      [160, 100, 0],     # a while loop
                                      [170, 60, 1],      # in the while
                                      [390, 30, 0]]},
        "spans": [["bench.window", 0, 400], ["mgk.build", 0, 400],
                  ["mgk.block", 20, 360], ["bench.batch", 20, 80],
                  ["mgk.batch", 30, 60], ["mgk.save", 300, 80],
                  ["mgk.batch", 900, 10]],             # outside
    }
    r = progtrace.reduce(record)
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(160e-9)
    assert r["scope_s"] == pytest.approx(110e-9)     # 50 + 60, self times
    # self times: the innermost span owns each instant of the window
    assert r["span_s"] == pytest.approx({
        "mgk.build": 40e-9, "mgk.block": 200e-9, "bench.batch": 20e-9,
        "mgk.batch": 60e-9, "mgk.save": 80e-9})
    # idle [0,100] splits at 20, 30, 90; [150,160] and [260,390] split
    # at 300 and 380
    assert r["idle"] == pytest.approx({
        "mgk.build": 20e-9 + 10e-9, "bench.batch": 20e-9,
        "mgk.batch": 60e-9, "mgk.block": 10e-9 + 40e-9, "mgk.save": 80e-9})
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


@pytest.mark.parametrize("op_name,scoped", [
    ("jit(step)/jit(mgk_pairs)/while/body/vmap(xmv_lowrank)/"
     "rij,jl,rkl->ik/dot_general", True),
    ("jit(step)/xmv_lowrank/dot_general", True),
    ("jit(step)/jit(mgk_pairs)/while/body/mul", False),
    ("jit(step)/xmv_lowrank_other/dot_general", False),
])
def test_an_op_name_is_in_scope_by_a_step_of_its_stack(op_name, scoped):
    assert (progtrace._IN_SCOPE.search(op_name) is not None) is scoped


def test_the_lowrank_step_names_its_scoped_instructions(tmp_path):
    """The compiled modules in a CPU trace's metadata plane name the
    lowrank XMV's instructions; a device event names its instruction
    by its HLO text."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import KroneckerDelta, SquareExponential
    from repro.data import bucket_graphs, make_drugbank_like_dataset
    from repro.distributed import GramDriver
    gs = [g for g in make_drugbank_like_dataset(20, seed=5)
          if 9 <= g.n_nodes <= 16][:3]
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    drv = GramDriver(bucket_graphs(gs, max_buckets=1), mesh,
                     KroneckerDelta(0.5, 8), SquareExponential(1.0, 10))
    with jax.profiler.trace(str(tmp_path)):
        drv.run()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    scoped = progtrace.scoped_instructions(str(path))
    assert any(name.startswith("dot") for name in scoped)
    assert progtrace.instruction(
        "%multiply_multiply_fusion.7 = (f32[64]{0}) fusion(f32[64]{0} "
        "%p.1), kind=kLoop") == "multiply_multiply_fusion.7"


def test_read_xplane_keeps_both_prefixes(tmp_path):
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from repro import obs
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with obs.span("mgk.block", block=7):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    record = progtrace.read_xplane(str(path))
    assert sorted(s[0] for s in record["spans"]) == ["bench.window",
                                                     "mgk.block"]


def test_devtrace_reduction_of_the_old_fixture_is_unchanged():
    r = devtrace.reduce(_fixture("gram_tile_trace.json.gz"))
    digest = hashlib.sha256(json.dumps(r).encode()).hexdigest()
    assert digest == \
        "284ea00f305f155cc7d11515b5d6882312796dfa7456c969f2fca83a5d6f9913"


def _recorded_run(name: str):
    """A run as the readers see it, from a fixture: the record of a
    short traced window and the window's counters, blocks and pairs."""
    fx = _fixture(name)
    window = types.SimpleNamespace(
        blocks=fx["blocks"],
        builds=[("store", {"counters": c}) for c in fx["builds"]])
    saved = types.SimpleNamespace(values=[0.0] * fx["pairs"])
    return types.SimpleNamespace(window=window, saved=saved), fx


@pytest.fixture
def recorded(monkeypatch):
    def load(name):
        run, fx = _recorded_run(name)
        reduced = progtrace.reduce(fx)
        monkeypatch.setattr(progtrace, "of_run", lambda run: reduced)
        return run, reduced, {m: load_module("metrics", m).read(run)
                              for m in NEW}
    return load


def test_readers_on_the_recorded_lowrank_window(recorded):
    run, r, m = recorded("lowrank_program_trace.json.gz")
    assert m["pack_ms_per_block"] is None
    assert m["host_syncs_per_block"] == 1.0
    assert 9400 < m["h2d_kb_per_block"] <= 9456.5
    assert m["matvec_pairs_per_pair"] > 11
    assert 0 < m["xmv_lowrank_busy_share"] <= 100
    assert m["batch_ms_per_block"] > 0
    assert m["idle_attributed_share"] >= 90
    top = max(r["idle"], key=r["idle"].get)
    assert top == "mgk.batch"


def test_readers_on_the_recorded_gram_tile_window(recorded):
    run, r, m = recorded("gram_tile_program_trace.json.gz")
    assert m["host_syncs_per_block"] == 35.0
    assert m["h2d_kb_per_block"] > 9456.5
    assert m["xmv_lowrank_busy_share"] is None
    assert m["pack_ms_per_block"] > m["batch_ms_per_block"] > 0
    assert m["idle_attributed_share"] >= 90
    assert not any(k.startswith("bench.") for k, v in r["idle"].items()
                   if v > 0.1 * sum(r["idle"].values()))


def test_readers_read_nothing_from_a_program_without_spans_or_counters():
    run = types.SimpleNamespace(
        window=types.SimpleNamespace(blocks=3, builds=[("nowhere", {})]),
        saved=types.SimpleNamespace(values=[0.0] * 192))
    assert progtrace.of_run(run) is None
    for m in NEW:
        assert load_module("metrics", m).read(run) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose new metrics also apply to a CPU-sized cell."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(BENCH, "configs", "nws96.json")) as f:
        tiny = json.load(f)
    tiny["dataset"].update(n_graphs=16, n_nodes=24)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(tiny))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "self-test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["n_graphs", "n_nodes"],
                            "why": "self-test"})
    spec["workloads"].append({"name": "tiny.lowrank", "config": "tiny",
                              "traffic": "lowrank", "chips": 1,
                              "why": "self-test"})
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("tiny.lowrank")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def test_readers_find_the_trace_and_counters_of_a_run(root, monkeypatch):
    """The harness's own traced run on the CPU: the span readers find
    the trace beside the window's stores, the counter readers the
    builds' counters (the CPU trace has no TPU plane: a recorded one
    stands in for the harness's device reduction)."""
    record = {"devices": {"/device:TPU:0": [["fusion.1", 10, 60]]},
              "spans": [["bench.window", 0, 100]]}
    monkeypatch.setattr(devtrace, "read_xplane", lambda path: record)
    monkeypatch.setitem(work.PEAKS, "cpu", work.PEAKS["TPU v5 lite"])
    cell = harness.load_cell("tiny.lowrank", root)
    r = harness.run_cell(cell, 2**31 + 11, 1.0, True, jax.devices()[:1],
                         time.perf_counter(), root=root)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"]
    assert m["batch_ms_per_block"] > 0
    assert m["host_syncs_per_block"] == 1.0
    # 64-pair blocks of 16 graphs padded to 24: two batches a block
    assert m["h2d_kb_per_block"] <= 2 * 64 * (2 * 24 * 24 + 5 * 24 + 1) \
        * 4 / 1024
    assert m["matvec_pairs_per_pair"] >= m["pcg_iters_per_pair"]
    assert "pack_ms_per_block" not in m          # lowrank packs nothing
    assert "xmv_lowrank_busy_share" not in m     # no TPU plane on the CPU
