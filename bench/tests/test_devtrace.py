"""The trace reduction, on a hand-made record and on the record of a
short window of ``nws96.gram-tile`` recorded on a TPU v5e chip."""
import gzip
import json
import os

import pytest

import devtrace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_reduce_hand_made_record():
    record = {
        "devices": {
            "/device:TPU:0": [["%fusion.1 = f32[8]{0} fusion()", 100, 50],
                              ["%while.4 = (f32[8]) while()", 160, 100],
                              ["%xmv_gram_tile.3 = f32[8] custom-call()",
                               170, 60],               # inside the while
                              ["fusion.2", 200, 20],   # inside the kernel
                              ["copy.9", 390, 30]],     # runs past w1
            "/device:TPU:1": [["fusion.1", 0, 400]],
        },
        "spans": [["bench.window", 0, 400], ["bench.block", 0, 250],
                  ["bench.block", 250, 150], ["bench.store.save", 300, 90],
                  ["bench.block", 900, 10]],           # outside
    }
    r = devtrace.reduce(record)
    assert r["window_s"] == pytest.approx(400e-9)
    # TPU:0 busy [100,150] + [160,260] + [390,400] = 160; TPU:1 400
    assert r["busy_s"] == pytest.approx((160 + 400) / 2 * 1e-9)
    assert r["n_devices"] == 2
    # self times: the while keeps 100 - 60, the kernel 60 - 20
    assert devtrace.op_seconds(r, ("xmv_gram_tile",)) == \
        pytest.approx(40 / 2 * 1e-9)
    assert r["op_s"]["while"] == pytest.approx(40 / 2 * 1e-9)
    assert r["op_s"]["fusion"] == pytest.approx((50 + 20 + 400) / 2 * 1e-9)
    # TPU:0 idles [0,100] and [150,160] in the first block; a gap goes
    # whole to the span open at its midpoint: [260,390] to the save
    idle = dict(r["idle_gaps"])
    assert idle["bench.block"] == pytest.approx((100 + 10) / 2 * 1e-9)
    assert idle["bench.store.save"] == pytest.approx(130 / 2 * 1e-9)
    assert r["device_ops"][0][0] == "fusion"


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        devtrace.reduce({"devices": {"/device:TPU:0": []}, "spans": []})


def test_read_xplane_finds_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.block"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    record = devtrace.read_xplane(str(path))
    names = [s[0] for s in record["spans"]]
    assert names.count("bench.window") == 1 and "bench.block" in names
    (w,) = [s for s in record["spans"] if s[0] == "bench.window"]
    (b,) = [s for s in record["spans"] if s[0] == "bench.block"]
    assert w[1] <= b[1] and b[1] + b[2] <= w[1] + w[2]


def test_reduce_recorded_chip_trace():
    with gzip.open(os.path.join(FIXTURES, "gram_tile_trace.json.gz"),
                   "rt") as f:
        record = json.load(f)
    r = devtrace.reduce(record)
    assert r["window_s"] == pytest.approx(2.0)
    assert r["busy_s"] == pytest.approx(1.50785125, rel=1e-9)
    kernel = devtrace.op_seconds(r, ("xmv_gram_tile", "xmv_row_panel"))
    assert kernel == pytest.approx(1.481750139, rel=1e-9)
    assert r["device_ops"][0][0] == "xmv_gram_tile"
    assert [name for name, _ in r["idle_gaps"]] == ["bench.block",
                                                    "bench.batch"]
    # the metric readers on the same reduction
    from plain import load_module
    run = type("Run", (), {"trace": r})()
    idle = load_module("metrics", "device_idle_share").read(run)
    busy = load_module("metrics", "xmv_busy_share").read(run)
    assert idle == pytest.approx(100 * (1 - 1.50785125 / 2.0), rel=1e-9)
    assert busy == pytest.approx(100 * 1.481750139 / 1.50785125, rel=1e-9)
