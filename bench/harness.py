"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
that belongs to one configuration, traffic mix or metric is found by its
name: ``bench/configs/<config>.json`` (through the configuration's
``file``), ``bench/traffic/<traffic>.json``, ``bench/generators/``,
``bench/base_kernels/`` and ``bench/metrics/<metric>.py``.

The window drives the program's own entry, ``GramDriver.run``, with a
progress callback that stamps each saved block. The first block that
finishes after ``--seconds`` closes the window; a build that completes
inside it starts again on a fresh store with the same driver.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import glob
import inspect
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

import repro.core
from repro.core import Graph
from repro.data import BucketedDataset, bucket_graphs
from repro.distributed import ChunkStore, GramDriver
from repro.distributed.gram import gram_pair_step

import reference
import devtrace
import work
from plain import BENCH, PlainGraph, load_module

ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- the cell -------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench: str


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    wl = _by_name(spec["workloads"], name, "workload")
    entry = _by_name(spec["configs"], wl["config"], "configuration")
    bench = os.path.join(root, "bench")

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(name=name, chips=int(wl["chips"]),
                config=_read_json(os.path.join(root, entry["file"])),
                traffic=_read_json(os.path.join(
                    bench, "traffic", wl["traffic"] + ".json")),
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)],
                bench=bench)


def chip_devices(chips: int) -> list:
    """The first ``chips`` TPU chips; :class:`NoChip` where there are
    none or too few. No run falls back to another platform."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default platform is"
                     f" {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found"
                     f" {len(devices)}")
    return devices[:chips]


def use_compile_cache(root: str = ROOT) -> None:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache`` at the root
    of the checkout (a fixed path: the path is part of the cache key).
    Every program is cached, so only a checkout's first run compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# -- data and the program's objects ---------------------------------------
def make_graphs(cell: Cell, seed: int) -> list[PlainGraph]:
    ds = cell.config["dataset"]
    gen = load_module("generators", ds["generator"], cell.bench)
    graphs = gen.make(ds, seed, cell.config["stop_prob"])
    if len(graphs) != ds["n_graphs"]:
        raise ValueError(f"generator {ds['generator']!r} made"
                         f" {len(graphs)} graphs, not {ds['n_graphs']}")
    return graphs


class TracedDataset(BucketedDataset):
    """The program's bucketed dataset with a host span around batching."""

    def batch(self, indices, pad_to):
        with TraceAnnotation("bench.batch"):
            return super().batch(indices, pad_to)


class TracedStore(ChunkStore):
    """The program's chunk store with host spans around saves and
    restores."""

    def save_block(self, *args, **kw):
        with TraceAnnotation("bench.store.save"):
            return super().save_block(*args, **kw)

    def load_block(self, *args, **kw):
        with TraceAnnotation("bench.store.load"):
            return super().load_block(*args, **kw)


def program_dataset(graphs: list[PlainGraph]) -> TracedDataset:
    """The program's graphs, bucketed by its own defaults."""
    ds = bucket_graphs([Graph.create(g.adjacency, g.edge_labels,
                                     g.vertex_labels, g.start_prob,
                                     g.stop_prob) for g in graphs])
    return TracedDataset(graphs=ds.graphs, buckets=ds.buckets,
                         multiple_of=ds.multiple_of)


def program_kernels(config: dict) -> tuple:
    return tuple(getattr(repro.core, k["type"])(**k["params"])
                 for k in (config["vertex_kernel"], config["edge_kernel"]))


def reference_kernels(cell: Cell) -> tuple:
    return tuple(
        functools.partial(
            load_module("base_kernels", k["type"], cell.bench).kappa,
            **k["params"])
        for k in (cell.config["vertex_kernel"], cell.config["edge_kernel"]))


def driver_settings(cell: Cell, overrides: dict | None = None) -> dict:
    """GramDriver keywords: the configuration's guarantees and block
    size, then the traffic's execution settings, then ``overrides``
    (a control). JSON lists become tuples, dtype names dtypes."""
    kw = {"tol": cell.config["tol"],
          "pairs_per_block": cell.config["pairs_per_block"],
          **cell.traffic["driver"], **(overrides or {})}
    for k, v in kw.items():
        if isinstance(v, list):
            kw[k] = tuple(v)
    if isinstance(kw.get("pack_dtype"), str):
        kw["pack_dtype"] = jnp.dtype(kw["pack_dtype"])
    return kw


def make_driver(cell: Cell, ds, devices, overrides=None) -> GramDriver:
    mesh = Mesh(np.array(devices).reshape(cell.config["mesh"]),
                ("data", "model"))
    vk, ek = program_kernels(cell.config)
    return GramDriver(ds, mesh, vk, ek, store=None,
                      **driver_settings(cell, overrides))


# -- set-up ---------------------------------------------------------------
def _warm_build(driver: GramDriver, work_dir: str) -> None:
    """One whole build on a throwaway store: every block shape of the
    window is compiled and run once, on the driver the window uses."""
    path = os.path.join(work_dir, "warm")
    driver.store = ChunkStore(path)
    driver.run()
    driver.store = None
    shutil.rmtree(path)


def _warm_shapes(driver: GramDriver, work_dir: str) -> None:
    """One block of every distinct argument shape of the build, solved
    through a twin of the driver's step. Its jitted solve is the one the
    driver's step calls, so the window finds each program compiled; the
    twin keeps its own pack cache, so the window still packs each graph
    when it first meets it, as a build does."""
    params = inspect.signature(gram_pair_step).parameters
    kw = {k: getattr(driver, k) for k in params
          if k not in ("mesh", "vertex_kernel", "edge_kernel")
          and hasattr(driver, k)}
    step = gram_pair_step(driver.mesh, driver.vertex_kernel,
                          driver.edge_kernel, **kw)
    seen = set()
    for b in driver.blocks():
        args = (driver.ds.batch(b.rows, pad_to=b.pad_row),
                driver.ds.batch(b.cols, pad_to=b.pad_col), b.rows, b.cols)
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in
                    jax.tree.leaves(step.lower(*args).args_info))
        if sig not in seen:
            seen.add(sig)
            np.asarray(step(*args).values)


WARM_UPS = {"build": _warm_build, "shapes": _warm_shapes}


# -- the window -----------------------------------------------------------
class CompileCounter:
    """Backend compiles and traces on the main thread while active."""

    def __init__(self):
        self.active = False
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and threading.current_thread() is \
                threading.main_thread():
            self.compiles += event == COMPILE_EVENT
            self.traces += event == TRACE_EVENT


class _WindowClosed(Exception):
    pass


@dataclasses.dataclass
class Window:
    seconds: float      # host clock, first build's start to the last block
    builds: list        # [(store root, driver.health)], one per build
    blocks: int
    compiles: int
    traces: int


class _Span:
    """A host span opened and closed from different callbacks."""

    def __init__(self):
        self._ann = None

    def open(self, name: str) -> None:
        self.close()
        self._ann = TraceAnnotation(name)
        self._ann.__enter__()

    def close(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


def run_window(driver: GramDriver, seconds: float, work_dir: str,
               counter: CompileCounter) -> Window:
    stamps: list[float] = []
    builds: list = []
    block = _Span()

    def progress(done: int, todo: int) -> None:
        t = time.perf_counter()
        stamps.append(t)
        if t >= deadline:
            block.close()
            raise _WindowClosed
        block.open("bench.block")

    window = _Span()
    window.open("bench.window")
    counter.active = True
    t0 = time.perf_counter()
    deadline = t0 + seconds
    block.open("bench.block")
    try:
        while True:
            store = TracedStore(os.path.join(work_dir,
                                             f"build{len(builds):04d}"))
            driver.store = store
            try:
                with TraceAnnotation("bench.build"):
                    driver.run(progress=progress)
            except _WindowClosed:
                builds.append((store.root, copy.deepcopy(driver.health)))
                break
            builds.append((store.root, copy.deepcopy(driver.health)))
    finally:
        counter.active = False
        block.close()
        window.close()
        driver.store = None
    return Window(seconds=stamps[-1] - t0, builds=builds,
                  blocks=len(stamps), compiles=counter.compiles,
                  traces=counter.traces)


# -- what the window saved, and the check --------------------------------
@dataclasses.dataclass
class Saved:
    """Every pair the window's blocks saved, flattened."""
    rows: np.ndarray
    cols: np.ndarray
    values: np.ndarray
    iterations: np.ndarray
    unhealthy: np.ndarray       # non-finite value or a PCG status bit
    misplaced_blocks: int       # rows/cols that differ from the plan
    healed_pairs: int           # in blocks that took a retry/escalation
    quarantined: int            # pairs the driver dropped


def collect(window: Window, plan: dict) -> Saved:
    parts = {k: [] for k in ("rows", "cols", "values", "iterations",
                             "unhealthy")}
    misplaced = healed = quarantined = 0
    for root, health in window.builds:
        store = ChunkStore(root)
        sick = {int(b) for b in health["blocks"]}
        quarantined += len(health["quarantined_pairs"])
        for bid in sorted(store.done_blocks()):
            rec = store.load_block(bid)
            b = plan[bid]
            if not (np.array_equal(rec["rows"], b.rows)
                    and np.array_equal(rec["cols"], b.cols)):
                misplaced += 1
            vals = np.asarray(rec["values"], np.float64)
            status = rec.get("status", np.zeros(len(vals), np.int32))
            parts["rows"].append(rec["rows"])
            parts["cols"].append(rec["cols"])
            parts["values"].append(vals)
            parts["iterations"].append(rec["iterations"])
            parts["unhealthy"].append(~np.isfinite(vals) | (status != 0))
            if bid in sick:
                healed += len(vals)
    cat = {k: np.concatenate(v) for k, v in parts.items()}
    return Saved(**cat, misplaced_blocks=misplaced, healed_pairs=healed,
                 quarantined=quarantined)


def sample_pairs(saved: Saved, graphs, n: int, seed: int) -> np.ndarray:
    """Indices into ``saved`` of the pairs to check: drawn from the
    seed, always with the pair of the largest product system."""
    nodes = np.array([g.n_nodes for g in graphs])
    size = nodes[saved.rows] * nodes[saved.cols]
    rng = np.random.default_rng([seed, 0x5EED])
    pick = rng.choice(len(size), size=min(n, len(size)), replace=False)
    return np.unique(np.append(pick[:n - 1], int(np.argmax(size))))


def check(cell: Cell, graphs, saved: Saved, seed: int) -> dict:
    """The numbers that decide ``correct``, each ``{"value", "limit"}``:
    the widest relative gap of a sampled pair to the reference, and the
    driver's failed pairs and misplaced blocks (limit 0)."""
    kv, ke = reference_kernels(cell)
    lim = cell.config["check"]
    t = time.perf_counter()
    worst = 0.0
    for k in sample_pairs(saved, graphs, lim["pairs"], seed):
        i, j = int(saved.rows[k]), int(saved.cols[k])
        ref = reference.mgk(graphs[i], graphs[j], kv, ke)
        gap = abs(saved.values[k] - ref) / abs(ref)
        worst = max(worst, gap if np.isfinite(gap) else np.inf)
    print(f"bench: reference took {time.perf_counter() - t:.3f} s",
          file=sys.stderr)
    failed = failed_pairs(saved)
    return {"max_rel_err": {"value": float(worst),
                            "limit": lim["max_rel_err"]},
            "failed_pairs": {"value": failed, "limit": 0},
            "misplaced_blocks": {"value": saved.misplaced_blocks,
                                 "limit": 0}}


def failed_pairs(saved: Saved) -> int:
    return int(saved.unhealthy.sum()) + saved.healed_pairs + \
        saved.quarantined


# -- one run --------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What a metric reader sees (``bench/metrics/<name>.py``)."""
    setup_s: float
    window: Window
    saved: Saved
    nnz: np.ndarray             # per graph
    nodes: np.ndarray
    edge_flops: int
    chips: int
    peaks: dict | None
    trace: dict | None          # devtrace.reduce() of the traced window


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             devices, t_start: float, overrides: dict | None = None,
             root: str = ROOT) -> dict:
    """One run: returns the result line as a dict (``checks`` last)."""
    work_dir = os.path.join(root, ".smoke", "bench", cell.name)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    counter = CompileCounter()
    t = time.perf_counter()
    graphs = make_graphs(cell, seed)
    driver = make_driver(cell, program_dataset(graphs), devices, overrides)
    t_data = time.perf_counter()
    WARM_UPS[cell.traffic["warmup"]](driver, work_dir)
    print(f"bench: set-up: {t - t_start:.3f} s to the data, data and"
          f" driver {t_data - t:.3f} s, warm-up"
          f" {time.perf_counter() - t_data:.3f} s", file=sys.stderr)
    trace_dir = os.path.join(work_dir, "trace")
    if traced:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    window = run_window(driver, seconds, work_dir, counter)
    if traced:
        jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices)
    plan = {b.block_id: b for b in driver.blocks()}
    del driver
    saved = collect(window, plan)
    print(f"bench: window {window.seconds:.3f} s, {window.blocks} blocks,"
          f" {len(window.builds)} builds, {len(saved.values)} pairs,"
          f" {window.compiles} compiles, {window.traces} traces",
          file=sys.stderr)
    checks = check(cell, graphs, saved, seed)
    reduced = None
    if traced:
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        reduced = devtrace.reduce(devtrace.read_xplane(path))
    kind = devices[0].device_kind
    edge = cell.config["edge_kernel"]["type"]
    run = Run(setup_s=setup_s, window=window, saved=saved,
              nnz=np.array([g.nnz for g in graphs]),
              nodes=np.array([g.n_nodes for g in graphs]),
              edge_flops=load_module("base_kernels", edge,
                                     cell.bench).FLOPS,
              chips=len(devices),
              peaks=work.peaks(kind) if traced else None, trace=reduced)
    metrics = {}
    for m in cell.per_layer if traced else cell.end_to_end:
        value = load_module("metrics", m["name"], cell.bench).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": int(len(saved.values) + saved.quarantined),
              "failed": failed_pairs(saved),
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    shutil.rmtree(work_dir, ignore_errors=True)
    return result
