"""The generator copies draw the program's graphs, and the DrugBank
configuration fixes one bucket layout for every seed."""
import json
import os

import numpy as np
import pytest

from plain import BENCH, load_module
from repro.data import bucket_graphs, make_drugbank_like_dataset, \
    make_synthetic_dataset

FIELDS = ("adjacency", "edge_labels", "vertex_labels", "start_prob",
          "stop_prob")


def same_graphs(ours, theirs) -> bool:
    return len(ours) == len(theirs) and all(
        np.array_equal(getattr(a, f), getattr(b, f))
        for a, b in zip(ours, theirs) for f in FIELDS)


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_nws_copy_draws_the_programs_graphs(seed):
    gen = load_module("generators", "nws")
    rng = np.random.default_rng(seed)
    ours = [gen.newman_watts_strogatz(96, 3, 0.1, rng=rng,
                                      n_vertex_labels=8, stop_prob=0.05)
            for _ in range(160)]
    assert same_graphs(ours, make_synthetic_dataset("nws", 160, 96,
                                                    seed=seed))


def test_nws_configuration_keeps_the_structure_across_seeds():
    cfg = config("nws96")
    gen = load_module("generators", "nws")
    a, b = (gen.make(cfg["dataset"], s, cfg["stop_prob"])
            for s in (3, 2**31 + 3))
    # seed 0 of the program's generator drew the structure
    first = make_synthetic_dataset("nws", 160, 96, seed=0)
    assert all(np.array_equal(x.adjacency, y.adjacency)
               for x, y in zip(a, first))
    assert all(np.array_equal(x.adjacency, y.adjacency)
               for x, y in zip(a, b))
    assert not np.array_equal(a[0].edge_labels, b[0].edge_labels)
    assert all(np.array_equal(x.edge_labels != 0, x.adjacency != 0)
               for x in a)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_drugbank_copy_draws_the_programs_graphs(seed):
    gen = load_module("generators", "drugbank")
    assert same_graphs(gen.make_drugbank_like_dataset(128, seed),
                       make_drugbank_like_dataset(128, seed=seed))


def test_drugbank_sizes_are_the_programs_seed_0_draw():
    sizes = config("drugbank")["dataset"]["sizes"]
    assert sizes == [g.n_nodes for g in make_drugbank_like_dataset(
        128, seed=0)]


def test_drugbank_configuration_has_one_bucket_layout():
    cfg = config("drugbank")
    gen = load_module("generators", "drugbank")
    layouts = set()
    for seed in (0, 1, 2, 2**31 + 5):
        graphs = gen.make(cfg["dataset"], seed, cfg["stop_prob"])
        assert [g.n_nodes for g in graphs] == cfg["dataset"]["sizes"]
        ds = bucket_graphs(graphs)
        layouts.add(tuple((b.pad_to, tuple(b.indices)) for b in ds.buckets))
    assert len(layouts) == 1
