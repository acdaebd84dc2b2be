"""The benchmark's Kronecker generator: Graph500's parameters, one
undirected graph per configuration with no isolated vertex, vertex ids
permuted from the seed, and a cache that hands the same graph back."""

import subprocess

import numpy as np
import pytest

from bench import graphgen, harness

TINY = {"generator": "kronecker", "scale": 9, "edge_factor": 16,
        "abc": [0.57, 0.19, 0.19], "directed": False, "seed": 1}
BIG_SEED = 2**31 + 12345


def test_configs_use_graph500_parameters():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        g = harness.resolve(spec, w["name"]).config["graph"]
        assert g["edge_factor"] == 16
        assert g["abc"] == [0.57, 0.19, 0.19]
        assert g["directed"] is False
        # a configuration is named for its scale: graph500-<scale>.<kind>
        assert g["scale"] == int(w["config"].split("-")[1].split(".")[0])


def test_structure_is_deterministic_and_follows_the_generator_seed():
    a = graphgen.structure(TINY)
    b = graphgen.structure(TINY)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    c = graphgen.structure(dict(TINY, seed=2))
    assert c[1].size != a[1].size or not np.array_equal(c[2], a[2])


def test_undirected_simple_and_no_isolated_vertex():
    ids, src, dst = graphgen.structure(TINY, chunk=1 << 11)  # many chunks
    n = ids.size
    assert ids.dtype == src.dtype == dst.dtype == np.int32
    assert np.all(np.diff(ids) > 0) and ids.max() < graphgen.n_ids(TINY)
    assert src.min() >= 0 and src.max() < n and dst.max() < n
    assert not np.any(src == dst)
    key = src.astype(np.int64) * n + dst
    assert np.all(np.diff(key) > 0)  # sorted, no duplicate
    back = np.sort(dst.astype(np.int64) * n + src)
    assert np.array_equal(back, key)  # every edge both ways
    assert np.all(np.bincount(src, minlength=n) > 0)


def test_chunking_does_not_change_the_draws_kept():
    # the same generator in one chunk or several drops the same kinds of
    # edges: sizes agree to within the draws' noise
    one = graphgen.structure(TINY)[1].size
    many = graphgen.structure(TINY, chunk=1 << 10)[1].size
    assert abs(one - many) < 0.02 * one


def test_matches_the_programs_generator_in_distribution():
    from repro.graph import rmat_graph

    g = dict(TINY, scale=12)
    ids, src, dst = graphgen.structure(g)
    ref = rmat_graph(scale=12, edge_factor=16, a=0.57, b=0.19, c=0.19,
                     seed=11)
    pairs = np.unique(np.minimum(ref.src, ref.dst) * 2**12
                      + np.maximum(ref.src, ref.dst))
    assert abs(src.size - 2 * pairs.size) < 0.01 * src.size
    deg = np.bincount(src)
    ref_deg = np.bincount(np.concatenate([pairs // 2**12, pairs % 2**12]))
    assert abs(deg.max() - ref_deg.max()) < 0.1 * ref_deg.max()


def test_labels_permute_the_ids_from_the_seed():
    ids = graphgen.structure(TINY)[0]
    a = graphgen.labels(TINY, ids, BIG_SEED)
    assert a.dtype == np.int64 and np.unique(a).size == ids.size
    assert a.min() >= 0 and a.max() < graphgen.n_ids(TINY)
    assert np.array_equal(a, graphgen.labels(TINY, ids, BIG_SEED))
    assert not np.array_equal(a, graphgen.labels(TINY, ids, BIG_SEED + 1))
    # seeds that differ only above bit 31 label differently
    assert not np.array_equal(
        a, graphgen.labels(TINY, ids, BIG_SEED + (1 << 32)))


def test_child_makes_it_and_the_cache_hands_it_back(tmp_path):
    *g1, info1 = graphgen.load_or_generate(TINY, str(tmp_path),
                                           platform="cpu",
                                           log=lambda m: None)
    *g2, info2 = graphgen.load_or_generate(TINY, str(tmp_path),
                                           platform="cpu",
                                           log=lambda m: None)
    assert (info1["cache"], info2["cache"]) == ("miss", "hit")
    for a, b, c in zip(g1, g2, graphgen.structure(TINY)):
        assert np.array_equal(a, b) and np.array_equal(a, c)


def test_child_never_falls_back_to_another_device(tmp_path):
    with pytest.raises(subprocess.CalledProcessError):
        graphgen.load_or_generate(TINY, str(tmp_path), platform="tpu",
                                  log=lambda m: None)
    assert not any(tmp_path.iterdir())
