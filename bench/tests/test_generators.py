"""The benchmark's copied generators reproduce the system's own bit for
bit, at a small size and at the cells' sizes."""
import numpy as np
import pytest

from harness import gen

SMALL = dict(n_flows=3000, total_packets=30000, alpha=1.1,
             max_flow_frac=0.02, n_epochs=8, log2_te=16, burstiness=0.2,
             arrival="paced")
CELL = dict(SMALL, n_flows=200_000, total_packets=2_000_000, n_epochs=32)


@pytest.mark.parametrize("k", [4, 14])
@pytest.mark.parametrize("t", [SMALL, CELL, dict(SMALL, arrival="poisson",
                                                 burstiness=0.0)],
                         ids=["small", "cell", "poisson"])
def test_trace_matches_gen_workload(k, t):
    from repro.net.topology import FatTree
    from repro.net.traffic import gen_workload

    seed = 2**31 + 17
    want = gen_workload(FatTree(k), seed=seed, **t)
    got = gen.gen_trace(gen.FatTree(k), t, seed)
    for f in ("keys", "sizes", "path_mat", "pkt_flow", "pkt_ts"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f), f)
    assert (got.log2_te, got.n_epochs) == (want.log2_te, want.n_epochs)
    assert got.observations == int(want.path_len[want.pkt_flow].sum())


@pytest.mark.parametrize("k", [4, 8, 14, 16])
def test_fat_tree_paths(k):
    from repro.net.topology import FatTree

    ours, theirs = gen.FatTree(k), FatTree(k)
    assert (ours.n_switches, ours.n_hosts) == (theirs.n_switches,
                                               theirs.n_hosts)
    rng = np.random.RandomState(k)
    src = rng.randint(0, ours.n_hosts, 5000)
    dst = rng.randint(0, ours.n_hosts, 5000)
    keys = rng.randint(0, 2**32, 5000, dtype=np.uint64).astype(np.uint32)
    np.testing.assert_array_equal(ours.paths(src, dst, keys),
                                  theirs.paths(src, dst, keys))


@pytest.mark.parametrize("n,base,gini", [(20, 128 * 1024, 0.4),
                                         (245, 8 * 1024, 0.4),
                                         (20, 4096, 0.0)])
def test_memories_match_memories_for(n, base, gini):
    from benchmarks.common import memories_for

    class Topo:
        n_switches = n

    want = memories_for(Topo, base, gini, np.random.RandomState(101))
    got = gen.memories(n, dict(base_bytes=base, gini=gini, memory_seed=101))
    assert got == want


def test_hashes_match():
    from repro.core import hashing as H

    rng = np.random.RandomState(3)
    keys = rng.randint(0, 2**32, 10000, dtype=np.uint64).astype(np.uint32)
    np.testing.assert_array_equal(gen.mix32(keys), H.mix32(keys))
    for mod in (2, 7, 1000, 123974, 2**20 + 3):
        np.testing.assert_array_equal(gen.hash_mod(keys, 11, mod),
                                      H.hash_mod(keys, 11, mod))
