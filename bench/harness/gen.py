"""Seeded traffic and memory generators of the benchmark.

These are copies of the system's own generators (``repro.net.traffic``:
``unique_keys``, ``zipf_sizes``, ``_bursty_timestamps``, ``gen_workload``,
``gini_memories``; ``repro.net.topology.FatTree.paths``;
``repro.core.hashing.mix32``/``hash_mod``), kept here so that no change to
the program can change the work a cell asks for.  ``bench/tests/
test_generators.py`` shows that they reproduce the originals bit for bit.

The paper's traffic is CAIDA equinix-nyc (~2M packets, ~200K flows over
~5 s), which is not redistributable: the trace is generated with its
statistics (Zipf flow sizes capped per flow, uniform host mapping with
src != dst, paced arrivals with a bursty share).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np
from scipy import stats

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_SEED_MULT = np.uint32(2654435769)


def mix32(x) -> np.ndarray:
    x = np.asarray(x).astype(np.uint32)
    x = (x ^ (x >> np.uint32(16))) * _M1
    x = (x ^ (x >> np.uint32(15))) * _M2
    return x ^ (x >> np.uint32(16))


def hash_mod(keys, seed: int, mod: int) -> np.ndarray:
    """Lemire fast-range of the seeded avalanche hash, in 16-bit limbs."""
    h = mix32(np.asarray(keys).astype(np.uint32) * _SEED_MULT
              + np.uint32(seed))
    mod_u = np.uint32(mod)
    t = ((h >> np.uint32(16)) * mod_u
         + (((h & np.uint32(0xFFFF)) * mod_u) >> np.uint32(16)))
    return (t >> np.uint32(16)).astype(np.int32)


def unique_keys(n: int, seed: int) -> np.ndarray:
    base = np.arange(n, dtype=np.uint32) + np.uint32((seed * 0x9E3779B9)
                                                     & 0xFFFFFFFF)
    return mix32(base)


def zipf_sizes(n_flows: int, total_packets: int, alpha: float,
               rng: np.random.RandomState,
               max_flow_frac: float) -> np.ndarray:
    p = np.arange(1, n_flows + 1, dtype=np.float64) ** (-alpha)
    p /= p.sum()
    p = np.minimum(p, max_flow_frac)
    p /= p.sum()
    sizes = np.maximum(1, np.round(p * total_packets)).astype(np.int64)
    rng.shuffle(sizes)
    return sizes


def bursty_timestamps(sizes: np.ndarray, duration: int, burstiness: float,
                      rng: np.random.RandomState, n_epochs: int,
                      arrival: str, burst_width: float = 0.25,
                      pkts_per_burst: int = 8):
    """Per-packet (flow index, timestamp): each flow is active over a
    cyclic sub-window (elephants over the whole trace), paced or Poisson
    within it, with a ``burstiness`` share in RTT-scale bursts."""
    n_flows = len(sizes)
    start_f = rng.rand(n_flows)
    dur_f = 0.1 + 0.9 * rng.beta(1.5, 1.5, size=n_flows)
    dur_f = np.where(sizes >= 2 * max(n_epochs, 1), 1.0, dur_f)
    pkt_flow = np.repeat(np.arange(n_flows), sizes)
    p = len(pkt_flow)
    if arrival == "paced":
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        idx_in_flow = np.arange(p) - starts[pkt_flow]
        phase = rng.rand(n_flows)
        u = (idx_in_flow + phase[pkt_flow] +
             0.25 * rng.randn(p)) / sizes[pkt_flow]
    else:
        u = rng.rand(p)
    frac = start_f[pkt_flow] + u * dur_f[pkt_flow]
    if burstiness > 0:
        n_bursts = np.maximum(1, sizes // pkts_per_burst)
        burst_id = (rng.rand(p) * n_bursts[pkt_flow]).astype(np.int64)
        center_u = mix32((pkt_flow * 131 + burst_id).astype(np.uint32)
                         ).astype(np.float64) / 2.0**32
        center = start_f[pkt_flow] + center_u * dur_f[pkt_flow]
        jitter = rng.rand(p) * (burst_width / max(n_epochs, 1))
        bursty = rng.rand(p) < burstiness
        frac = np.where(bursty, center + jitter, frac)
    frac = np.mod(frac, 1.0)
    ts = np.minimum((frac * duration).astype(np.int64), duration - 1)
    return pkt_flow, ts


class FatTree:
    """k-ary fat-tree: k^2/2 edge, k^2/2 aggregation, (k/2)^2 core
    switches, k^3/4 hosts; ECMP choices hashed from the flow key."""

    def __init__(self, k: int):
        self.k = k
        self.half = k // 2
        n_edge = k * self.half
        self.agg0 = n_edge
        self.core0 = 2 * n_edge
        self.n_switches = 2 * n_edge + self.half ** 2
        self.n_hosts = n_edge * self.half

    def paths(self, src, dst, keys) -> np.ndarray:
        """(n, 5) switch ids per flow, -1 padded."""
        h = self.half
        keys = np.asarray(keys, dtype=np.uint32)
        e_s, e_d = np.asarray(src) // h, np.asarray(dst) // h
        pod_s, pod_d = e_s // h, e_d // h
        agg_choice = hash_mod(keys, 11, h)
        core_choice = hash_mod(keys, 13, h)
        agg_s = self.agg0 + pod_s * h + agg_choice
        core = self.core0 + agg_choice * h + core_choice
        agg_d = self.agg0 + pod_d * h + agg_choice
        out = np.full((len(keys), 5), -1, dtype=np.int64)
        same_edge = e_s == e_d
        same_pod = (pod_s == pod_d) & ~same_edge
        cross = ~same_edge & ~same_pod
        out[:, 0] = e_s
        out[same_pod, 1] = agg_s[same_pod]
        out[same_pod, 2] = e_d[same_pod]
        out[cross, 1] = agg_s[cross]
        out[cross, 2] = core[cross]
        out[cross, 3] = agg_d[cross]
        out[cross, 4] = e_d[cross]
        return out


@dataclass
class Trace:
    """A generated trace: flows, their paths and per-packet arrivals."""

    keys: np.ndarray        # (n_flows,) uint32 distinct flow ids
    sizes: np.ndarray       # (n_flows,) packets per flow
    path_mat: np.ndarray    # (n_flows, 5) switch ids, -1 padded
    pkt_flow: np.ndarray    # (P,) flow of each packet
    pkt_ts: np.ndarray      # (P,) int64 timestamps
    log2_te: int
    n_epochs: int

    @property
    def path_len(self) -> np.ndarray:
        return (self.path_mat >= 0).sum(axis=1)

    @property
    def observations(self) -> int:
        """Switch observations: packets times switches on their path."""
        return int(self.path_len[self.pkt_flow].sum())


def gen_trace(topo: FatTree, t: Dict, seed: int,
              key_seed: Optional[int] = None) -> Trace:
    """The trace of traffic parameters ``t`` (a config's ``traffic``):
    flow sizes, hosts, ECMP routes and arrivals from ``seed``, and the
    flow keys the sketches see from ``key_seed``.  Without ``key_seed``
    the routes are hashed from those same keys, which is
    ``gen_workload``; with it, every key seed sends the same flows along
    the same routes, so every switch sees the same packets under other
    identities."""
    rng = np.random.RandomState(seed)
    sizes = zipf_sizes(t["n_flows"], t["total_packets"], t["alpha"], rng,
                       t["max_flow_frac"])
    route_keys = unique_keys(t["n_flows"], seed + 1)
    keys = (route_keys if key_seed is None
            else unique_keys(t["n_flows"], key_seed + 1))
    src = rng.randint(0, topo.n_hosts, size=t["n_flows"])
    dst = rng.randint(0, topo.n_hosts, size=t["n_flows"])
    same = src == dst
    dst[same] = (dst[same] + 1 + rng.randint(0, topo.n_hosts - 1,
                                             size=same.sum())) % topo.n_hosts
    path_mat = topo.paths(src, dst, route_keys)
    duration = t["n_epochs"] << t["log2_te"]
    pkt_flow, pkt_ts = bursty_timestamps(sizes, duration, t["burstiness"],
                                         rng, t["n_epochs"], t["arrival"])
    return Trace(keys, sizes, path_mat, pkt_flow, pkt_ts, t["log2_te"],
                 t["n_epochs"])


def gini_memories(n: int, base_bytes: int, gini: float,
                  rng: np.random.RandomState) -> np.ndarray:
    """Lognormal per-switch memories with Gini index ``gini``, mean
    ``base_bytes`` (the paper's §6 heterogeneity generator)."""
    if gini <= 0:
        return np.full(n, base_bytes, dtype=np.int64)
    sigma = np.sqrt(2.0) * stats.norm.ppf((gini + 1.0) / 2.0)
    x = rng.lognormal(mean=0.0, sigma=sigma, size=n)
    x = x / x.mean() * base_bytes
    return np.maximum(x.astype(np.int64), 64)


def memories(n_switches: int, m: Dict) -> Dict[int, int]:
    """Per-switch sketch memory of a config's ``memory`` block: drawn once
    from its own ``memory_seed``, so every run seed sees one deployment."""
    vals = gini_memories(n_switches, m["base_bytes"], m["gini"],
                         np.random.RandomState(m["memory_seed"]))
    return {sw: int(vals[sw]) for sw in range(n_switches)}
