"""Plain numpy reference of the disaggregated-sketch semantics.

Written from the paper's description and the deployment's stated
parameters, and imports nothing of the system under test:

* every switch holds one single-row sketch fragment of ``width =
  memory // 4`` counters (Count Sketch or Count-Min), whose column,
  sign and subepoch hashes are the seeded avalanche hash of the flow key
  under seeds derived from (switch, epoch, role);
* an epoch of ``2^log2_te`` time units is cut into ``n`` subepochs (a
  power of two); a packet's subepoch is the timestamp bit-slice
  ``ts[log2_te - log2 n : log2_te]``, and a fragment counts a packet only
  in its flow's hashed subepoch (§4.1), and single-hop flows also in the
  subepoch ``n/2`` later when §4.4 mitigation is on;
* the §4.2 control: each fragment's error bound (Eq. 4/5) is read from
  its counters, and ``n`` doubles above twice the target and halves
  below half of it (Eq. 6).  In window mode ``n`` is frozen for the
  window's epochs, and at its end the window's bounds are replayed in
  order, each rescaled from the frozen ``n`` to the walking one;
* the fragment-merge window query (§4.3): each on-path fragment's
  counter, signed and scaled by ``n``, merged by median (CS) or minimum
  (CMS) across the path, and summed over the window's epochs.

Counters are integers (int64 here).  ``precision="bf16"`` rounds every
counter to bfloat16 before it is read, which is the control that the
correctness limits must reject.

An Eq. 6 decision whose rescaled bound lies within ``TIE`` (relative) of
a threshold is a tie: arithmetic of any finite precision (the system
reads its bounds in float32, this reference in float64) may decide it
either way, and both outcomes are admissible.  ``replay`` records its
ties, takes the other branch at those named in ``flips``, and
``replay_matching`` finds the admissible trajectory a given one follows.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

_GOLD = np.uint32(2654435769)
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_ROLE_COL, _ROLE_SIGN, _ROLE_SUB = 0x1000, 0x2000, 0x3000
N_MAX = 1024
TIE = 1e-5


def _h(keys: np.ndarray, seeds) -> np.ndarray:
    """Seeded avalanche hash, uint32 (numpy wraps uint32 products)."""
    x = keys.astype(np.uint32) * _GOLD + np.asarray(seeds, np.uint32)
    x = (x ^ (x >> np.uint32(16))) * _M1
    x = (x ^ (x >> np.uint32(15))) * _M2
    return x ^ (x >> np.uint32(16))


def _col(keys, seeds, widths) -> np.ndarray:
    """Column in [0, width): ``(h * width) >> 32`` formed from 16-bit limbs
    in 32-bit registers, as the switches compute it (every product and
    sum wraps at 2^32); evaluated in 64-bit integers here."""
    u32 = 0xFFFFFFFF
    h = _h(keys, seeds).astype(np.int64)
    w = np.asarray(widths, np.int64)
    t = (((h >> 16) * w & u32) + ((((h & 0xFFFF) * w) & u32) >> 16)) & u32
    return t >> 16


def _seed(frag, epoch, role, base) -> np.ndarray:
    return ((np.asarray(frag, np.int64) * 1_000_003 + epoch * 7919 + role
             + base) & 0x7FFFFFFF)


def _bf16(c: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return c.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


@dataclass
class Deployment:
    """What the reference needs of a configuration and its trace."""

    kind: str                   # "cs" | "cms"
    mitigation: bool
    base_seed: int
    widths: np.ndarray          # (S,) counters per switch
    rho_target: float
    window: int
    log2_te: int
    n_epochs: int
    keys: np.ndarray            # (n_flows,) uint32
    path_mat: np.ndarray        # (n_flows, 5), -1 padded
    pkt_flow: np.ndarray        # (P,)
    pkt_ts: np.ndarray          # (P,) int64


@dataclass
class Result:
    """Counters and control trajectory of one replay."""

    counters: Dict[Tuple[int, int], np.ndarray] = field(default_factory=dict)
    n_used: List[np.ndarray] = field(default_factory=list)   # per epoch
    n_log: List[np.ndarray] = field(default_factory=list)    # after epoch
    peb: List[np.ndarray] = field(default_factory=list)
    ties: List[Tuple[int, int]] = field(default_factory=list)


def _observations(d: Deployment):
    """Every (packet, switch on its path) pair, sorted by (epoch, switch):
    returns switch, key, timestamp, single-hop flag and epoch bounds."""
    n_sw = len(d.widths)
    hops = d.path_mat[d.pkt_flow]                       # (P, 5)
    pkt, pos = np.nonzero(hops >= 0)
    sw = hops[pkt, pos]
    ep = d.pkt_ts[pkt] >> d.log2_te
    order = np.argsort(ep * n_sw + sw, kind="stable")
    pkt, sw, ep = pkt[order], sw[order], ep[order]
    flow = d.pkt_flow[pkt]
    single = (d.path_mat[flow] >= 0).sum(axis=1) == 1
    bounds = np.searchsorted(ep, np.arange(d.n_epochs + 1))
    return sw, d.keys[flow], d.pkt_ts[pkt], single, bounds


def _epoch_counters(d: Deployment, e: int, n: np.ndarray, sw, keys, ts,
                    single) -> List[np.ndarray]:
    """One epoch's (n[s], width[s]) counters of every switch."""
    log_n = np.log2(n).astype(np.int64)
    n_o, w_o = n[sw], d.widths[sw]
    sub_pkt = (ts >> (d.log2_te - log_n[sw])) & (n_o - 1)
    sub_flow = (_h(keys, _seed(sw, e, _ROLE_SUB, d.base_seed)).astype(np.int64)
                & (n_o - 1))
    hit = sub_pkt == sub_flow
    if d.mitigation:
        second = (sub_flow + n_o // 2) & (n_o - 1)
        hit |= single & (n_o >= 2) & (sub_pkt == second)
    sw, keys, sub, w_o = sw[hit], keys[hit], sub_pkt[hit], w_o[hit]
    col = _col(keys, _seed(sw, e, _ROLE_COL, d.base_seed), w_o)
    val = np.ones(len(keys), np.float64)
    if d.kind == "cs":
        val -= 2.0 * (_h(keys, _seed(sw, e, _ROLE_SIGN, d.base_seed))
                      & np.uint32(1))
    sizes = n * d.widths
    off = np.concatenate([[0], np.cumsum(sizes)])
    flat = np.bincount(off[sw] + sub * w_o + col, weights=val,
                       minlength=int(off[-1]))
    flat = np.rint(flat).astype(np.int64)
    return [flat[off[s]:off[s + 1]].reshape(n[s], d.widths[s])
            for s in range(len(n))]


def _peb(c: np.ndarray, kind: str) -> float:
    """Eq. 4 per subepoch row, Eq. 5 mean over the epoch's rows."""
    c = c.astype(np.float64)
    w = c.shape[1]
    if kind == "cs":
        rows = np.sqrt((c * c).sum(axis=1) / w)
    else:
        rows = np.abs(c).sum(axis=1) / w
    return float(rows.mean())


def _next_n(n: int, peb: float, rho: float, flip: bool) -> Tuple[int, bool]:
    """Eq. 6, and whether the decision was a tie (taken the other way
    when ``flip``)."""
    for thr, moved in ((2.0 * rho, min(2 * n, N_MAX)),
                       (rho / 2.0, max(1, n // 2))):
        if abs(peb - thr) <= TIE * thr:
            return (n if (peb > thr if thr > rho else peb < thr) == flip
                    else moved), True
    if peb > 2.0 * rho:
        return min(2 * n, N_MAX), False
    if peb < rho / 2.0:
        return max(1, n // 2), False
    return n, False


def replay(d: Deployment, precision: str = "exact",
           flips: frozenset = frozenset()) -> Result:
    """Window-mode replay of the whole trace, every fragment from n = 1.
    ``flips``: (epoch, switch) ties decided the other way."""
    sw, keys, ts, single, bounds = _observations(d)
    n_sw = len(d.widths)
    ns = np.ones(n_sw, np.int64)
    out = Result()
    for e0 in range(0, d.n_epochs, d.window):
        frozen = ns.copy()
        pebs = []
        for e in range(e0, min(e0 + d.window, d.n_epochs)):
            lo, hi = bounds[e], bounds[e + 1]
            cs = _epoch_counters(d, e, frozen, sw[lo:hi], keys[lo:hi],
                                 ts[lo:hi], single[lo:hi])
            if precision == "bf16":
                cs = [_bf16(c) for c in cs]
            elif precision != "exact":
                raise ValueError(f"unknown precision {precision!r}")
            for s, c in enumerate(cs):
                out.counters[(e, s)] = c
            out.n_used.append(frozen.copy())
            pebs.append(np.array([_peb(c, d.kind) for c in cs]))
        for e, p in enumerate(pebs, start=e0):
            for s in range(n_sw):
                ns[s], tie = _next_n(int(ns[s]), p[s] * frozen[s] / ns[s],
                                     d.rho_target, (e, s) in flips)
                if tie:
                    out.ties.append((e, s))
            out.peb.append(p)
            out.n_log.append(ns.copy())
    return out


def replay_matching(d: Deployment, n_log: Sequence[np.ndarray],
                    max_flips: int = 8) -> Result:
    """The admissible replay whose trajectory ``n_log`` (one (S,) array
    per epoch) follows, flipping only ties; where it leaves every
    admissible trajectory, the replay that agrees with it longest."""
    flips: frozenset = frozenset()
    res = replay(d)
    for _ in range(max_flips):
        diff = [(e, int(np.flatnonzero(n != res.n_log[e])[0]))
                for e, n in enumerate(n_log) if (n != res.n_log[e]).any()]
        if not diff or diff[0] not in res.ties or diff[0] in flips:
            break
        flips = flips | {diff[0]}
        res = replay(d, flips=flips)
    return res


def deployment(cfg: Dict, trace, widths: np.ndarray) -> Deployment:
    """The reference's view of a configuration file and its trace."""
    sk, ctl = cfg["sketch"], cfg["control"]
    return Deployment(
        kind=sk["kind"], mitigation=bool(sk["mitigation"]),
        base_seed=int(sk["base_seed"]), widths=widths,
        rho_target=float(ctl["rho_target"]), window=int(ctl["window"]),
        log2_te=trace.log2_te, n_epochs=trace.n_epochs, keys=trace.keys,
        path_mat=trace.path_mat, pkt_flow=trace.pkt_flow,
        pkt_ts=trace.pkt_ts)


def window_estimates(d: Deployment, res: Result, flows: np.ndarray,
                     epochs: Sequence[int]) -> np.ndarray:
    """Fragment-merge window estimates of ``flows`` (indices into the
    trace's flows) over ``epochs``; every flow's path must be given."""
    keys = d.keys[flows]
    paths = d.path_mat[flows]
    plen = (paths >= 0).sum(axis=1)
    total = np.zeros(len(flows))
    for e in epochs:
        raw = np.full(paths.shape, np.nan)
        for s in np.unique(paths[paths >= 0]):
            c = res.counters[(e, int(s))]
            n, w = c.shape
            r, p = np.nonzero(paths == s)
            k = keys[r]
            sub = (_h(k, _seed(s, e, _ROLE_SUB, d.base_seed)).astype(np.int64)
                   & (n - 1))
            v = c[sub, _col(k, _seed(s, e, _ROLE_COL, d.base_seed), w)]
            v = v.astype(np.float64)
            if d.mitigation and n >= 2:
                v2 = c[(sub + n // 2) & (n - 1),
                       _col(k, _seed(s, e, _ROLE_COL, d.base_seed), w)]
                v = np.where(plen[r] == 1, 0.5 * (v + v2), v)
            if d.kind == "cs":
                v = v * (1.0 - 2.0 * (
                    _h(k, _seed(s, e, _ROLE_SIGN, d.base_seed))
                    & np.uint32(1)))
            raw[r, p] = v * n
        if d.kind == "cs":
            total += np.nanmedian(raw, axis=1)
        else:
            total += np.nanmin(raw, axis=1)
    return total
