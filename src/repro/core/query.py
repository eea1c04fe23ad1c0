"""Central querying (paper §4.3): composite sketches from subepoch records.

Per epoch:
  Step 1 — the caller retrieves the records of the fragments on the queried
  flow's path (all flows in one call share a path).
  Step 2 — every record is queried as a single-row sketch, its estimate is
  split over ``N_R = n_m / n`` *normalized* subepochs, the per-normalized-
  subepoch estimates are merged across fragments (min for CMS, median for
  CS/UnivMon), temporal blind spots are filled with the mean of the observed
  normalized subepochs, and the slot estimates are summed into the epoch
  estimate.

Everything is vectorized over the queried keys (numpy; this is the
controller-side analysis plane, not the data plane).
"""
from __future__ import annotations

import warnings
from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import hashing as H
from .fragment import EpochRecords, level_seed_mix


def _raw_estimates(rec: EpochRecords, keys: np.ndarray,
                   level: Optional[int]) -> np.ndarray:
    """Query one record-set as single-row sketches: raw per-key estimates
    from the subepoch each key was mapped to. Returns (n_keys,) plus the
    flow→subepoch mapping."""
    col_seed, sign_seed, _ = rec.seeds()
    counters = rec.counters
    if rec.kind == "um":
        assert level is not None
        counters = counters[level]
        col_seed = level_seed_mix(col_seed, level)
        sign_seed = level_seed_mix(sign_seed, level)
    w = counters.shape[-1]
    col = H.hash_mod(keys, col_seed, w)
    signed = rec.kind in ("cs", "um")
    sgn = H.hash_sign(keys, sign_seed).astype(np.float64) if signed else 1.0
    return counters, col, sgn


def _fill_layer(layer: np.ndarray, raw: np.ndarray, sub: np.ndarray,
                n_r: int, sel: Optional[np.ndarray] = None) -> None:
    """Spread raw estimates over their N_R normalized-subepoch slots."""
    n_keys = layer.shape[0]
    o = raw / n_r
    rows = np.arange(n_keys)
    cols = sub.astype(np.int64)[:, None] * n_r + np.arange(n_r)[None, :]
    if sel is None:
        layer[rows[:, None], cols] = o[:, None]
    else:
        layer[rows[sel][:, None], cols[sel]] = o[sel][:, None]


def query_epoch(records: Sequence[EpochRecords], keys: np.ndarray,
                kind: str, single_hop: Optional[np.ndarray] = None,
                level: Optional[int] = None,
                merge: str = "subepoch") -> np.ndarray:
    """Epoch estimate for each key from the on-path fragments' records.

    merge="subepoch": the Fig. 9 / §4.3 Step-2 procedure — normalize all
    records into n_m subepoch slots, merge per slot (min/median), fill
    temporal blind spots with the mean of covered slots, sum.

    merge="fragment": the §4.2 "amplify success probability through
    merging" reading — each fragment's record is scaled proportionally
    (x n, §1) into an epoch-level estimate, then min/median is taken
    ACROSS FRAGMENTS.  Keeps the full path-length merge robustness at the
    cost of assuming within-epoch rate uniformity per fragment.
    """
    keys = np.asarray(keys, dtype=np.uint32)
    n_keys = len(keys)
    if n_keys == 0 or not records:
        return np.zeros(n_keys)
    if merge == "fragment":
        return _query_epoch_fragment_merge(records, keys, kind, single_hop,
                                           level)
    n_m = max(r.n for r in records)

    layers: List[np.ndarray] = []
    for rec in records:
        counters, col, sgn = _raw_estimates(rec, keys, level)
        _, _, sub_seed = rec.seeds()
        sub = H.hash_pow2(keys, sub_seed, rec.n)
        n_r = n_m // rec.n
        raw = counters[sub, col].astype(np.float64) * sgn
        layer = np.full((n_keys, n_m), np.nan)
        _fill_layer(layer, raw, sub, n_r)
        layers.append(layer)
        # §4.4 mitigation: single-hop flows carry a second subepoch record.
        if rec.mitigation and rec.n >= 2 and single_hop is not None \
                and single_hop.any():
            sub2 = (sub + rec.n // 2) & (rec.n - 1)
            raw2 = counters[sub2, col].astype(np.float64) * sgn
            layer2 = np.full((n_keys, n_m), np.nan)
            _fill_layer(layer2, raw2, sub2, n_r, sel=single_hop)
            layers.append(layer2)

    est = np.stack(layers)  # (n_layers, n_keys, n_m)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", category=RuntimeWarning)
        if kind == "cms":
            merged = np.nanmin(est, axis=0)
        else:
            merged = np.nanmedian(est, axis=0)
        # Temporal blind spots: extrapolate from the mean of observed slots.
        fill = np.nanmean(merged, axis=1, keepdims=True)
    fill = np.where(np.isnan(fill), 0.0, fill)
    merged = np.where(np.isnan(merged), fill, merged)
    return merged.sum(axis=1)


def _query_epoch_fragment_merge(records, keys, kind, single_hop, level):
    ests = np.empty((len(records), len(keys)))
    for i, rec in enumerate(records):
        counters, col, sgn = _raw_estimates(rec, keys, level)
        _, _, sub_seed = rec.seeds()
        sub = H.hash_pow2(keys, sub_seed, rec.n)
        raw = counters[sub, col].astype(np.float64) * sgn
        if rec.mitigation and rec.n >= 2 and single_hop is not None \
                and single_hop.any():
            sub2 = (sub + rec.n // 2) & (rec.n - 1)
            raw2 = counters[sub2, col].astype(np.float64) * sgn
            raw = np.where(single_hop, (raw + raw2) / 2.0, raw)
        ests[i] = raw * rec.n  # proportional scaling to the epoch (§1)
    if kind == "cms":
        return ests.min(axis=0)
    return np.median(ests, axis=0)


def fleet_query_epoch(stacked, col_seeds: np.ndarray,
                      sign_seeds: np.ndarray, sub_seeds: np.ndarray,
                      ns: np.ndarray, widths: np.ndarray,
                      keys: np.ndarray, kind: str,
                      frag_sel: Optional[np.ndarray] = None,
                      mit: Optional[np.ndarray] = None,
                      single_hop: bool = False) -> np.ndarray:
    """Batched epoch point-query over a fleet's counters.

    ``stacked`` is one epoch's counters: an (n_rows, n_sub_max,
    width_max) block, or a ``FlatStack`` whose ``base`` holds the (n_rows,)
    cell offsets of the epoch (a window buffer's layout, ``repro.kernels.
    sketch_query``).  One vectorized pass (rows are fragments, or
    fragment×level pairs for UnivMon): every row's raw estimate for every
    key is gathered at once (hashes broadcast over the row axis), scaled
    proportionally to the epoch (x n, §1), and merged across rows — min
    for Count-Min, median for Count Sketch / UnivMon levels.
    Semantically identical to ``query_epoch(..., merge="fragment")`` on
    the unpacked per-fragment records (tested in tests/test_fleet.py).

    ``frag_sel`` (bool, (n_rows,)) restricts the merge to the rows on
    the queried flows' path — §4.3 Step 1 (for UnivMon additionally the
    queried level's rows).  Without it, *all* rows are merged, which is
    only correct when every queried flow traverses every fragment (e.g.
    the §6.3 linear-path scenarios): off-path fragments hold near-zero
    collision values that would bias the min/median toward zero.

    ``single_hop=True`` applies the §4.4 mitigation average on rows
    flagged in ``mit``: single-hop flows carry a second subepoch record
    at ``sub + n/2``, and the two estimates are averaged (all queried
    keys must share single-hop status, which query_flows guarantees per
    path group).
    """
    from ..kernels.sketch_query import as_flat

    keys = np.asarray(keys, dtype=np.uint32)
    if isinstance(stacked, np.ndarray):
        flat, base, stride = as_flat(stacked[None])
        base = base[0]
    else:
        flat, base, stride = stacked
    if frag_sel is not None:
        frag_sel = np.asarray(frag_sel, bool)
        if not frag_sel.any():
            raise ValueError(
                "fleet_query_epoch: frag_sel selects no rows — an "
                "all-masked merge has no survivor; drop the epoch "
                "(blind-epoch extrapolation) or widen the selection")
        base = np.asarray(base)[frag_sel]
        stride = np.asarray(stride)[frag_sel]
        col_seeds = np.asarray(col_seeds)[frag_sel]
        sign_seeds = np.asarray(sign_seeds)[frag_sel]
        sub_seeds = np.asarray(sub_seeds)[frag_sel]
        ns = np.asarray(ns)[frag_sel]
        widths = np.asarray(widths)[frag_sel]
        if mit is not None:
            mit = np.asarray(mit, bool)[frag_sel]
    if len(keys) == 0 or len(base) == 0:
        return np.zeros(len(keys))
    ns = np.asarray(ns, np.int64)[:, None]            # (F, 1)
    widths = np.asarray(widths, np.int64)[:, None]
    base = np.asarray(base, np.int64)[:, None]
    stride = np.asarray(stride, np.int64)[:, None]
    k2 = keys[None, :]                                # (1, K)
    col = H.hash_mod(k2, np.asarray(col_seeds)[:, None], widths)   # (F, K)
    sub = H.hash_pow2(k2, np.asarray(sub_seeds)[:, None], ns)
    raw = flat[base + sub * stride + col].astype(np.float64)
    if single_hop and mit is not None and mit.any():
        sub2 = (sub + ns // 2) & (ns - 1)
        raw2 = flat[base + sub2 * stride + col].astype(np.float64)
        use = np.asarray(mit, bool)[:, None] & (ns >= 2)
        raw = np.where(use, 0.5 * (raw + raw2), raw)
    if kind in ("cs", "um"):
        raw = raw * H.hash_sign(k2, np.asarray(sign_seeds)[:, None]
                                ).astype(np.float64)
    raw = raw * ns.astype(np.float64)
    if kind == "cms":
        return raw.min(axis=0)
    return np.median(raw, axis=0)


def fleet_query_window(stacked_by_epoch: Sequence[np.ndarray],
                       params_by_epoch: Sequence[np.ndarray],
                       widths: Optional[np.ndarray], keys: np.ndarray,
                       kind: str,
                       frag_sel=None,
                       single_hop: bool = False) -> np.ndarray:
    """Window point-query over fleet stacks: O_Q = Sum(O) of per-epoch
    batched queries — the fleet twin of ``query_window`` with
    ``merge="fragment"``.  Each epoch's counters are a block or a
    one-epoch ``FlatStack``, as ``fleet_query_epoch`` takes them.

    ``params_by_epoch`` carries each epoch's ``(n_rows, N_PARAMS)``
    fleet parameter table (seeds are per-epoch, so the table differs
    every epoch even for a static fleet); ``widths=None`` reads each
    epoch's hash moduli from its own parameter table (required when a
    resource-reclaim shrink changed a fragment's width mid-replay);
    ``frag_sel`` restricts every epoch's merge to the on-path rows —
    either one (n_rows,) mask for the whole window, or a sequence /
    (E, n_rows) array of per-epoch masks (fragment liveness under
    churn); ``single_hop`` applies the §4.4 average on ``PARAM_MIT``
    rows, as in ``fleet_query_epoch``.
    """
    from ..kernels.sketch_update import fleet as FK

    keys = np.asarray(keys, dtype=np.uint32)
    out = np.zeros(len(keys))
    sels = _per_epoch_sels(frag_sel, len(params_by_epoch))
    for stacked, p, sel in zip(stacked_by_epoch, params_by_epoch, sels):
        out += fleet_query_epoch(
            stacked,
            col_seeds=p[:, FK.PARAM_COL_SEED].astype(np.int64),
            sign_seeds=p[:, FK.PARAM_SIGN_SEED].astype(np.int64),
            sub_seeds=p[:, FK.PARAM_SUB_SEED].astype(np.int64),
            ns=p[:, FK.PARAM_N_SUB].astype(np.int64),
            widths=p[:, FK.PARAM_WIDTH].astype(np.int64)
            if widths is None else widths,
            keys=keys, kind=kind, frag_sel=sel,
            mit=p[:, FK.PARAM_MIT] != 0, single_hop=single_hop)
    return out


def _per_epoch_sels(frag_sel, n_epochs: int) -> List:
    """Normalize a window ``frag_sel`` to one mask per epoch: accepts
    None, a single (n_rows,) mask, or per-epoch masks as an (E, n_rows)
    array / sequence of E masks."""
    if frag_sel is None:
        return [None] * n_epochs
    if isinstance(frag_sel, np.ndarray) and frag_sel.ndim == 1:
        return [frag_sel] * n_epochs
    sels = list(frag_sel)
    if len(sels) != n_epochs:
        raise ValueError(
            f"per-epoch frag_sel has {len(sels)} masks for "
            f"{n_epochs} epochs")
    return sels


def fleet_query_window_device(stack, params_by_epoch, keys: np.ndarray,
                              kind: str,
                              frag_sel: Optional[np.ndarray] = None,
                              single_hop: bool = False,
                              mesh=None) -> np.ndarray:
    """Device-side twin of ``fleet_query_window``: the same §4.3
    fragment-merge window query, run where the stacked counters already
    live so only the ``(K,)`` estimate vector crosses the host boundary.
    Thin re-export of the jitted gather/merge engine — see
    ``repro.kernels.sketch_query.fleet_window_query_device`` for the
    argument contract; ``fleet_query_window`` on the host copy of the
    same stack stays the numpy oracle (tests/test_query_device.py).
    ``mesh``: optional ("switch",) device mesh for a row-sharded stack —
    the merge runs as a shard_map with an all_gather of only the raw
    per-row estimate slices (docs/sharding.md).
    """
    from ..kernels.sketch_query import fleet_window_query_device

    return fleet_window_query_device(stack, params_by_epoch, keys, kind,
                                     frag_sel=frag_sel,
                                     single_hop=single_hop, mesh=mesh)


def um_fleet_query_window_device(stack, params_by_epoch, keys: np.ndarray,
                                 n_levels: int,
                                 frag_sel: Optional[np.ndarray] = None,
                                 mesh=None) -> np.ndarray:
    """All ``n_levels`` UnivMon window estimates in one device call —
    thin re-export of ``repro.kernels.sketch_query.um_window_query_device``
    (the §6.2 per-level inputs; see ``FleetEpochRunner
    .um_level_window_query`` for the routed entry point).  ``mesh`` routes
    a row-sharded stack through the cross-device merge."""
    from ..kernels.sketch_query import um_window_query_device

    return um_window_query_device(stack, params_by_epoch, keys, n_levels,
                                  frag_sel=frag_sel, mesh=mesh)


def window_observability(records_by_epoch: Sequence[Sequence],
                         ) -> Tuple[int, float]:
    """(observable_epochs, scale) of a record-plane query window: how
    many epochs contribute at least one live record, and the §4.3
    blind-epoch extrapolation factor E / E_observable masked window
    estimates are scaled by (``inf`` when every epoch is blind — the
    caller's unobservable-flow error case).  The single source of the
    staleness accounting surfaced by ``DiSketchSystem.observability``
    and applied by ``query_flows``."""
    n = len(records_by_epoch)
    obs = sum(1 for records in records_by_epoch if records)
    return obs, (n / obs if obs else float("inf"))


def query_window(records_by_epoch: Sequence[Sequence[EpochRecords]],
                 keys: np.ndarray, kind: str,
                 single_hop: Optional[np.ndarray] = None,
                 level: Optional[int] = None,
                 merge: str = "subepoch",
                 chunk: int = 16384) -> np.ndarray:
    """Sum of per-epoch estimates over a query window (O_Q = Sum(O))."""
    keys = np.asarray(keys, dtype=np.uint32)
    out = np.zeros(len(keys))
    for start in range(0, len(keys), chunk):
        sl = slice(start, start + chunk)
        sh = single_hop[sl] if single_hop is not None else None
        for records in records_by_epoch:
            if records:
                out[sl] += query_epoch(records, keys[sl], kind,
                                       single_hop=sh, level=level,
                                       merge=merge)
    return out


# ---------------------------------------------------------------------------
# UnivMon network-wide G-sum / entropy over composite sketches (§6.2)
# ---------------------------------------------------------------------------


def um_gsum_combine(ests: np.ndarray, lvl: np.ndarray, g,
                    k_heavy: int = 1024) -> float:
    """The UnivMon top-down Y-recursion over precomputed per-level
    window estimates (``ests``: (n_levels, K); ``lvl``: (K,) level
    membership).  Shared tail of the host and device estimator paths —
    the device plane produces ``ests`` with one batched gather/merge
    (``um_fleet_query_window_device``) and can also run this combine
    on-device (``kernels.sketch_query.um_gsum_device``)."""
    n_levels = ests.shape[0]
    y = 0.0
    for l in range(n_levels - 1, -1, -1):
        sel = lvl >= l
        if not sel.any():
            y = 2.0 * y
            continue
        est = np.maximum(ests[l, sel], 1.0)
        order = np.argsort(-est)[:k_heavy]
        hh_est = est[order]
        in_next = (lvl[sel][order] >= (l + 1)).astype(np.float64)
        if l == n_levels - 1:
            y = float(np.sum(g(hh_est)))
        else:
            y = 2.0 * y + float(np.sum((1.0 - 2.0 * in_next) * g(hh_est)))
    return y


def um_gsum_window(records_by_epoch_per_path, keys_per_path, g,
                   n_levels: int, level_seed: int,
                   k_heavy: int = 1024, merge: str = "subepoch") -> float:
    """Recursive UnivMon estimator over disaggregated composite sketches.

    ``records_by_epoch_per_path``: list (one entry per path-group) of
    per-epoch record lists; ``keys_per_path``: the candidate keys of each
    group.  Per-level window frequencies are estimated with the standard
    composite query (``merge`` selects the §4.3 subepoch merge or the
    fragment merge — the latter is what the device query plane computes),
    then combined with the UnivMon Y-recursion.
    """
    # Estimate per-level window frequency for every candidate key.
    all_keys, all_lvl, est_per_level = [], [], []
    for keys, recs_by_epoch in zip(keys_per_path, records_by_epoch_per_path):
        keys = np.asarray(keys, dtype=np.uint32)
        if len(keys) == 0:
            continue
        lvl = H.level_of(keys, level_seed, n_levels)
        ests = np.zeros((n_levels, len(keys)))
        for l in range(n_levels):
            m = lvl >= l
            if not m.any():
                continue
            ests[l, m] = query_window(recs_by_epoch, keys[m], "um", level=l,
                                      merge=merge)
        all_keys.append(keys)
        all_lvl.append(lvl)
        est_per_level.append(ests)
    if not all_keys:
        return 0.0
    lvl = np.concatenate(all_lvl)
    ests = np.concatenate(est_per_level, axis=1)
    return um_gsum_combine(ests, lvl, g, k_heavy=k_heavy)


def um_entropy_window(records_by_epoch_per_path, keys_per_path,
                      n_levels: int, level_seed: int, total: float,
                      k_heavy: int = 1024,
                      merge: str = "subepoch") -> float:
    """Empirical entropy in bits over the query window."""
    s = um_gsum_window(records_by_epoch_per_path, keys_per_path,
                       lambda x: x * np.log2(np.maximum(x, 1.0)),
                       n_levels, level_seed, k_heavy=k_heavy, merge=merge)
    if total <= 0:
        return 0.0
    return float(np.log2(total) - s / total)
