"""Benchmark: the sketch_update Pallas kernel vs the jnp scatter-add
reference — wall-time here is CPU interpret-mode (correctness harness);
the structural metrics (VMEM footprint, MXU work of the factored one-hot
matmul recast) are computed analytically for the TPU target (§5 of the
paper: the data plane must run at line rate).

Includes a small **geometry autotuner**: every scenario sweeps
``(blk, w_blk, value_mode)`` candidates (feasibility-filtered by the
kernel's own VMEM model) plus, for the fleet, the n_sub-grouped vs
single-launch dispatch, and the winning config is recorded next to the
headline number.  On CPU the winner reflects interpret-mode cost; on a
TPU host the same sweep re-tunes for Mosaic, which is the point.

Also the CI gate for the fleet engine: ``python -m benchmarks.kernel_bench
[--quick]`` writes ``BENCH_kernel.json`` at the repo root — schema:
``{"bench": "kernel", "schema": 2, "headline": {...}, "rows": [...]}``
with every row carrying a ``bench`` tag and a shared ``pkts_per_s``
column — and exits non-zero if (a) any correctness column
(``pallas_matches_ref``, ``fleet_matches_loop``, ``ragged_matches_dense``)
is false, or (b) the headline throughput regresses >20% against the
committed baseline file (``--no-gate`` skips (b), e.g. on a machine class
different from the one that produced the baseline).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from .common import emit

_MATCH_COLS = ("pallas_matches_ref", "fleet_matches_loop",
               "ragged_matches_dense", "query_matches_oracle",
               "resilience_ok", "durability_ok", "chaos_ok",
               "sharded_ok")
SCHEMA = 2
#: headline metrics gated against the committed baseline (>20% drop fails)
_GATED = ("ragged_pkts_per_s", "uniform_fleet_speedup_x")
_GATE_DROP = 0.20

_JSON_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_kernel.json"))


def write_bench_json(rows, headline) -> str:
    """Persist the bench trajectory where CI (and the next PR) finds it."""
    with open(_JSON_PATH, "w") as f:
        json.dump({"bench": "kernel", "schema": SCHEMA,
                   "headline": headline, "rows": rows}, f, indent=1,
                  default=str)
    return _JSON_PATH


def failing_rows(rows):
    """Rows whose correctness columns are not all true."""
    return [r for r in rows
            if not all(bool(r[k]) for k in _MATCH_COLS if k in r)]


def all_matches_ok(rows) -> bool:
    return not failing_rows(rows)


def headline_from_rows(rows, quick: bool = True) -> dict:
    """The machine-comparable summary of one bench run."""
    import jax

    h = {"backend": jax.default_backend(),
         "cpu_count": os.cpu_count(),
         "quick": quick,
         "all_matches_ok": all_matches_ok(rows)}
    for r in rows:
        if r.get("bench") == "single_kernel":
            h["single_kernel_pkts_per_s"] = max(
                h.get("single_kernel_pkts_per_s", 0), r["pkts_per_s"])
        elif r.get("bench") == "fleet_vs_loop":
            h["uniform_fleet_pkts_per_s"] = r["pkts_per_s"]
            h["uniform_fleet_speedup_x"] = r["fleet_speedup_x"]
        elif r.get("bench") == "ragged_vs_dense_skewed":
            h["ragged_pkts_per_s"] = r["pkts_per_s"]
            h["ragged_speedup_x_vs_dense"] = r["ragged_speedup_x"]
        elif r.get("bench") == "query_plane":
            # device query plane: best keys/sec across kinds + the
            # host-boundary bytes the device path avoids (not gated —
            # new metric, no committed baseline class yet)
            h["query_keys_per_s"] = max(h.get("query_keys_per_s", 0),
                                        r["pkts_per_s"])
            h["query_host_bytes_saved_x"] = max(
                h.get("query_host_bytes_saved_x", 0),
                r["host_bytes_saved_x"])
        elif r.get("bench") == "univmon_fleet":
            # UnivMon virtual-level-row engine (not gated yet — new
            # metric, no committed baseline class)
            h["um_fleet_pkts_per_s"] = r["pkts_per_s"]
            h["um_fleet_speedup_x"] = r["fleet_speedup_x"]
            h["um_query_keys_per_s"] = r["level_query_keys_per_s"]
        elif r.get("bench") == "resilience":
            # churn plane: how much the masked policy beats the
            # failure-oblivious baseline at the worst failure fraction
            # (correctness-gated via resilience_ok, not perf-gated)
            h["resilience_masked_improvement_x"] = max(
                h.get("resilience_masked_improvement_x", 0),
                r["masked_improvement_x"])
        elif r.get("bench") == "durability":
            # export plane (correctness-gated via durability_ok, not
            # perf-gated): masked durable error vs the retry-disabled
            # oblivious baseline, and worst-case crash-recovery cost
            if r.get("scenario") == "drop":
                h["durability_masked_improvement_x"] = max(
                    h.get("durability_masked_improvement_x", 0),
                    r["masked_improvement_x"])
            elif r.get("scenario") == "crash":
                h["durability_recovery_rounds"] = max(
                    h.get("durability_recovery_rounds", 0),
                    r["recovery_rounds"])
        elif r.get("bench") == "chaos":
            # composed failure planes (correctness-gated via chaos_ok,
            # not perf-gated): worst config divergence + error under
            # the lossiest control channel swept
            if r.get("scenario") == "ctrl_loss":
                h["chaos_stale_epochs"] = max(
                    h.get("chaos_stale_epochs", 0), r["n_stale_epochs"])
                h["chaos_worst_rmse"] = max(
                    h.get("chaos_worst_rmse", 0.0), r["rmse"])
        elif r.get("bench") == "fleet_sharded":
            # 245-switch fat-tree over an 8-way forced-host device mesh
            # (correctness-gated via sharded_ok, not perf-gated: the
            # forced devices share this host's cores, so scaling_x only
            # tracks plumbing overhead here, not real parallelism)
            if "pkts_per_s_8dev" in r:
                h["sharded_n_switches"] = r["n_switches"]
                h["sharded_pkts_per_s_1dev"] = r["pkts_per_s_1dev"]
                h["sharded_pkts_per_s_8dev"] = r["pkts_per_s_8dev"]
                h["sharded_scaling_x"] = r["scaling_x"]
    return h


def load_baseline(path: str = None) -> dict:
    """Headline of the committed BENCH_kernel.json (any schema vintage);
    {} if absent."""
    path = path or _JSON_PATH
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        doc = json.load(f)
    if "headline" in doc:
        return doc["headline"]
    # schema-1 (PR-2) fallback: reconstruct from rows
    h = {}
    for r in doc.get("rows", []):
        if r.get("bench") == "ragged_vs_dense_skewed":
            h["ragged_pkts_per_s"] = r.get("ragged_pkts_per_s")
        elif r.get("bench") == "fleet_vs_loop":
            h["uniform_fleet_speedup_x"] = r.get("fleet_speedup_x")
    return h


def gate_failures(headline: dict, baseline: dict) -> list:
    """Headline metrics that regressed more than _GATE_DROP vs baseline.

    Both gated metrics are workload-dependent, so nothing is gated
    across different bench modes (quick vs full; a schema-1 baseline
    records no mode and is treated as quick).  Absolute throughputs
    (``*_pkts_per_s``) are additionally only comparable on the machine
    class that produced the baseline (backend + cpu_count must match).
    Ratio metrics (``*_speedup_x``) are gated across machine classes,
    but only fail when they also fall below 1.0 — the machine-portable
    structural invariant is "the fleet does not fall behind the loop",
    not the exact ratio some other host measured.
    """
    if bool(baseline.get("quick", True)) != bool(headline.get("quick")):
        return []
    same_machine = (baseline.get("backend") == headline.get("backend")
                    and baseline.get("cpu_count") == headline.get(
                        "cpu_count"))
    fails = []
    for key in _GATED:
        old, new = baseline.get(key), headline.get(key)
        if old and not new:
            # a gated metric vanishing must not silently disable the gate
            fails.append(f"{key}: missing from the current headline "
                         f"(baseline {old})")
            continue
        if not (old and new) or new >= (1.0 - _GATE_DROP) * old:
            continue
        if key.endswith("_pkts_per_s") and not same_machine:
            continue
        if key.endswith("_speedup_x") and not same_machine and new >= 1.0:
            continue
        fails.append(f"{key}: {new} < {1 - _GATE_DROP:.0%} of "
                     f"baseline {old}")
    return fails


def _grouped_rect(params, packets, *, n_sub_max: int, width_max: int,
                  **kw) -> np.ndarray:
    """``dispatch_ragged_grouped``'s group launches laid into the
    ``(rows, n_sub_max, width_max)`` rectangle the oracles return, for
    comparison only (the engine keeps each group at its own extent)."""
    from repro.core.fleet import dispatch_ragged_grouped

    outs, layout, _, _ = dispatch_ragged_grouped(params, packets, **kw)
    flat = np.concatenate([np.asarray(o).ravel() for o in outs])
    e_count, n_rows = layout.base.shape
    rect = np.zeros((e_count * n_rows, n_sub_max, width_max), np.float32)
    for e in range(e_count):
        for r in range(n_rows):
            start, (_, n, stride) = layout.cell(e, r, r + 1)
            w = int(layout.widths[r])
            rect[e * n_rows + r, :n, :w] = \
                flat[start:start + n * stride].reshape(n, stride)[:, :w]
    return rect


def _time_call(fn, budget_s: float = 0.25, batches: int = 3) -> float:
    """Steady-state seconds/call, robust to a noisy shared machine: warm
    up (compile), then take the *fastest* of ``batches`` fixed-budget
    averaging windows (background load only ever slows a window down)."""
    fn()
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < budget_s:
            fn()
            n += 1
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def _geometry_candidates(width: int, n_sub: int, quick: bool):
    """(blk, w_blk, value_mode) sweep, feasibility-filtered by the
    kernel's VMEM model and deduped after capping w_blk at the width."""
    from repro.kernels.sketch_update.kernel import (VMEM_BUDGET_BYTES,
                                                    pow2_width_cap,
                                                    vmem_bytes)

    w_cap = pow2_width_cap(width)
    geoms = [(1024, 2048), (2048, 2048), (2048, 4096)]
    modes = ["f32", "count"]
    if not quick:
        geoms += [(1024, 4096)]
        modes.append("limb")
    seen, out = set(), []
    for blk, w_blk in geoms:
        w_blk = min(w_blk, w_cap)
        for mode in modes:
            key = (blk, w_blk, mode)
            if key in seen:
                continue
            seen.add(key)
            if vmem_bytes(blk, w_blk, n_sub, mode) <= VMEM_BUDGET_BYTES:
                out.append(key)
    return out


def run(quick: bool = True):
    import jax.numpy as jnp
    from repro.kernels.sketch_update.kernel import vmem_bytes
    from repro.kernels.sketch_update.ops import sketch_update

    rows = []
    rng = np.random.RandomState(0)
    p = 1 << (14 if quick else 16)
    keys = jnp.asarray(rng.randint(0, 1 << 20, p).astype(np.uint32))
    vals = jnp.asarray(np.ones(p, np.float32))
    ts = jnp.asarray(rng.randint(0, 1 << 16, p).astype(np.uint32))
    for width, n_sub in [(2048, 8), (16384, 8), (65536, 16)]:
        kw = dict(width=width, n_sub=n_sub, log2_te=16, col_seed=1,
                  sign_seed=2, sub_seed=3, signed=True)
        out_ref = sketch_update(keys, vals, ts, backend="ref", **kw)
        # guard off on both sides of the comparison (the candidates run
        # with check_overflow=False too)
        t_ref = _time_call(lambda: sketch_update(
            keys, vals, ts, backend="ref", check_overflow=False,
            **kw).block_until_ready())
        best = None
        for blk, w_blk, mode in _geometry_candidates(width, n_sub, quick):
            run_one = (lambda blk=blk, w_blk=w_blk, mode=mode:
                       sketch_update(keys, vals, ts, backend="pallas",
                                     interpret="auto", blk=blk,
                                     w_blk=w_blk, value_mode=mode,
                                     check_overflow=False, **kw))
            ok = bool(np.array_equal(np.asarray(out_ref),
                                     np.asarray(run_one())))
            t = _time_call(lambda: run_one().block_until_ready())
            row = {"bench": "single_kernel_tune", "width": width,
                   "n_sub": n_sub, "blk": blk, "w_blk": w_blk,
                   "value_mode": mode, "pallas_matches_ref": ok,
                   "pkts_per_s": round(p / t)}
            rows.append(row)
            if ok and (best is None or t < best[0]):
                best = (t, row)
        if best is None:
            # every candidate diverged — the tune rows carry
            # pallas_matches_ref=False and __main__ exits non-zero
            continue
        t, win = best
        rows.append({
            "bench": "single_kernel", "width": width, "n_sub": n_sub,
            "blk": win["blk"], "w_blk": win["w_blk"],
            "value_mode": win["value_mode"],
            "pallas_matches_ref": all(
                r["pallas_matches_ref"] for r in rows
                if r["bench"] == "single_kernel_tune"
                and r["width"] == width and r["n_sub"] == n_sub),
            "vmem_kb": vmem_bytes(win["blk"], win["w_blk"], n_sub,
                                  win["value_mode"]) // 1024,
            "vmem_ok_16MB": vmem_bytes(win["blk"], win["w_blk"], n_sub,
                                       win["value_mode"]) < 16 * 2 ** 20,
            # factored contraction: 2 * n_sub * padded_width MACs/packet
            # (the limb mode runs two contractions, hi and lo)
            "mxu_flops_per_pkt": (
                2 * n_sub * (width + (-width) % win["w_blk"])
                * (2 if win["value_mode"] == "limb" else 1)),
            "pkts_per_s": win["pkts_per_s"],
            "ref_pkts_per_s": round(p / t_ref),
        })
    emit("kernel_bench", [r for r in rows if r["bench"] == "single_kernel"])
    from .chaos import run as run_chaos
    from .durability import run as run_durability
    from .resilience import run as run_resilience
    from .sharded import run as run_sharded

    rows = (rows + run_fleet(quick=quick) + run_fleet_ragged(quick=quick)
            + run_query_plane(quick=quick)
            + run_univmon_fleet(quick=quick)
            + run_resilience(quick=quick)
            + run_durability(quick=quick)
            + run_chaos(quick=quick)
            + run_sharded(quick=quick))
    headline = headline_from_rows(rows, quick=quick)
    path = write_bench_json(rows, headline)
    print(f"headline: {json.dumps(headline)}")
    print(f"-> {path}")
    return rows


def _fleet_inputs(quick: bool):
    """A fleet-shaped epoch: heterogeneous widths/n_sub, uniform load.
    16 (quick) / 32 switches — a per-fragment loop's dispatch overhead is
    invisible at PR-2's 4 switches and dominant at network scale."""
    from repro.kernels.sketch_update import fleet as FK

    rng = np.random.RandomState(1)
    n_frags = 16 if quick else 32
    p = 1 << (11 if quick else 13)
    widths = ([512, 2048, 1024, 4096, 256, 2048, 512, 1024] * 4)[:n_frags]
    nsubs = ([4, 8, 2, 16, 1, 8, 4, 2] * 4)[:n_frags]
    keys = rng.randint(0, 1 << 20, (n_frags, p)).astype(np.uint32)
    vals = np.ones((n_frags, p), np.float32)
    ts = rng.randint(0, 1 << 16, (n_frags, p)).astype(np.uint32)
    params = np.zeros((n_frags, FK.N_PARAMS), np.int32)
    for f in range(n_frags):
        params[f, FK.PARAM_COL_SEED] = 101 + f
        params[f, FK.PARAM_SIGN_SEED] = 202 + f
        params[f, FK.PARAM_SUB_SEED] = 303 + f
        params[f, FK.PARAM_WIDTH] = widths[f]
        params[f, FK.PARAM_N_SUB] = nsubs[f]
        params[f, FK.PARAM_LOG2_N_SUB] = nsubs[f].bit_length() - 1
    return keys, vals, ts, params, widths, nsubs


def run_fleet(quick: bool = True):
    """Fleet engine vs per-fragment loop on a uniform-load heterogeneous
    fleet: one batched dispatch for all fragments against one
    ``sketch_update`` pallas_call per fragment.

    Wall-time is CPU interpret-mode, so the absolute packets/sec is not
    the TPU number — but the *ratio* exposes the dispatch/serialization
    overhead the fleet path removes, and the equality check proves the
    batched path is a drop-in replacement.  The loop baseline runs with
    its own auto-tuned geometry and without the overflow sync, so the
    ratio is batching vs serialization, not an artifact of the guard.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.fleet import FleetPacket, dispatch_ragged_grouped
    from repro.kernels.sketch_update import fleet as FK

    keys, vals, ts, params, widths, nsubs = _fleet_inputs(quick)
    n_frags, p = keys.shape
    kw = dict(n_sub_max=max(nsubs), width_max=max(widths), log2_te=16,
              signed=True)
    kj, vj, tj = jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ts)
    pj = jnp.asarray(params)
    pkt = FleetPacket(keys=keys.ravel(),
                      values=vals.ravel().astype(np.int64),
                      ts=ts.ravel().astype(np.int64),
                      offsets=np.arange(n_frags + 1, dtype=np.int64) * p,
                      frag_order=tuple(range(n_frags)))

    out_loop = FK.fleet_update_loop(keys, vals, ts, params,
                                    backend="pallas", interpret="auto",
                                    check_overflow=False, **kw)
    t_loop = _time_call(lambda: FK.fleet_update_loop(
        keys, vals, ts, params, backend="pallas", interpret="auto",
        check_overflow=False, **kw))

    rows, best = [], None
    for blk, w_blk, mode in [(1024, 2048, "f32"), (2048, 2048, "f32"),
                             (2048, 4096, "f32"), (2048, 2048, "count")]:
        run_one = (lambda blk=blk, w_blk=w_blk, mode=mode:
                   FK.fleet_update(kj, vj, tj, pj, blk=blk, w_blk=w_blk,
                                   value_mode=mode, interpret="auto", **kw))
        ok = bool(np.array_equal(np.asarray(run_one()), out_loop))
        t = _time_call(lambda: run_one().block_until_ready())
        rows.append({"bench": "fleet_tune", "layout": "dense", "blk": blk,
                     "w_blk": w_blk, "value_mode": mode,
                     "fleet_matches_loop": ok,
                     "pkts_per_s": round(n_frags * p / t)})
        if ok and (best is None or t < best[0]):
            best = (t, rows[-1])
    # the production path: ragged CSR grouped by n_sub
    group_kw = dict(log2_te=16, signed=True)
    for blk in (1024, 2048):
        run_one = (lambda blk=blk: dispatch_ragged_grouped(
            params, [pkt], blk=blk, value_mode="f32", interpret="auto",
            **group_kw)[0])
        ok = bool(np.array_equal(_grouped_rect(
            params, [pkt], blk=blk, value_mode="f32", interpret="auto",
            **kw), out_loop))
        t = _time_call(lambda: jax.block_until_ready(run_one()))
        rows.append({"bench": "fleet_tune", "layout": "ragged_grouped",
                     "blk": blk, "w_blk": 0, "value_mode": "f32",
                     "fleet_matches_loop": ok,
                     "pkts_per_s": round(n_frags * p / t)})
        if ok and (best is None or t < best[0]):
            best = (t, rows[-1])
    if best is None:
        return rows  # all candidates diverged; __main__ exits non-zero
    t_fleet, win = best
    total_pkts = n_frags * p
    # Cell padding of the stacked layout (n_sub_max x width_max per
    # fragment); the dead-block skips make most of it cheap in compute,
    # but it is still the layout's memory footprint.
    live = sum(w * n for w, n in zip(widths, nsubs))
    pad_work_x = n_frags * max(widths) * max(nsubs) / live
    rows.append({
        "bench": "fleet_vs_loop",
        "n_frags": n_frags,
        "pkts_per_frag": p,
        "layout": win["layout"], "blk": win["blk"], "w_blk": win["w_blk"],
        "value_mode": win["value_mode"],
        "fleet_matches_loop": all(r["fleet_matches_loop"] for r in rows),
        "pkts_per_s": win["pkts_per_s"],
        "loop_pkts_per_s": round(total_pkts / t_loop),
        "fleet_speedup_x": round(t_loop / t_fleet, 2),
        "pad_work_x": round(pad_work_x, 2),
        "device_dispatches_fleet": (len(set(nsubs))
                                    if win["layout"] == "ragged_grouped"
                                    else 1),
        "device_dispatches_loop": n_frags,
    })
    emit("kernel_bench_fleet",
         [r for r in rows if r["bench"] == "fleet_vs_loop"])
    return rows


def run_fleet_ragged(quick: bool = True):
    """Ragged CSR layout vs the PR-1 dense rectangle on a *skewed*
    heterogeneous fleet — the dense layout's worst case.

    One hot fragment dominates the epoch; the dense rectangle pads every
    fragment to pow2(hottest segment) while the CSR stream pads each
    segment to one ``blk`` boundary.  The sweep covers single-launch vs
    n_sub-grouped dispatch (``repro.core.fleet.dispatch_ragged_grouped``,
    the production default: grouping removes the subepoch-row padding a
    single launch pays toward ``n_sub_max``) and the packing block size.
    ``ragged_matches_dense`` / ``fleet_matches_loop`` pin bit-identity of
    all paths on heterogeneous widths/n_sub.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.fleet import (FleetPacket, dispatch_ragged_grouped,
                                  pack_csr)
    from repro.kernels.sketch_update import fleet as FK

    rng = np.random.RandomState(2)
    hot = 1 << (13 if quick else 15)
    lens = [hot, 128, 64, 256, 32, 512, 128, 64]
    widths = [2048, 256, 512, 1024, 128, 2048, 256, 512]
    nsubs = [8, 2, 4, 16, 1, 8, 2, 4]
    n_frags = len(lens)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    p_live = int(offsets[-1])
    pkt = FleetPacket(
        keys=rng.randint(0, 1 << 20, p_live).astype(np.uint32),
        values=np.ones(p_live, np.int64),
        ts=rng.randint(0, 1 << 16, p_live).astype(np.int64),
        offsets=offsets, frag_order=tuple(range(n_frags)))
    params = np.zeros((n_frags, FK.N_PARAMS), np.int32)
    for f in range(n_frags):
        params[f, FK.PARAM_COL_SEED] = 101 + f
        params[f, FK.PARAM_SIGN_SEED] = 202 + f
        params[f, FK.PARAM_SUB_SEED] = 303 + f
        params[f, FK.PARAM_WIDTH] = widths[f]
        params[f, FK.PARAM_N_SUB] = nsubs[f]
        params[f, FK.PARAM_LOG2_N_SUB] = nsubs[f].bit_length() - 1
    kw = dict(n_sub_max=max(nsubs), width_max=max(widths), log2_te=16,
              signed=True)

    dense_blk = 1024
    dkeys, dvals, dts = pkt.densify(dense_blk)
    args_d = (jnp.asarray(dkeys), jnp.asarray(dvals), jnp.asarray(dts),
              jnp.asarray(params))
    out_dense = np.asarray(FK.fleet_update(
        *args_d, blk=dense_blk, w_blk=2048, interpret="auto", **kw))
    t_dense = _time_call(lambda: FK.fleet_update(
        *args_d, blk=dense_blk, w_blk=2048,
        interpret="auto", **kw).block_until_ready())
    out_loop = FK.fleet_update_loop(dkeys, dvals, dts, params,
                                    backend="ref", **kw)

    rows, best = [], None
    for grouped in (False, True):
        for blk in ((1024, 2048) if grouped else (1024,)):
            if grouped:
                run_one = (lambda blk=blk: dispatch_ragged_grouped(
                    params, [pkt], blk=blk, interpret="auto",
                    value_mode="f32", log2_te=16, signed=True)[0])
                got = _grouped_rect(params, [pkt], blk=blk,
                                    interpret="auto", value_mode="f32", **kw)
            else:
                fk, fv, ft, bf = pack_csr([pkt], blk)
                args = (jnp.asarray(fk), jnp.asarray(fv), jnp.asarray(ft),
                        jnp.asarray(params), jnp.asarray(bf))
                run_one = (lambda args=args, blk=blk:
                           FK.fleet_update_ragged(*args, blk=blk,
                                                  value_mode="f32",
                                                  interpret="auto", **kw))
                got = np.asarray(run_one())
            ok = bool(np.array_equal(got, out_dense))
            t = _time_call(lambda: jax.block_until_ready(run_one()))
            rows.append({"bench": "ragged_tune", "grouped": grouped,
                         "blk": blk, "ragged_matches_dense": ok,
                         "pkts_per_s": round(p_live / t)})
            if ok and (best is None or t < best[0]):
                best = (t, rows[-1])
    if best is None:
        return rows  # all candidates diverged; __main__ exits non-zero
    t_ragged, win = best
    pad_blk = win["blk"]
    fk = pack_csr([pkt], pad_blk)[0]
    rows.append({
        "bench": "ragged_vs_dense_skewed",
        "n_frags": n_frags,
        "live_pkts": p_live,
        "hot_seg": hot,
        "grouped": win["grouped"], "blk": pad_blk,
        "ragged_matches_dense": all(r["ragged_matches_dense"]
                                    for r in rows),
        "fleet_matches_loop": bool(np.array_equal(out_dense, out_loop)),
        "pad_work_x_dense": round(dkeys.size / p_live, 2),
        "pad_work_x_ragged": round(fk.size / p_live, 3),
        "pkts_per_s": round(p_live / t_ragged),
        "dense_pkts_per_s": round(p_live / t_dense),
        "ragged_speedup_x": round(t_dense / t_ragged, 2),
    })
    emit("kernel_bench_ragged",
         [r for r in rows if r["bench"] == "ragged_vs_dense_skewed"])
    return rows


def run_query_plane(quick: bool = True):
    """Device-resident query plane vs the host-transfer oracle on an
    epoch-window stack (the §4.3 batched gather/merge engine,
    ``repro.kernels.sketch_query``).

    Measures keys/sec through the jitted device engine (the key-batch
    size is the autotuned knob — buckets are compiled shapes, so the
    sweep finds the batch that amortizes dispatch best) against the
    numpy oracle on pre-transferred host stacks, and records the *host
    boundary bytes* each path moves per query: the device path ships
    the key batch down and the (K,) float64 estimates back; the
    host path must first move (and widen to int64) the entire
    ``(E, F, n_sub_max, width_max)`` counter stack.  ``pkts_per_s``
    carries keys/sec here (the shared throughput column).
    """
    import jax.numpy as jnp
    from repro.core import query as Q
    from repro.kernels.sketch_query import fleet_window_query_device
    from repro.kernels.sketch_update import fleet as FK

    rng = np.random.RandomState(4)
    e_count = 4
    n_frags = 16 if quick else 32
    n_sub_max, width_max = 16, 2048
    widths = ([512, 2048, 1024, 2048, 256, 2048, 512, 1024] * 4)[:n_frags]
    nsubs = ([4, 8, 2, 16, 1, 8, 4, 2] * 4)[:n_frags]
    stack = np.zeros((e_count, n_frags, n_sub_max, width_max), np.float32)
    params = np.zeros((e_count, n_frags, FK.N_PARAMS), np.int32)
    for e in range(e_count):
        for f in range(n_frags):
            # integer counters, exact zeros outside the live block (the
            # fleet-kernel stacked-layout contract)
            stack[e, f, :nsubs[f], :widths[f]] = rng.randint(
                -500, 500, (nsubs[f], widths[f]))
            params[e, f, FK.PARAM_COL_SEED] = 101 + 37 * e + f
            params[e, f, FK.PARAM_SIGN_SEED] = 202 + 37 * e + f
            params[e, f, FK.PARAM_SUB_SEED] = 303 + 37 * e + f
            params[e, f, FK.PARAM_WIDTH] = widths[f]
            params[e, f, FK.PARAM_N_SUB] = nsubs[f]
            params[e, f, FK.PARAM_LOG2_N_SUB] = nsubs[f].bit_length() - 1
    stack_dev = jnp.asarray(stack)
    host_stacks = [stack[e].astype(np.int64) for e in range(e_count)]
    host_params = [params[e] for e in range(e_count)]
    widths_arr = np.asarray(widths, np.int64)
    frag_sel = np.zeros(n_frags, bool)
    frag_sel[::3] = True                  # a §4.3 path restriction

    rows, winners = [], {}
    k_sweep = (256, 1024, 4096) if quick else (256, 1024, 4096, 16384)
    for kind in ("cms", "cs"):
        st_dev = jnp.abs(stack_dev) if kind == "cms" else stack_dev
        hs = [np.abs(h) for h in host_stacks] if kind == "cms" \
            else host_stacks
        best = None
        for n_keys in k_sweep:
            keys = rng.randint(0, 1 << 20, n_keys).astype(np.uint32)
            ok = all(
                np.allclose(
                    fleet_window_query_device(st_dev, host_params, keys,
                                              kind, frag_sel=sel),
                    Q.fleet_query_window(hs, host_params, widths_arr,
                                         keys, kind, frag_sel=sel),
                    rtol=1e-6)
                for sel in (None, frag_sel))
            t_dev = _time_call(lambda: fleet_window_query_device(
                st_dev, host_params, keys, kind))
            t_host = _time_call(lambda: Q.fleet_query_window(
                hs, host_params, widths_arr, keys, kind))
            row = {"bench": "query_tune", "kind": kind, "n_keys": n_keys,
                   "query_matches_oracle": bool(ok),
                   "pkts_per_s": round(n_keys / t_dev),
                   "host_keys_per_s": round(n_keys / t_host)}
            rows.append(row)
            if ok and (best is None
                       or row["pkts_per_s"] > best["pkts_per_s"]):
                best = row
        if best is not None:
            winners[kind] = best
    for kind, win in winners.items():
        n_keys = win["n_keys"]
        dev_bytes = n_keys * 4 + n_keys * 8      # keys down, f64 out back
        stack_bytes = stack.nbytes               # f32 across the boundary
        rows.append({
            "bench": "query_plane", "kind": kind,
            "e_count": e_count, "n_frags": n_frags,
            "n_sub_max": n_sub_max, "width_max": width_max,
            "n_keys": n_keys,
            "query_matches_oracle": all(
                r["query_matches_oracle"] for r in rows
                if r["bench"] == "query_tune" and r["kind"] == kind),
            "pkts_per_s": win["pkts_per_s"],
            "host_keys_per_s": win["host_keys_per_s"],
            "host_bytes_per_query_device": dev_bytes,
            "host_bytes_window_transfer": stack_bytes,
            "host_bytes_saved_x": round(stack_bytes / dev_bytes, 1),
        })
    emit("kernel_bench_query",
         [r for r in rows if r["bench"] == "query_plane"])
    return rows


def run_univmon_fleet(quick: bool = True):
    """UnivMon on the fleet: virtual level rows in one batched dispatch
    vs one ``sketch_update`` per (fragment, level), plus the device
    all-levels window query vs the per-level host oracle.

    Update side: F heterogeneous um fragments x L levels are F*L param
    rows driven by ONE CSR stream (packed once per fragment — the level
    grid axis fans packet blocks out in-kernel), against a loop that
    dispatches F*L single-row kernels.  ``pkts_per_s`` counts *stream*
    packets (each implicitly updating all L level rows), so the fleet
    and loop numbers share a denominator.  Query side: keys/sec through
    ``um_window_query_device`` (all L levels in one call) vs L per-level
    passes of the numpy oracle.
    """
    import jax
    from repro.core.disketch import DiSketchSystem, SwitchStream
    from repro.core.fleet import (build_params, dispatch_ragged_grouped,
                                  fold_packet_flags, pack_streams)
    from repro.core import query as Q
    from repro.kernels.sketch_query import um_window_query_device
    from repro.kernels.sketch_update import fleet as FK

    rng = np.random.RandomState(5)
    n_frags = 8 if quick else 16
    n_levels = 8
    p = 1 << (11 if quick else 13)
    log2_te = 16
    mems = {f: w * 4 * n_levels
            for f, w in enumerate(([512, 2048, 1024, 4096, 256, 2048,
                                    512, 1024] * 2)[:n_frags])}
    streams = {f: SwitchStream(
        rng.randint(0, 1 << 20, p).astype(np.uint32),
        np.ones(p, np.int64),
        rng.randint(0, 1 << log2_te, p).astype(np.int64))
        for f in range(n_frags)}

    def make(backend):
        return DiSketchSystem(mems, "um", rho_target=1e9, log2_te=log2_te,
                              n_levels=n_levels, backend=backend)

    fleet = make("fleet")
    packet = pack_streams(streams, fleet.fleet.frag_order)
    fleet.run_epoch(0, streams, packet=packet)

    # loop baseline: the same F*L single-row updates through the
    # per-row kernel loop (pallas backend, auto geometry, no guard sync)
    folded = fold_packet_flags(packet, log2_te, n_levels=n_levels,
                               level_seed=fleet.fleet.level_seed)
    params = build_params(fleet.fragments, 0, {f: 1 for f in mems},
                          fleet.fleet.frag_order)
    dense_keys = folded.keys.reshape(n_frags, p)
    dense_vals = np.ones((n_frags, p), np.float32)
    dense_ts = np.asarray(folded.ts).reshape(n_frags, p)
    kw = dict(n_sub_max=1, width_max=int(fleet.fleet.widths.max()),
              log2_te=log2_te, signed=True)
    out_loop = FK.fleet_update_loop(dense_keys, dense_vals, dense_ts,
                                    params, backend="pallas",
                                    interpret="auto", check_overflow=False,
                                    **kw)
    ok_update = True
    for i, sw in enumerate(fleet.fleet.frag_order):
        w = fleet.fragments[sw].width
        rec = np.asarray(fleet.records[0][sw].counters)       # (L, 1, w)
        for lev in range(n_levels):
            ok_update &= np.array_equal(
                out_loop[i * n_levels + lev, :1, :w], rec[lev])

    # kernel-vs-kernel, like the other *_speedup_x rows: the grouped
    # ragged engine dispatch against the per-(fragment, level) kernel
    # loop, both on the pre-folded packet, neither paying host-side
    # record unpacking or the overflow sync.
    dispatch_kw = dict(n_levels=n_levels, value_mode="f32",
                       interpret="auto", log2_te=log2_te, signed=True)
    t_fleet = _time_call(lambda: jax.block_until_ready(
        dispatch_ragged_grouped(params, [folded], **dispatch_kw)[0]))
    t_loop = _time_call(lambda: FK.fleet_update_loop(
        dense_keys, dense_vals, dense_ts, params, backend="pallas",
        interpret="auto", check_overflow=False, **kw))

    # query side: 4-epoch window, all-levels device engine vs the
    # per-level host oracle on the same (transferred-once) stacks
    sysw = make("fleet")
    sysw.run_window(0, [streams] * 4, packets=[packet] * 4)
    epochs = [0, 1, 2, 3]
    params_w = [sysw.fleet._params_log[e] for e in epochs]
    buf = sysw.fleet._window_bufs[0][0]
    stack4 = buf.view(epochs)               # the device buffer's cells
    host = [buf.host_epoch(e) for e in epochs]
    rows, best = [], None
    for n_keys in ((1024, 4096) if quick else (1024, 4096, 16384)):
        keys = rng.randint(0, 1 << 20, n_keys).astype(np.uint32)
        got = um_window_query_device(stack4, params_w, keys, n_levels)
        ref = np.stack([Q.fleet_query_window(
            host, params_w, sysw.fleet.row_widths, keys, "um",
            frag_sel=sysw.fleet._row_sel(None, level))
            for level in range(n_levels)])
        ok = bool(np.allclose(got, ref, rtol=1e-6))
        t_dev = _time_call(lambda: um_window_query_device(
            stack4, params_w, keys, n_levels))
        t_host = _time_call(lambda: [Q.fleet_query_window(
            host, params_w, sysw.fleet.row_widths, keys, "um",
            frag_sel=sysw.fleet._row_sel(None, level))
            for level in range(n_levels)])
        # pkts_per_s carries keys/sec here — the schema-2 shared
        # throughput column, same convention as the query_tune rows
        row = {"bench": "um_query_tune", "n_keys": n_keys,
               "query_matches_oracle": ok,
               "pkts_per_s": round(n_keys / t_dev),
               "host_keys_per_s": round(n_keys / t_host)}
        rows.append(row)
        if ok and (best is None or row["pkts_per_s"] > best["pkts_per_s"]):
            best = row

    rows.append({
        "bench": "univmon_fleet",
        "n_frags": n_frags, "n_levels": n_levels, "pkts_per_frag": p,
        "fleet_matches_loop": bool(ok_update),
        "query_matches_oracle": all(
            r["query_matches_oracle"] for r in rows
            if r["bench"] == "um_query_tune"),
        "pkts_per_s": round(n_frags * p / t_fleet),
        "loop_pkts_per_s": round(n_frags * p / t_loop),
        "fleet_speedup_x": round(t_loop / t_fleet, 2),
        "level_query_keys_per_s": 0 if best is None else best["pkts_per_s"],
        "level_query_host_keys_per_s": (0 if best is None
                                        else best["host_keys_per_s"]),
        "device_dispatches_loop": n_frags * n_levels,
    })
    emit("kernel_bench_univmon",
         [r for r in rows if r["bench"] == "univmon_fleet"])
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    quick = "--quick" in sys.argv
    gate = "--no-gate" not in sys.argv
    baseline = load_baseline()
    rows = run(quick=quick)
    bad = failing_rows(rows)
    if bad:
        bad = [{k: r[k] for k in ("bench", *_MATCH_COLS) if k in r}
               for r in bad]
        print(f"FAIL: kernel/fleet outputs diverged: {bad}", file=sys.stderr)
        sys.exit(1)
    if gate:
        fails = gate_failures(headline_from_rows(rows, quick=quick),
                              baseline)
        if fails:
            print(f"FAIL: perf regression vs committed baseline: {fails}",
                  file=sys.stderr)
            sys.exit(1)
