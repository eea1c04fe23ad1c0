#!/usr/bin/env python3
"""Smoke test of the sketch system's served path on a TPU.

    python chip_smoke.py             # one chip: the §6.1 scenario
    python chip_smoke.py --chips 4   # four chips: sharded fleet only

One chip drives ``DiSketchSystem(backend="fleet")`` through its public
entry points at the paper's §6.1 deployment size (FatTree(4), 200K
flows, ~2M packets over 32 epochs, Fig. 12 memories) for cs (with §4.4
mitigation), cms and UnivMon (16 levels):

  * kernels: the ragged update lowers to a Mosaic ``tpu_custom_call``
    and every value mode matches the jnp scatter oracle on the chip;
  * update exactness: a per-epoch fleet replay reproduces the loop
    backend's subepoch counts and counters bit for bit;
  * served path: ``Replayer.run(window=8)`` then
    ``query_flows(merge="fragment")`` answered on device with no window
    stack crossing to the host, within 1e-6 of the host fragment-merge
    query over the same system's records (and ``query_entropy`` for um).

``--chips 4`` instead replays FatTree(14) (245 switches) sharded over a
4-chip ``switch`` mesh against the single-device fleet in the same
process, with shard-local XOR parity and one mid-window switch death,
and requires bit-identical counters, recovery and estimates.

Lines before the last are set-up information; the timings in them are
not benchmark numbers.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check raises, and the script exits non-zero without that line;
it refuses to run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.common import (fat_tree_scenario, full_path_queries,  # noqa: E402
                               memories_for)
from repro.compile_cache import enable_compile_cache  # noqa: E402

WINDOW = 8
MEM_KB = 128        # Fig. 12 memory point (benchmarks/freq_estimation.py)
UM_MEM_KB = 512     # entropy grid point (benchmarks/entropy.py)
UM_LEVELS = 16
REL_TOL = 1e-6
#: The device G-sum accumulates in f32 against the host's f64
#: (``kernels.sketch_query.um_gsum_device``).
ENTROPY_REL_TOL = 1e-4
#: kind -> DiSketchSystem options of the one-chip phases.
KINDS = {"cs": dict(mitigation=True), "cms": {},
         "um": dict(n_levels=UM_LEVELS)}


def log(*parts) -> None:
    print(*parts, flush=True)


def require(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compile: {self.programs} programs, backend "
                f"{self.secs:.1f} s, persistent cache hits {self.hits}, "
                f"misses {self.misses}")


def check_kernels(seed: int) -> None:
    """The ragged update compiled for the chip, in every value mode, with
    UnivMon level rows and §4.4 mitigation, against the scatter oracle."""
    import jax.numpy as jnp

    from repro.core.fleet import CSR_BLK, FleetPacket, pack_csr
    from repro.kernels.sketch_update import fleet as FK
    from repro.kernels.sketch_update.kernel import (LVL_SHIFT, SH_SHIFT,
                                                    lane_tiles,
                                                    resolve_interpret)

    require(resolve_interpret("auto") is False,
            'interpret="auto" did not resolve to compiled mode')
    rng = np.random.RandomState(seed)
    lens = np.array([3000, 5, 0, 1500, 2600])
    widths = [5000, 300, 128, 4096, 20000]
    nsubs = [4, 1, 2, 8, 16]
    n_levels = 4
    p = int(lens.sum())
    ts = (rng.randint(0, 1 << 16, p)
          | (rng.randint(0, 32, p) << LVL_SHIFT)
          | (rng.randint(0, 2, p) << SH_SHIFT))
    pkt = FleetPacket(keys=rng.randint(0, 1 << 20, p).astype(np.uint32),
                      values=rng.randint(1, 200, p).astype(np.int64),
                      ts=ts.astype(np.int64),
                      offsets=np.concatenate([[0], np.cumsum(lens)]),
                      frag_order=tuple(range(len(lens))))
    params = np.zeros((len(lens) * n_levels, FK.N_PARAMS), np.int32)
    for r in range(len(params)):
        f = r // n_levels
        params[r] = (101 + r, 202 + r, 303 + f, widths[f], nsubs[f],
                     nsubs[f].bit_length() - 1, r % n_levels, f % 2)
    keys, vals, ts, bf = pack_csr([pkt], CSR_BLK)
    kw = dict(n_sub_max=16, width_max=max(widths), log2_te=16,
              signed=True, n_levels=n_levels, with_mitigation=True)
    hlo = FK._fleet_update_ragged_jit.lower(
        lane_tiles(keys, jnp.uint32), lane_tiles(vals, jnp.float32),
        lane_tiles(ts, jnp.uint32), jnp.asarray(params),
        jnp.asarray(bf), padded_width=20480, blk=CSR_BLK, w_blk=4096,
        value_mode="count", interpret=False, **kw).as_text()
    require("tpu_custom_call" in hlo, "no tpu_custom_call in the HLO")
    log("kernels: lowered ragged update contains tpu_custom_call: True")
    dkeys, dvals, dts = pkt.densify(CSR_BLK)
    ref = FK.fleet_update_loop(dkeys, dvals, dts, params, backend="ref",
                               **{k: v for k, v in kw.items()
                                  if k not in ("n_levels",
                                               "with_mitigation")})
    for mode in ("count", "limb", "f32"):
        out = np.asarray(FK.fleet_update_ragged(
            keys, vals, ts, params, bf, blk=CSR_BLK, value_mode=mode,
            **kw))
        require(np.array_equal(out, ref), f"value mode {mode} != oracle")
    log("kernels: count/limb/f32 ragged updates (4 UnivMon level rows, "
        "mitigation) bit-identical to the scatter oracle: True")


def build(mems, kind, rho, wl, backend, mesh=None, **kw):
    from repro.core.disketch import DiSketchSystem

    return DiSketchSystem(mems, kind, rho_target=rho, log2_te=wl.log2_te,
                          backend=backend, mesh=mesh, **kw)


def check_exact(kind, mems, rho, wl, rep) -> None:
    """Per-epoch fleet replay == loop backend, ns and counters."""
    opts = KINDS[kind]
    loop = build(mems, kind, rho, wl, "loop", **opts)
    rep.run(loop)
    fleet = build(mems, kind, rho, wl, "fleet", **opts)
    t0 = time.perf_counter()
    rep.run(fleet)
    t_fleet = time.perf_counter() - t0
    require(fleet.n_log == loop.n_log, f"{kind}: ns trajectory differs")
    same = all(np.array_equal(loop.records[e][sw].counters,
                              fleet.records[e][sw].counters)
               for e in range(wl.n_epochs) for sw in mems)
    require(same, f"{kind}: fleet counters differ from the loop backend")
    n_max = max(max(n.values()) for n in fleet.n_log)
    log(f"exact[{kind}{'+mitigation' if opts.get('mitigation') else ''}]:"
        f" per-epoch fleet == loop over {wl.n_epochs} epochs x "
        f"{len(mems)} switches (ns and counters, n_max {n_max}): True "
        f"[fleet replay {t_fleet:.1f} s]")


def check_served(kind, mems, rho, wl, rep) -> None:
    """Window replay, then device fragment-merge queries vs the host
    fragment-merge query over the same system's materialized records."""
    from repro.core.sketches import true_entropy

    system = build(mems, kind, rho, wl, "fleet", **KINDS[kind])
    t0 = time.perf_counter()
    rep.run(system, window=WINDOW)
    t_run = time.perf_counter() - t0
    fleet = system.fleet
    epochs = list(range(wl.n_epochs))
    bufs = {id(fleet._window_bufs[e][0]): fleet._window_bufs[e][0]
            for e in epochs}
    stack_bytes = sum(int(np.prod(b._shape)) * 4 for b in bufs.values())
    _, keys, _, paths = full_path_queries(wl)
    t0 = time.perf_counter()
    est_dev = system.query_flows(keys, paths, epochs, merge="fragment")
    t_query = time.perf_counter() - t0
    if kind == "um":
        # Top-k over every key: a binding cutoff may break ties among
        # equal estimates differently on the two planes.
        ent_kw = dict(n_levels=UM_LEVELS, merge="fragment",
                      k_heavy=len(wl.keys))
        total = float(wl.sizes.sum())
        ent_dev = system.query_entropy(wl.keys, wl.paths, epochs, total,
                                       **ent_kw)
    untouched = all(fleet._window_bufs[e][0]._host is None for e in epochs)
    require(untouched, f"{kind}: a window stack crossed to the host")
    for e in epochs:                        # materialize every record
        dict(system.records[e])
    require(not fleet.has_device_window(epochs), "records not materialized")
    est_host = system.query_flows(keys, paths, epochs, merge="fragment")
    rel = (np.abs(est_dev - est_host)
           / np.maximum(np.abs(est_host), 1.0)).max()
    require(np.isfinite(est_dev).all() and rel <= REL_TOL,
            f"{kind}: device query max rel err {rel:.3g} > {REL_TOL}")
    log(f"served[{kind}]: Replayer.run(window={WINDOW}) {t_run:.1f} s, "
        f"{len(bufs)} resident window stacks, {stack_bytes} bytes; device "
        f"query_flows(merge=fragment) over {len(keys)} full-path flows "
        f"{t_query:.1f} s; no stack transferred: True; max rel err vs "
        f"host records {rel:.3g}")
    if kind == "um":
        ent_host = system.query_entropy(wl.keys, wl.paths, epochs, total,
                                        **ent_kw)
        rel_h = abs(ent_dev - ent_host) / abs(ent_host)
        require(np.isfinite(ent_dev) and rel_h <= ENTROPY_REL_TOL,
                f"um: device entropy {ent_dev} vs host {ent_host}")
        log(f"served[um]: query_entropy(merge=fragment) device "
            f"{ent_dev:.6f} bits, host {ent_host:.6f}, true "
            f"{true_entropy(wl.sizes):.6f}; rel diff {rel_h:.3g}")


def one_chip(seed: int, stats: CompileStats) -> None:
    from repro.core.disketch import calibrate_rho_target
    from repro.core.fragment import FragmentConfig

    check_kernels(seed)
    t0 = time.perf_counter()
    topo, wl, rep, rng = fat_tree_scenario(False, het=0.4, seed=seed)
    log(f"scenario: FatTree(4), {topo.n_switches} switches, "
        f"{len(wl.keys)} flows, {len(wl.pkt_flow)} packets, "
        f"{int(wl.path_len[wl.pkt_flow].sum())} switch observations, "
        f"{wl.n_epochs} epochs [{time.perf_counter() - t0:.1f} s]")
    for kind, opts in KINDS.items():
        mem_kb = UM_MEM_KB if kind == "um" else MEM_KB
        mems = memories_for(topo, mem_kb * 1024, 0.4, rng)
        widths = [FragmentConfig(0, kind, m, n_levels=UM_LEVELS).width
                  for m in mems.values()]
        rho = calibrate_rho_target(mems, kind,
                                   rep.epoch_stream(wl.n_epochs // 2),
                                   wl.log2_te, **opts)
        log(f"config[{kind}]: {mem_kb} KiB/switch (het 0.4), widths "
            f"{min(widths)}..{max(widths)}, rho_target {rho:.4g}")
        check_exact(kind, mems, rho, wl, rep)
        check_served(kind, mems, rho, wl, rep)
        log(f"after {kind}: {stats.line()}")


def four_chips(seed: int, stats: CompileStats) -> None:
    """Sharded fleet over make_switch_mesh(4) vs the single-device fleet:
    counters, XOR-parity recovery and fragment-merge estimates."""
    import jax

    from repro.core.disketch import calibrate_rho_target
    from repro.core.fleet import parity_groups_chunked
    from repro.launch.mesh import make_switch_mesh
    from repro.net.simulator import FailureSchedule, Replayer
    from repro.net.topology import FatTree
    from repro.net.traffic import gen_workload

    require(len(jax.devices()) >= 4, "--chips 4 needs four devices")
    t0 = time.perf_counter()
    topo = FatTree(14)
    wl = gen_workload(topo, n_flows=200_000, total_packets=2_000_000,
                      n_epochs=32, burstiness=0.2, seed=seed)
    rep = Replayer(wl, topo.n_switches)
    mems = memories_for(topo, 2 * 1024, 0.5, np.random.RandomState(5))
    log(f"scenario: FatTree(14), {topo.n_switches} switches, "
        f"{len(wl.keys)} flows, {len(wl.pkt_flow)} packets, "
        f"{wl.n_epochs} epochs [{time.perf_counter() - t0:.1f} s]")
    mesh = make_switch_mesh(4)
    per_shard = -(-topo.n_switches // 4)
    groups = parity_groups_chunked(sorted(mems), per_shard)
    victim, down_at = 100, 3                # dies mid-way through window 0
    _, keys, _, paths = full_path_queries(wl)
    keys, paths = keys[:2048], paths[:2048]
    epochs = list(range(wl.n_epochs))
    for kind in ("cs", "cms"):
        # rho_target is calibrated as in the one-chip phases (median
        # probe PEB); the check below requires that the windows still
        # spread n over more than one n_sub group.
        rho = calibrate_rho_target(mems, kind,
                                   rep.epoch_stream(wl.n_epochs // 2),
                                   wl.log2_te)
        log(f"config[{kind}]: 2 KiB/switch (het 0.5), rho_target {rho:.4g}")
        runs = {}
        for name, m in (("1dev", None), ("4dev", mesh)):
            system = build(mems, kind, rho, wl, "fleet", mesh=m,
                           fleet_kwargs={"parity_groups": groups})
            devices = []
            if m is not None:
                dispatch = system.fleet._dispatch

                def recorded(*a, _d=dispatch, _log=devices, **k):
                    out = _d(*a, **k)
                    _log.append(sorted(str(d) for d in out.devices()))
                    return out

                system.fleet._dispatch = recorded
            sched = FailureSchedule(topo.n_switches,
                                    downs={victim: (down_at, None)})
            t0 = time.perf_counter()
            rep.run(system, window=WINDOW, failures=sched)
            t_run = time.perf_counter() - t0
            if m is not None:
                buf = system.fleet._window_bufs[0][0].device()
                held = sorted((s.index[1].start or 0, str(s.device))
                              for s in buf.addressable_shards)
                log(f"mesh[{kind}]: window-stack shards (first row, "
                    f"device): {held}")
                log(f"mesh[{kind}]: devices that ran the shard kernels of "
                    f"window 0: {devices[:4]}")
            recovered = system.fleet.recover()
            est = system.query_flows(keys, paths, epochs,
                                     merge="fragment", failures="recover")
            stacks = [system.fleet._host_stack(e) for e in epochs]
            runs[name] = (system.n_log, recovered, est, stacks)
            log(f"mesh[{kind}/{name}]: Replayer.run(window={WINDOW}) "
                f"{t_run:.1f} s, recovered cells "
                f"{sum(len(v) for v in recovered.values())}; "
                f"{stats.line()}")
        (n1, rec1, est1, st1), (n4, rec4, est4, st4) = runs["1dev"], \
            runs["4dev"]
        require(rec1 and rec1 == rec4, f"{kind}: recovery differs: "
                f"{rec1} vs {rec4}")
        require(n1 == n4, f"{kind}: ns trajectory differs")
        n_groups = [sorted(set(n.values())) for n in n1[::WINDOW]]
        require(max(map(len, n_groups)) > 1,
                f"{kind}: every window ran one n_sub group {n_groups}")
        require(all(np.array_equal(a, b) for a, b in zip(st1, st4)),
                f"{kind}: sharded counters differ")
        require(np.array_equal(est1, est4), f"{kind}: estimates differ")
        log(f"mesh[{kind}]: 4-chip sharded == single-device: counters, "
            f"parity recovery of switch {victim} (down at epoch "
            f"{down_at}, mid-window) epochs {sorted(rec1)}, "
            f"{len(keys)} merge=fragment estimates, n_sub groups per "
            f"window {n_groups}: True")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fleet check on 4 chips")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 1
    stats = CompileStats()
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile "
        f"cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, stats)
    else:
        one_chip(args.seed, stats)
    log(f"{stats.line()}; total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
