"""DiSketch system orchestration: fragments + control loop + query plane.

Ties together the per-node fragments (fragment.py), the error-equalization
control loop (equalize.py), and the central query engine (query.py) into the
system of Fig. 7: per-switch single-row fragments, subepoch records streamed
to a controller, composite queries over query windows.

``DiscoSystem`` is the DISCO baseline [17]: identical per-row disaggregation
but no subepoching (n = 1 always) and no error equalization.
``AggregatedSystem`` is the traditional baseline: a full (depth x width)
sketch on each core switch.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from . import equalize, query, sketches
from .fragment import EpochRecords, FragmentConfig, process_epoch


def _g_entropy(x):
    """Entropy G-function, jnp-traceable (module-level so the device
    G-sum jit cache keys on a stable callable)."""
    import jax.numpy as jnp

    return x * jnp.log2(jnp.maximum(x, 1.0))


@dataclass
class SwitchStream:
    """Packets traversing one switch during one epoch."""
    keys: np.ndarray         # uint32 flow ids
    values: np.ndarray       # int64 increments (1 per packet for counts)
    ts: np.ndarray           # int64 timestamps
    single_hop: Optional[np.ndarray] = None  # bool, §4.4


class DiSketchSystem:
    """The paper's system: spatiotemporally disaggregated sketching.

    ``backend`` selects the epoch execution engine:
      * ``"loop"`` (default) — per-switch numpy fragments, one
        ``process_epoch`` per switch;
      * ``"fleet"`` — one batched Pallas dispatch updates all fragments
        (``core.fleet.FleetEpochRunner``, ragged CSR layout) with
        bit-identical counters for every kind — cs, cms, and UnivMon
        (levels as virtual fragment rows), with or without §4.4
        mitigation.  ``fleet_kwargs`` are forwarded to the runner (blk,
        w_blk, interpret, keep_stacked, layout).

    ``mesh`` (fleet backend only) shards the fragment fleet over the
    ``"switch"`` axis of a 1-D device mesh
    (``launch.mesh.make_switch_mesh``): updates dispatch shard-locally,
    window stacks live row-sharded across devices, and queries
    all_gather only the gathered counter slices — bit-identical to the
    single-device fleet (docs/sharding.md).

    The fleet backend additionally supports *window mode*
    (``run_window`` / ``Replayer.run(system, window=E)``): E consecutive
    epochs in one super-dispatch with the subepoch counts frozen per
    window — a throughput/control-latency trade the paper's §4.2
    tolerates ("within a factor of two"); per-epoch control stays the
    default.
    """

    name = "disketch"
    subepoching = True

    def __init__(self, switch_memories: Dict[int, int], kind: str,
                 rho_target: float, log2_te: int, counter_bytes: int = 4,
                 mitigation: bool = False, n_levels: int = 16, seed: int = 0,
                 backend: str = "loop",
                 fleet_kwargs: Optional[Dict] = None,
                 mesh=None):
        with obs.span("system.init", fragments=len(switch_memories)):
            self.kind = kind
            self.rho_target = rho_target
            self.log2_te = log2_te
            self.fragments: Dict[int, FragmentConfig] = {
                sw: FragmentConfig(frag_id=sw, kind=kind, memory_bytes=mem,
                                   counter_bytes=counter_bytes,
                                   mitigation=mitigation, n_levels=n_levels,
                                   base_seed=seed)
                for sw, mem in switch_memories.items()
            }
            # rho_-1 undefined: start every fragment at n_0 = 1 (§4.2).
            self.ns: Dict[int, int] = {sw: 1 for sw in switch_memories}
            self.records: Dict[int, Dict[int, EpochRecords]] = {}  # epoch -> sw
            self.peb_log: List[Dict[int, float]] = []
            self.n_log: List[Dict[int, int]] = []
            # -- churn state (net.simulator.FailureSchedule drives this) -----
            # Switches whose sketch resource is currently reclaimed.  A dead
            # switch keeps forwarding traffic (disaggregation uses residual
            # resources, §1) — it just stops counting: its packets become
            # value-0 no-ops on the fleet, it is skipped by the loop backend,
            # masked from every query path, and held out of the §4.2 control.
            self.dead: set = set()
            self._dead_at: Dict[int, frozenset] = {}   # epoch -> dead set
            # Resource resizes (shrinks AND grows) arriving mid-window are
            # deferred to the next dispatch boundary (widths are frozen per
            # window); factors multiply while pending.
            self._pending_resize: Dict[int, float] = {}
            # Width each switch had when its most recent PEB was observed —
            # a later resize makes that observation *stale*, and the §6
            # re-equalization must converge against the width-clamped bound
            # (see ``_reequalize_survivors``), not the raw stale number.
            self._peb_width: Dict[int, int] = {}
            # Re-equalization clamps surfaced to ``observability`` (the
            # "intended vs applied under actual residual memory" record).
            self.clamp_log: List[Dict] = []
            # External control mode (``runtime.control.VersionedControlPlane``
            # sets this): the system stops self-applying the Eq. 6 / §6
            # control — ``ns`` holds whatever config the switches *actually
            # applied*, and the (possibly lossy) control plane owns intent.
            self.control_external = False
            # Observability accounting of the last query window (stamped by
            # query_flows / query_entropy; see ``observability``).
            self.last_observability: Optional[Dict] = None
            # query_flows calls so far: the request number of its span
            self.queries = 0
            if backend not in ("loop", "fleet"):
                raise ValueError(f"unknown backend {backend!r}")
            if mesh is not None and backend != "fleet":
                raise ValueError(
                    "mesh sharding requires backend='fleet' (the loop "
                    "backend is per-switch host numpy)")
            self.backend = backend
            self.fleet: Optional["FleetEpochRunner"] = None
            if backend == "fleet":
                from .fleet import FleetEpochRunner
                kw = dict(fleet_kwargs or {})
                if mesh is not None:
                    kw.setdefault("mesh", mesh)
                self.fleet = FleetEpochRunner(self.fragments, log2_te, **kw)

    # -- churn control plane -------------------------------------------------

    def apply_event(self, event, *, defer_resize: bool = False) -> None:
        """Apply one churn event to the control plane.

        ``event`` is duck-typed (``net.simulator.FailureEvent`` or any
        object with ``.kind`` in {"fail", "shrink", "grow", "recover"},
        ``.switch``, and ``.factor``) so the core never imports the
        simulator.  "fail" reclaims the switch's sketch resource and
        triggers §6 re-equalization of the survivors; "recover" rejoins
        the switch as a fresh fragment at n_0 = 1 (§4.2 — its history is
        gone with the reclaimed memory); "shrink"/"grow" multiply the
        fragment's memory by ``event.factor`` — immediately, or deferred
        to the next dispatch boundary when ``defer_resize`` (widths are
        frozen within a window; grows and shrinks defer symmetrically).
        """
        sw = event.switch
        if sw not in self.fragments:
            raise KeyError(f"churn event for unknown switch {sw}")
        if event.kind == "fail":
            if sw not in self.dead:
                self.dead.add(sw)
                if not self.control_external:
                    self._reequalize_survivors()
        elif event.kind == "recover":
            if sw in self.dead:
                self.dead.discard(sw)
                self.ns[sw] = 1
        elif event.kind in ("shrink", "grow"):
            if defer_resize:
                self._pending_resize[sw] = (self._pending_resize.get(sw, 1.0)
                                            * event.factor)
            else:
                self._apply_resize(sw, event.factor)
        else:
            raise ValueError(f"unknown churn event kind {event.kind!r}")

    def _last_pebs(self) -> Dict[int, float]:
        last: Dict[int, float] = {}
        for pebs in self.peb_log:
            last.update(pebs)
        return last

    def _reequalize_survivors(self) -> None:
        # §6: a death shifts no load (the switch keeps forwarding), but
        # the survivors' last observed PEBs are the freshest signal the
        # controller has — jump each survivor to its converged Eq. 6
        # setting in one control step instead of the factor-2-per-epoch
        # ramp.  Survivors already inside the [rho/2, 2rho] band (and
        # switches with no observation yet) are untouched, so an
        # equalized fleet stays bit-identical after an off-path death.
        #
        # A survivor whose residual memory was resized *after* its last
        # PEB observation must NOT converge against the raw stale
        # number: the directive is clamped by the actual width, so
        # converge_n runs against the width-scaled bound (Eq. 4 is
        # ~1/width) and the clamp — intended vs applied — is surfaced
        # through ``clamp_log`` into ``observability``.
        if not self.subepoching:
            return
        last = self._last_pebs()
        survivors = {sw: n for sw, n in self.ns.items() if sw not in self.dead}
        intended = equalize.reequalize(survivors, last, self.rho_target)
        applied = dict(intended)
        for sw, n0 in survivors.items():
            peb = last.get(sw)
            w_obs = self._peb_width.get(sw)
            w_now = self.fragments[sw].width
            if peb is None or peb <= 0 or w_obs is None or w_obs == w_now:
                continue
            applied[sw] = equalize.converge_n(
                n0, peb * (w_obs / w_now), self.rho_target)
            if applied[sw] != intended[sw]:
                self.clamp_log.append({
                    "switch": sw, "at_epoch": len(self.peb_log),
                    "n_intended": intended[sw], "n_applied": applied[sw],
                    "width_observed": w_obs, "width_actual": w_now})
        self.ns.update(applied)

    def _apply_resize(self, sw: int, factor: float) -> None:
        from dataclasses import replace as dc_replace

        cfg = self.fragments[sw]
        new_mem = max(int(cfg.memory_bytes * factor), 4 * cfg.counter_bytes)
        w_old = cfg.width
        self.fragments[sw] = dc_replace(cfg, memory_bytes=new_mem)
        if self.fleet is not None:
            self.fleet.refresh_widths()
        # Predictive §6 control: resizing the column count scales the
        # per-counter load (and hence the Eq. 4 bound) by ~w_old/w_new —
        # up for shrinks, down for grows.  Converge n against that
        # prediction now; the next observed epoch corrects any modelling
        # error through the ordinary Eq. 6 loop.  In external-control
        # mode the (lossy) plane owns this adjustment instead.
        if (self.subepoching and not self.control_external
                and sw not in self.dead):
            last = self._last_pebs().get(sw)
            w_new = self.fragments[sw].width
            if last is not None and last > 0 and w_new != w_old:
                self.ns[sw] = equalize.converge_n(
                    self.ns[sw], last * (w_old / w_new), self.rho_target)

    def _apply_pending_resizes(self) -> None:
        for sw, factor in self._pending_resize.items():
            self._apply_resize(sw, factor)
        self._pending_resize.clear()

    # -- data plane ----------------------------------------------------------

    def run_epoch(self, epoch: int, streams: Dict[int, SwitchStream],
                  packet=None, events: Optional[Sequence] = None) -> None:
        """Process one epoch.  ``packet`` (a prepacked ``FleetPacket``,
        e.g. from ``Replayer.epoch_packet``) lets the fleet backend skip
        re-packing ``streams``; the loop backend ignores it.  ``events``
        are churn events taking effect at this epoch's start."""
        self._apply_pending_resizes()
        for ev in (events or ()):
            self.apply_event(ev)
        if self.dead:
            self._dead_at[epoch] = frozenset(self.dead)
        else:
            self._dead_at.pop(epoch, None)
        if self.backend == "fleet":
            ns = (self.ns if self.subepoching
                  else {sw: 1 for sw in self.fragments})
            recs, pebs = self.fleet.run_epoch(epoch, ns, streams,
                                              packet=packet, dead=self.dead)
        else:
            recs, pebs = self._run_epoch_loop(epoch, streams)
        if self.subepoching and not self.control_external:
            for sw, peb in pebs.items():
                self.ns[sw] = equalize.next_n(self.ns[sw], peb,
                                              self.rho_target)
        self.records[epoch] = recs
        self.peb_log.append(pebs)
        for sw in pebs:
            self._peb_width[sw] = self.fragments[sw].width
        self.n_log.append(dict(self.ns))

    def _run_epoch_loop(self, epoch: int, streams: Dict[int, SwitchStream],
                        ) -> Tuple[Dict[int, EpochRecords],
                                   Dict[int, float]]:
        epoch_start = epoch << self.log2_te
        recs: Dict[int, EpochRecords] = {}
        pebs: Dict[int, float] = {}
        for sw, cfg in self.fragments.items():
            if sw in self.dead:
                continue
            st = streams.get(sw)
            n = self.ns[sw] if self.subepoching else 1
            if st is None or len(st.keys) == 0:
                st = SwitchStream(np.zeros(0, np.uint32), np.zeros(0, np.int64),
                                  np.zeros(0, np.int64))
            rec = process_epoch(cfg, epoch, n, st.keys, st.values, st.ts,
                                epoch_start, self.log2_te,
                                single_hop=st.single_hop)
            recs[sw] = rec
            pebs[sw] = equalize.peb_epoch(rec)
        return recs, pebs

    def run_window(self, epoch0: int,
                   streams_list: Sequence[Dict[int, SwitchStream]],
                   packets: Optional[Sequence] = None,
                   events_by_epoch: Optional[Sequence[Sequence]] = None,
                   ) -> None:
        """Process ``len(streams_list)`` consecutive epochs starting at
        ``epoch0`` in ONE fleet super-dispatch (window mode).

        ``ns`` is frozen across the window for the kernel; at the window
        boundary the observed per-epoch PEBs are replayed through Eq. 6
        in order, so the control trajectory still reacts to every epoch
        (just with window-granularity latency).  ``packets`` (prepacked
        ``FleetPacket``s, e.g. from ``Replayer.epoch_packet``) skip
        re-packing.  Non-fleet backends fall back to per-epoch
        processing (exact per-epoch control).

        Each replayed PEB was observed at the frozen n, so the replay
        rescales it to the walking n (``equalize.next_n_observed``).

        ``events_by_epoch`` (one event sequence per window offset)
        injects churn: a mid-window "fail" at offset e masks the
        switch's epochs >= e AND marks its un-exported earlier epochs
        [0, e) as *lost* — the reclaimed memory held them; they are
        zeroed unless an XOR-parity group (``fleet_kwargs=
        {"parity_groups": ...}``) makes them recoverable.  Mid-window
        shrink/grow events defer to the next dispatch (widths are
        frozen per window); fail/recover control effects (re-equalized
        survivors, n reset) also land on the next dispatch for the same
        reason.
        """
        if self.backend != "fleet":
            for e, streams in enumerate(streams_list):
                self.run_epoch(
                    epoch0 + e, streams,
                    events=events_by_epoch[e] if events_by_epoch else None)
            return
        from .fleet import pack_streams

        with obs.span("system.run_window", epoch0=epoch0,
                      epochs=len(streams_list)):
            e_count = len(streams_list)
            if events_by_epoch is not None and len(events_by_epoch) != e_count:
                raise ValueError("events_by_epoch must have one entry per epoch "
                                 f"({len(events_by_epoch)} != {e_count})")
            self._apply_pending_resizes()
            for ev in (events_by_epoch[0] if events_by_epoch else ()):
                self.apply_event(ev)
            ns = (dict(self.ns) if self.subepoching
                  else {sw: 1 for sw in self.fragments})
            dead_sets = [frozenset(self.dead)]
            fail_pts: List[Tuple[int, int]] = []
            for e in range(1, e_count):
                for ev in (events_by_epoch[e] if events_by_epoch else ()):
                    if ev.kind == "fail" and ev.switch not in self.dead:
                        fail_pts.append((e, ev.switch))
                    self.apply_event(ev, defer_resize=True)
                dead_sets.append(frozenset(self.dead))
            lost_sets: List[set] = [set() for _ in range(e_count)]
            for e, sw in fail_pts:
                for e2 in range(e):
                    if sw not in dead_sets[e2]:
                        lost_sets[e2].add(sw)
            if packets is None:
                packets = [pack_streams(st, self.fleet.frag_order)
                           for st in streams_list]
            recs_list, pebs_list = self.fleet.run_window(
                epoch0, ns, packets,
                dead_by_epoch=dead_sets, lost_by_epoch=lost_sets)
            for e, (recs, pebs) in enumerate(zip(recs_list, pebs_list)):
                if dead_sets[e]:
                    self._dead_at[epoch0 + e] = dead_sets[e]
                else:
                    self._dead_at.pop(epoch0 + e, None)
                self.records[epoch0 + e] = recs
                self.peb_log.append(pebs)
                for sw in pebs:
                    self._peb_width[sw] = self.fragments[sw].width
                if self.subepoching and not self.control_external:
                    for sw, peb in pebs.items():
                        self.ns[sw] = equalize.next_n_observed(
                            self.ns[sw], peb, ns[sw], self.rho_target)
                self.n_log.append(dict(self.ns))

    # -- query plane --------------------------------------------------------

    def observability(self, epochs: Sequence[int]) -> Dict:
        """Staleness/observability accounting for a query window: per
        epoch, how many fragment cells are genuine observations *right
        now* (not dead, not lost, not held back by a pending export),
        plus the whole-window blind-epoch extrapolation scale
        (E / E_observable) masked queries apply.  Stamped on
        ``last_observability`` by every query entry point."""
        epochs = list(epochs)
        n_frags = len(self.fragments)
        per_epoch: Dict[int, int] = {}
        for e in epochs:
            if self.fleet is not None and (
                    e in self.fleet._window_bufs or e in self.fleet.stacked):
                live = self.fleet.frag_live(e)
                per_epoch[e] = (n_frags if live is None
                                else int(live.sum()))
            else:
                recs = self.records.get(e, {})
                per_epoch[e] = sum(1 for sw in recs
                                   if self._valid(sw, e))
        obs, scale = query.window_observability(
            [[None] * per_epoch[e] for e in epochs])
        return {"epochs": len(epochs), "observable_epochs": obs,
                "scale": scale,
                "observable_cells": sum(per_epoch.values()),
                "total_cells": n_frags * len(epochs),
                "per_epoch": per_epoch,
                # §6 directives clamped by actual residual memory
                # (intended vs applied config; see _reequalize_survivors)
                "config_clamps": list(self.clamp_log)}

    def _valid(self, sw: int, epoch: int) -> bool:
        """Is (switch, epoch) a genuine observation?  Dead and lost
        cells are not; parity-recovered cells are again."""
        if self.fleet is not None:
            live = self.fleet.frag_live(epoch)
            if live is None:
                return True
            return bool(live[self.fleet._frag_pos[sw]])
        return sw not in self._dead_at.get(epoch, frozenset())

    def _records_for(self, path: Sequence[int], epochs: Sequence[int],
                     failures: str = "mask") -> List[List[EpochRecords]]:
        # A window query over an unprocessed epoch must fail loudly: a
        # silently dropped epoch truncates the O_Q = Sum(O) estimate,
        # which looks like sketch error, not like the caller's bug it is
        # (matches FleetEpochRunner.window_query).
        missing = [e for e in epochs if e not in self.records]
        if missing:
            raise KeyError(f"epochs {missing} have no records "
                           "(not processed); run them before querying")
        if failures == "oblivious":
            return [[self.records[e][sw] for sw in path
                     if sw in self.records[e]] for e in epochs]
        return [[self.records[e][sw] for sw in path
                 if sw in self.records[e] and self._valid(sw, e)]
                for e in epochs]

    def query_flows(self, keys: np.ndarray, paths: Sequence[Tuple[int, ...]],
                    epochs: Sequence[int], merge: str = "subepoch",
                    failures: str = "mask") -> np.ndarray:
        """Window frequency estimates for flows with per-flow paths.

        On the fleet backend with ``merge="fragment"``, windows whose
        counter stacks are still device-resident (processed via
        ``run_window`` and not yet materialized) are answered by the
        on-device query plane — only the ``(K,)`` estimates cross the
        host boundary.  All paths of the request go in one batched
        gather/merge per resident stack and key chunk, each key merged
        over its own path's rows (``FleetEpochRunner.window_query_paths``);
        under a device mesh, or when churn masking touches a queried
        epoch, each path takes its own ``window_query`` call.  Everything
        else (the default subepoch merge, loop backend, materialized
        windows) goes through the per-record composite query over
        ``self.records``.  The ``query.flows`` span counts the device
        launches (``device_calls``), the keys the batched call answered
        (``batched_keys``) and the paths sent one by one to the device
        (``fallback_paths``).

        UnivMon frequency estimates come from level 0 (the level that
        sees the full stream) on both planes; §4.4 mitigation's
        second-subepoch average applies per path group (single-hop ==
        path length 1) on both planes too.

        ``failures`` sets the churn policy (both planes):
          * ``"mask"`` (default) — drop dead/lost fragment-epochs from
            the merge; a path whose fragments are all out for some epoch
            makes that epoch *blind* and the window estimate is
            extrapolated by E / E_observable (the §4.3 temporal
            blind-spot treatment applied across epochs).  A path with
            zero observable epochs raises.
          * ``"recover"`` — first reconstruct every XOR-parity-
            recoverable lost cell (``FleetEpochRunner.recover``), then
            mask whatever remains.
          * ``"oblivious"`` — pretend nothing failed (the zeroed rows
            poison min/median merges); baseline for benchmarks.
        """
        if failures not in ("oblivious", "mask", "recover"):
            raise ValueError(f"unknown failure policy {failures!r}")
        self.queries += 1
        with obs.span("query.flows", request=self.queries,
                      keys=len(keys)) as sp:
            self.last_observability = self.observability(epochs)
            keys = np.asarray(keys, dtype=np.uint32)
            ids: Dict[Tuple[int, ...], int] = {}
            path_id = np.fromiter(
                (ids.setdefault(tuple(p), len(ids)) for p in paths),
                np.int32, len(keys))
            uniq = list(ids)
            sp.set_metadata(paths=len(uniq))
            device_ok = (merge == "fragment" and self.fleet is not None
                         and self.fleet.has_device_window(epochs))
            if failures == "recover" and self.fleet is not None:
                # patch the stacks before either plane reads them
                self.fleet.recover(epochs)
                failures = "mask"
            launches = self.fleet.query_launches if self.fleet else 0
            if device_ok and self.fleet.batches_paths(epochs, failures):
                out = self.fleet.window_query_paths(epochs, keys, uniq,
                                                    path_id, level=0)
                sp.set_metadata(
                    device_calls=self.fleet.query_launches - launches,
                    batched_keys=len(keys), fallback_paths=0)
                return out
            out = np.zeros(len(keys))
            # um frequency estimates come from level 0 (the full-stream
            # level); the record plane needs level=None for non-um kinds.
            level = 0 if self.kind == "um" else None
            order = np.argsort(path_id, kind="stable")
            bounds = np.searchsorted(path_id[order], np.arange(len(uniq) + 1))
            for j, path in enumerate(uniq):
                idxs = order[bounds[j]:bounds[j + 1]]
                if device_ok:
                    out[idxs] = self.fleet.window_query(
                        epochs, keys[idxs], path=path, level=0,
                        single_hop=len(path) == 1, failures=failures)
                    continue
                recs = self._records_for(path, epochs, failures=failures)
                scale = 1.0
                if failures != "oblivious":
                    # query_window skips empty (blind) epochs; extrapolate
                    # O_Q from the observed ones (§4.3 blind-spot fill,
                    # lifted from subepoch slots to whole epochs).
                    n_obs, scale = query.window_observability(recs)
                    if not n_obs:
                        raise ValueError(
                            f"no epoch in {list(epochs)} has a live fragment on "
                            f"path {path}; the window is unobservable")
                sh = np.full(len(idxs), len(path) == 1)
                out[idxs] = query.query_window(
                    recs, keys[idxs], self.kind,
                    single_hop=sh, level=level, merge=merge) * scale
            sp.set_metadata(
                device_calls=(self.fleet.query_launches - launches
                              if self.fleet else 0),
                batched_keys=0, fallback_paths=len(uniq) if device_ok else 0)
            return out

    def query_entropy(self, keys: np.ndarray,
                      paths: Sequence[Tuple[int, ...]],
                      epochs: Sequence[int], total: float,
                      n_levels: int = 16, level_seed: int = 7777,
                      k_heavy: int = 1024,
                      merge: str = "subepoch",
                      failures: str = "mask") -> float:
        """Network-wide empirical entropy from the UnivMon level stack.

        ``merge="fragment"`` selects the §4.2 proportional-scaling
        fragment merge for the per-level estimates; on the fleet
        backend with device-resident windows that path runs end-to-end
        on device — one batched all-levels gather/merge per path group
        (``FleetEpochRunner.um_level_window_query``) feeding the jitted
        top-down G-sum combine, with only the per-level estimates and
        one scalar crossing the host boundary.  The default subepoch
        merge always goes through the per-record plane.

        ``failures`` follows ``query_flows``; note the record plane
        masks dead/lost cells but does not extrapolate blind epochs
        (the G-sum is not additive across epochs), while the device
        plane applies the same E / E_observable scaling to the
        per-level frequency estimates as the frequency path.
        """
        assert self.kind == "um"
        if failures not in ("oblivious", "mask", "recover"):
            raise ValueError(f"unknown failure policy {failures!r}")
        self.last_observability = self.observability(epochs)
        by_path: Dict[Tuple[int, ...], List[int]] = {}
        for i, p in enumerate(paths):
            by_path.setdefault(tuple(p), []).append(i)
        keys = np.asarray(keys, dtype=np.uint32)
        device_ok = (merge == "fragment" and self.fleet is not None
                     and self.fleet.has_device_window(epochs)
                     and n_levels == self.fleet.n_levels
                     and level_seed == self.fleet.level_seed)
        if device_ok:
            from ..kernels.sketch_query import um_gsum_device

            ests, lvls = [], []
            for path, idxs in by_path.items():
                ks = keys[np.asarray(idxs)]
                if not len(ks):
                    continue
                ests.append(self.fleet.um_level_window_query(
                    epochs, ks, path=path, failures=failures))
                lvls.append(query.H.level_of(ks, level_seed, n_levels))
            if not ests:
                return 0.0 if total <= 0 else float(np.log2(total))
            s = um_gsum_device(np.concatenate(ests, axis=1),
                               np.concatenate(lvls), _g_entropy,
                               k_heavy=k_heavy)
            if total <= 0:
                return 0.0
            return float(np.log2(total) - s / total)
        if failures == "recover" and self.fleet is not None:
            self.fleet.recover(epochs)
            failures = "mask"
        recs, keysets = [], []
        for path, idxs in by_path.items():
            recs.append(self._records_for(path, epochs, failures=failures))
            keysets.append(keys[np.asarray(idxs)])
        return query.um_entropy_window(recs, keysets, n_levels, level_seed,
                                       total, k_heavy=k_heavy, merge=merge)


def calibrate_rho_target(switch_memories: Dict[int, int], kind: str,
                         streams: Dict[int, SwitchStream], log2_te: int,
                         quantile: float = 0.5, **kw) -> float:
    """Select a network-wide rho_target from a probe epoch (§4.2/§7).

    Runs one epoch with n = 1 everywhere and returns a quantile of the
    observed per-fragment PEBs: the target is what well-provisioned
    fragments already deliver; worse fragments subsample time (raise n)
    until they match it.  The median (0.5) won a quantile sweep on the
    Fat-Tree scenarios (lower quantiles over-subdivide healthy fragments
    and pay slot-coverage loss; higher ones degenerate to DISCO),
    consistent with the paper's "within a factor of two is forgiving".
    """
    probe = DiSketchSystem(switch_memories, kind, rho_target=float("inf"),
                           log2_te=log2_te, **kw)
    probe.run_epoch(0, streams)
    pebs = [p for p in probe.peb_log[0].values() if p > 0]
    if not pebs:
        return 1.0
    return float(max(np.quantile(pebs, quantile), 1.0))


class DiscoSystem(DiSketchSystem):
    """DISCO [17]: per-row disaggregation, no subepoching / equalization."""

    name = "disco"
    subepoching = False


class AggregatedSystem:
    """Traditional deployment: a full sketch on each core switch (§6)."""

    name = "aggregated"

    def __init__(self, core_memories: Dict[int, int], kind: str,
                 depth: int = 4, counter_bytes: int = 4, n_levels: int = 16,
                 seed: int = 0):
        self.kind = kind
        self.depth = depth
        self.n_levels = n_levels
        self.specs: Dict[int, object] = {}
        self.counters: Dict[int, Dict[int, np.ndarray]] = {}  # epoch -> sw
        self._cur: Dict[int, np.ndarray] = {}
        for sw, mem in core_memories.items():
            w = max(mem // (counter_bytes * depth), 4)
            if kind == "um":
                w = max(w // n_levels, 4)
                self.specs[sw] = sketches.UnivMonSpec(depth, w, n_levels,
                                                      seed=seed + sw)
            else:
                self.specs[sw] = sketches.SketchSpec(kind, depth, w,
                                                     seed=seed + sw)

    def run_epoch(self, epoch: int, streams: Dict[int, SwitchStream],
                  events: Optional[Sequence] = None) -> None:
        if events:
            raise ValueError(
                "AggregatedSystem models no churn: a monolithic core sketch "
                "has no reclaimable per-switch fragments; failure schedules "
                "apply to disaggregated systems only")
        recs = {}
        for sw, spec in self.specs.items():
            st = streams.get(sw)
            if self.kind == "um":
                c = sketches.um_make_counters(spec)
                if st is not None and len(st.keys):
                    c = sketches.um_update(spec, c, st.keys, st.values)
            else:
                c = sketches.make_counters(spec)
                if st is not None and len(st.keys):
                    c = sketches.update(spec, c, st.keys, st.values)
            recs[sw] = c
        self.counters[epoch] = recs

    def query_flows(self, keys: np.ndarray, core_switch: Sequence[int],
                    epochs: Sequence[int]) -> np.ndarray:
        """Query each flow at the (single) core switch on its path."""
        keys = np.asarray(keys, dtype=np.uint32)
        # same loud-failure contract as DiSketchSystem._records_for: a
        # silently skipped epoch truncates O_Q and skews baseline
        # comparisons one-sidedly
        missing = [e for e in epochs if e not in self.counters]
        if missing:
            raise KeyError(f"epochs {missing} have no counters "
                           "(not processed); run them before querying")
        out = np.zeros(len(keys))
        by_sw: Dict[int, List[int]] = {}
        for i, sw in enumerate(core_switch):
            by_sw.setdefault(int(sw), []).append(i)
        for sw, idxs in by_sw.items():
            idxs = np.asarray(idxs)
            spec = self.specs[sw]
            for e in epochs:
                c = self.counters[e][sw]
                if self.kind == "um":
                    out[idxs] += sketches.um_query_freq(spec, c, keys[idxs])
                else:
                    out[idxs] += sketches.query(spec, c, keys[idxs])
        return out
