"""Epoch-driven replay engine: feeds per-switch packet streams to a system.

Precomputes, for every switch, the indices of packets whose path traverses
it (packets are replayed chronologically; the epoch split uses timestamps,
so subepoch semantics are exact).  Drives any system exposing
``run_epoch(epoch, {switch: SwitchStream})``.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..core.disketch import SwitchStream
from ..runtime.fault_tolerance import HeartbeatMonitor
from .traffic import Workload


@dataclass(frozen=True)
class FailureEvent:
    """One churn event, consumed by ``DiSketchSystem.apply_event``.

    ``kind``: "fail" (sketch resource reclaimed — the switch keeps
    forwarding), "recover" (resource returned; the fragment restarts
    fresh at n_0 = 1), "shrink" (memory multiplied by ``factor`` <= 1),
    or "grow" (memory multiplied by ``factor`` > 1 — a co-resident app
    released SRAM back to the fragment).
    """
    epoch: int
    switch: int
    kind: str
    factor: float = 1.0


class _EpochClock:
    """Injectable clock stepping ``epoch_s`` seconds per replay epoch."""

    def __init__(self, epoch_s: float):
        self.epoch_s = epoch_s
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class FailureSchedule:
    """Scripted switch churn, *detected* through a heartbeat monitor.

    The schedule holds the ground truth — ``downs[sw] = (down_epoch,
    up_epoch | None)`` plus scripted resource-reclaim shrinks — but the
    events it emits are what the control plane can actually observe:
    each ``advance(epoch)`` steps the injectable clock by ``epoch_s``,
    beats every up switch into a ``runtime.fault_tolerance.
    HeartbeatMonitor``, and derives "fail"/"recover" events from the
    monitor's timeout transitions.  With the default ``timeout_s =
    0.75 * epoch_s`` a death is detected in the first epoch the switch
    misses (one full silent epoch > timeout), so masking aligns with
    ground truth; a larger timeout models detection lag — the epochs
    before detection stay unmasked, exactly as a real deployment would
    mis-trust them.

    Deterministic and replayable: the clock is owned by the schedule
    (or injected for tests), never wall time.
    """

    def __init__(self, n_switches: int,
                 downs: Optional[Dict[int, Tuple[int, Optional[int]]]] = None,
                 shrinks: Optional[Sequence[Tuple[int, int, float]]] = None,
                 *, epoch_s: float = 1.0,
                 timeout_s: Optional[float] = None,
                 clock: Optional[Callable[[], float]] = None):
        self.n_switches = n_switches
        self.downs: Dict[int, Tuple[int, Optional[int]]] = dict(downs or {})
        for sw, (d, u) in self.downs.items():
            if not 0 <= sw < n_switches:
                raise ValueError(f"switch {sw} out of range "
                                 f"[0, {n_switches})")
            if u is not None and u <= d:
                raise ValueError(f"switch {sw}: up epoch {u} must follow "
                                 f"down epoch {d}")
        self._shrinks: Dict[int, List[FailureEvent]] = {}
        for ep, sw, factor in (shrinks or ()):
            # factor <= 1 is a resource reclaim ("shrink"); factor > 1
            # a resource release ("grow") — the bidirectional model of
            # §6's "residual resources change over time".
            if not factor > 0.0:
                raise ValueError(f"resize factor {factor} must be > 0")
            kind = "shrink" if factor <= 1.0 else "grow"
            self._shrinks.setdefault(int(ep), []).append(
                FailureEvent(int(ep), int(sw), kind, float(factor)))
        self.epoch_s = epoch_s
        self._clock = clock if clock is not None else _EpochClock(epoch_s)
        self._own_clock = clock is None
        self.monitor = HeartbeatMonitor(
            n_switches,
            timeout_s=0.75 * epoch_s if timeout_s is None else timeout_s,
            clock=self._clock)
        self._known_dead: set = set()
        self.log: List[FailureEvent] = []

    def is_up(self, sw: int, epoch: int) -> bool:
        """Ground truth (the monitor may not have detected it yet)."""
        d_u = self.downs.get(sw)
        if d_u is None:
            return True
        d, u = d_u
        return epoch < d or (u is not None and epoch >= u)

    def advance(self, epoch: int) -> List[FailureEvent]:
        """Emit the churn events *detected* at ``epoch``'s start."""
        if self._own_clock:
            self._clock.t = epoch * self.epoch_s
        for sw in range(self.n_switches):
            if self.is_up(sw, epoch):
                self.monitor.beat(sw)
        failed = self.monitor.failed_hosts()
        events: List[FailureEvent] = []
        for sw in sorted(failed - self._known_dead):
            events.append(FailureEvent(epoch, sw, "fail"))
        for sw in sorted(self._known_dead - failed):
            events.append(FailureEvent(epoch, sw, "recover"))
        self._known_dead = set(failed)
        events.extend(self._shrinks.get(epoch, ()))
        self.log.extend(events)
        return events

    @classmethod
    def random(cls, n_switches: int, frac_failed: float, *,
               down_epoch: int, up_epoch: Optional[int] = None,
               seed: int = 0, **kw) -> "FailureSchedule":
        """Kill a random ``frac_failed`` of the switches at
        ``down_epoch`` (optionally recovering at ``up_epoch``)."""
        rng = np.random.default_rng(seed)
        k = int(round(frac_failed * n_switches))
        victims = rng.choice(n_switches, size=k, replace=False)
        downs = {int(sw): (down_epoch, up_epoch) for sw in victims}
        return cls(n_switches, downs, **kw)


class ResourcePressure:
    """Time-varying resource contention from co-resident switch apps.

    The paper's premise (§6) is that a fragment lives in *residual*
    SRAM other in-network applications also claim.  This generator
    models that bidirectionally: at each epoch a seeded per-switch
    process may *grab* a fraction of the fragment's memory (a "shrink"
    event with factor ``1 - grab``), hold it for a few epochs, then
    *release* it (a "grow" event with the inverse factor ``1 / (1 -
    grab)``).  At most one grab is in flight per switch.

    Fully pregenerated at construction from ``seed`` — two instances
    with the same arguments emit identical event streams, which is what
    lets the chaos harness replay a run against a config twin.  Exposes
    the same ``advance(epoch)`` interface as ``FailureSchedule``, so it
    drives ``Replayer.run(..., failures=...)`` directly or composes via
    ``ComposedSchedule``.

    Note the integer-truncation caveat: memory is tracked in whole
    bytes, so a grab/release cycle restores the original width only up
    to ``int()`` truncation of the two multiplications.
    """

    def __init__(self, n_switches: int, *, horizon: int, seed: int = 0,
                 p_grab: float = 0.15,
                 grab_frac: Tuple[float, float] = (0.3, 0.7),
                 hold: Tuple[int, int] = (1, 4)):
        if not 0.0 <= p_grab <= 1.0:
            raise ValueError(f"p_grab={p_grab} not in [0, 1]")
        lo, hi = grab_frac
        if not 0.0 < lo <= hi < 1.0:
            raise ValueError(f"grab_frac range {grab_frac} not in (0, 1)")
        h_lo, h_hi = int(hold[0]), int(hold[1])
        if h_lo < 1 or h_hi < h_lo:
            raise ValueError(f"hold range {hold} invalid")
        self.n_switches = int(n_switches)
        self.horizon = int(horizon)
        rng = np.random.default_rng(seed)
        self._events: Dict[int, List[FailureEvent]] = {}
        for sw in range(self.n_switches):
            busy_until = 0
            for ep in range(self.horizon):
                if ep < busy_until or rng.random() >= p_grab:
                    continue
                grab = float(rng.uniform(lo, hi))
                release = ep + int(rng.integers(h_lo, h_hi + 1))
                self._events.setdefault(ep, []).append(
                    FailureEvent(ep, sw, "shrink", 1.0 - grab))
                if release < self.horizon:
                    self._events.setdefault(release, []).append(
                        FailureEvent(release, sw, "grow",
                                     1.0 / (1.0 - grab)))
                busy_until = release
        self.log: List[FailureEvent] = []

    def advance(self, epoch: int) -> List[FailureEvent]:
        events = list(self._events.get(int(epoch), ()))
        self.log.extend(events)
        return events


class ComposedSchedule:
    """Chain several event sources (``FailureSchedule``,
    ``ResourcePressure``, ...) behind one ``advance(epoch)`` — the
    chaos harness's way of running churn and resource pressure in the
    same replay.  Events are emitted in schedule order per epoch."""

    def __init__(self, schedules: Sequence):
        self.schedules = list(schedules)
        self.log: List[FailureEvent] = []

    def advance(self, epoch: int) -> List[FailureEvent]:
        events: List[FailureEvent] = []
        for s in self.schedules:
            events.extend(s.advance(epoch))
        self.log.extend(events)
        return events


class Replayer:
    def __init__(self, wl: Workload, n_switches: int,
                 packet_cache: int = 8):
        self.wl = wl
        self.n_switches = n_switches
        # Packed-epoch LRU capacity: packed streams are O(epoch packets)
        # each, so an unbounded cache would accumulate the entire trace
        # over a long replay.  8 epochs ≈ two 4-epoch windows.
        self.packet_cache = packet_cache
        pkt_keys = wl.pkt_keys
        single_hop_flow = wl.path_len == 1
        epoch_of = (wl.pkt_ts >> wl.log2_te).astype(np.int64)
        # Per-switch packet index lists, pre-split by epoch.
        self._streams: List[Dict[int, SwitchStream]] = [
            {} for _ in range(wl.n_epochs)]
        # (epoch, frag_order) -> FleetPacket, LRU-evicted
        self._packets: "OrderedDict" = OrderedDict()
        for sw in range(n_switches):
            on_path = (wl.path_mat == sw).any(axis=1)  # per flow
            pkt_sel = on_path[wl.pkt_flow]
            if not pkt_sel.any():
                continue
            idx = np.nonzero(pkt_sel)[0]
            e = epoch_of[idx]
            order = np.argsort(e, kind="stable")
            idx = idx[order]
            bounds = np.searchsorted(e[order], np.arange(wl.n_epochs + 1))
            for ep in range(wl.n_epochs):
                lo, hi = bounds[ep], bounds[ep + 1]
                if lo == hi:
                    continue
                sl = idx[lo:hi]
                self._streams[ep][sw] = SwitchStream(
                    keys=pkt_keys[sl],
                    values=np.ones(len(sl), dtype=np.int64),
                    ts=wl.pkt_ts[sl],
                    single_hop=single_hop_flow[wl.pkt_flow[sl]],
                )

    def run(self, system, window: int = 1,
            failures: Optional[FailureSchedule] = None) -> None:
        # Fleet-backed systems consume the cached packed packet tensor
        # (built once per epoch, shared across systems and replays).
        # ``window=E`` batches E consecutive epochs into one fleet
        # super-dispatch (``system.run_window``; ns frozen per window).
        # ``failures`` advances a churn schedule alongside the replay
        # and injects the detected events into the system.
        fleet = getattr(system, "fleet", None)
        if window > 1 and fleet is not None:
            for e0 in range(0, self.wl.n_epochs, window):
                eps = range(e0, min(e0 + window, self.wl.n_epochs))
                kw = {}
                if failures is not None:
                    kw["events_by_epoch"] = [failures.advance(e)
                                             for e in eps]
                    if any(kw["events_by_epoch"]):
                        # a failure/recovery cycle reprocesses the whole
                        # window: stale LRU entries from a previous run
                        # of these epochs must not pair old packing with
                        # the new churn state
                        self.invalidate_packets(eps)
                system.run_window(
                    e0, [self._streams[e] for e in eps],
                    packets=[self.epoch_packet(e, fleet.frag_order)
                             for e in eps], **kw)
            return
        for ep in range(self.wl.n_epochs):
            kw = {}
            if failures is not None:
                kw["events"] = failures.advance(ep)
                if kw["events"]:
                    self.invalidate_packets([ep])
            if fleet is not None:
                system.run_epoch(ep, self._streams[ep],
                                 packet=self.epoch_packet(
                                     ep, fleet.frag_order), **kw)
            else:
                system.run_epoch(ep, self._streams[ep], **kw)

    def epoch_stream(self, epoch: int) -> Dict[int, SwitchStream]:
        return self._streams[epoch]

    def invalidate_packets(self, epochs) -> int:
        """Evict the packed-epoch LRU entries for ``epochs`` (every
        frag_order variant).  Called by ``run`` whenever a
        failure/recovery cycle reprocesses those epochs: the packed
        tensors are shared across systems and replays, so an entry a
        caller mutated (or that pairs with superseded churn state) must
        be rebuilt from the pristine per-switch streams rather than
        silently reused.  Returns the number of entries evicted."""
        eset = set(int(e) for e in epochs)
        victims = [k for k in self._packets if k[0] in eset]
        for k in victims:
            del self._packets[k]
        return len(victims)

    def epoch_packet(self, epoch: int, frag_order=None):
        """Packed fragment-major packet tensor for the fleet engine.

        Concatenates the epoch's per-switch streams (keys/values/ts) with
        segment offsets, in ``frag_order`` (default: all switches in id
        order).  Cached in an LRU of ``packet_cache`` epochs — recently
        packed epochs are shared across systems/replays, but a long
        replay never accumulates every epoch's packed stream.
        """
        from ..core.fleet import pack_streams

        if frag_order is None:
            frag_order = tuple(range(self.n_switches))
        frag_order = tuple(frag_order)
        key = (epoch, frag_order)
        with obs.span("replay.epoch_packet") as sp:
            pkt = self._packets.get(key)
            hit = pkt is not None
            if pkt is None:
                pkt = pack_streams(self._streams[epoch], frag_order)
                self._packets[key] = pkt
                while len(self._packets) > self.packet_cache:
                    self._packets.popitem(last=False)
            else:
                self._packets.move_to_end(key)
            sp.set_metadata(hit=int(hit), packets=len(pkt.keys))
        return pkt


def rmse(est: np.ndarray, truth: np.ndarray) -> float:
    e = np.asarray(est, dtype=np.float64) - np.asarray(truth,
                                                       dtype=np.float64)
    return float(np.sqrt(np.mean(e * e)))


def nrmse(est: np.ndarray, truth: np.ndarray, total: float) -> float:
    """Paper §6.3: RMSE normalized by total packet count (dimensionless)."""
    return rmse(est, truth) / max(float(total), 1.0)


def are(est: np.ndarray, truth: np.ndarray) -> float:
    """Average relative error over queried flows."""
    t = np.maximum(np.asarray(truth, dtype=np.float64), 1.0)
    return float(np.mean(np.abs(np.asarray(est) - truth) / t))
