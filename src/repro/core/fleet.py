"""Fleet execution engine: one batched device dispatch per network epoch
— or per multi-epoch *window*.

``DiSketchSystem.run_epoch`` originally walked switches in a Python loop,
calling the numpy fragment path once per switch — correct, but serialized
exactly where the ROADMAP demands line-rate throughput.  This module
packs every switch's epoch stream into one flat blk-aligned CSR stream
(``pack_csr``: per-fragment segments + a block->fragment map, waste
<= blk per fragment) and updates *all* fragments with a single
``fleet_update_ragged`` kernel launch (repro.kernels.sketch_update.fleet),
then unpacks the stacked counters into the same per-fragment
``EpochRecords`` the query plane already consumes.  The
error-equalization control loop (§4.2) reads its PEBs directly from the
stacked output (``equalize.peb_fleet``).  Host-side, the per-epoch cost
is one vectorized scatter of the packet stream into its blk-aligned
destinations (pure numpy index arithmetic, no per-fragment Python
copies) plus O(n_frags) bookkeeping — no per-packet Python work.

**Epoch-window super-dispatch** (``FleetEpochRunner.run_window``): since
the kernel reads per-row seeds/width/n_sub from the parameter table, E
epochs x F fragments are just E*F param rows.  A whole control window is
dispatched in one launch with ``ns`` frozen for the window (§4.2 is
"within a factor of two" forgiving; per-epoch control stays the
default).  Counters stay device-resident across the window: the overflow
peak and the per-row PEBs are computed on-device, and the single host
transfer + int64 conversion + record unpacking happen lazily, once per
window, on first query-plane access (``WindowRecords``).

**UnivMon & §4.4 mitigation** run on the fleet too (since PR 5): every
UnivMon level is a *virtual fragment row* of the parameter table (table
row ``(e*F + f)*L + l`` carries the level-mixed column/sign seeds and
its ``PARAM_LEVEL``), the packet stream is still packed once per
fragment (a level grid axis fans each packet block out in-kernel), and
the per-key level id / single-hop flag ride the high bits of the packed
timestamp (``fold_packet_flags``).  See docs/univmon.md for the design
and exactness argument.

Numerical contract: for every kind — ``cs``, ``cms``, and ``um``, with
or without §4.4 mitigation — the fleet path produces bit-identical
counters to the per-switch loop (same ``frag_seed``/``level_seed_mix``
derivation, same hash arithmetic in-kernel), and the ragged CSR layout
is bit-identical to the PR-1 dense rectangle on cs/cms
(``layout="dense"``, kept as an oracle/baseline); validated in
tests/test_fleet.py and tests/test_univmon_fleet.py.
"""
from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels.sketch_update.kernel import MIN_BLK, abs_peak
from . import equalize
from .fragment import (EpochRecords, FragmentConfig, _ROLE_COL, _ROLE_SIGN,
                       _ROLE_SUB, frag_seed, level_seed_mix)

#: CSR alignment of the packed packet stream, which is also the ragged
#: kernel's packet tile: the smallest packet block the chip compiles.
#: Interpret-mode tests may pass any multiple of 128.
CSR_BLK = MIN_BLK


@dataclass
class FleetPacket:
    """One epoch's packets for the whole fleet, packed fragment-major.

    ``keys``/``values``/``ts`` are the concatenation of every fragment's
    stream in ``frag_order``; ``offsets[f] : offsets[f+1]`` is fragment
    ``frag_order[f]``'s segment.  Built once per epoch (by
    ``net.simulator.Replayer.epoch_packet`` or ``pack_streams``) and
    densified on demand.
    """

    keys: np.ndarray           # (P,) uint32
    values: np.ndarray         # (P,) int64
    ts: np.ndarray             # (P,) int64
    offsets: np.ndarray        # (n_frags + 1,) int64 segment offsets
    frag_order: Tuple[int, ...]
    single_hop: Optional[np.ndarray] = None  # (P,) bool, §4.4 flag

    @property
    def n_frags(self) -> int:
        return len(self.frag_order)

    def seg_lengths(self) -> np.ndarray:
        return np.diff(self.offsets)

    def select(self, idx: np.ndarray) -> "FleetPacket":
        """Sub-packet with only the fragments at positions ``idx`` (in
        ``frag_order`` position space) — the n_sub-grouped dispatch
        slices each group's segments out of the epoch packet."""
        segs = [(int(self.offsets[i]), int(self.offsets[i + 1]))
                for i in idx]

        def cat(arr):
            return np.concatenate([arr[lo:hi] for lo, hi in segs])

        offs = np.concatenate([[0], np.cumsum([hi - lo
                                               for lo, hi in segs])])
        return FleetPacket(cat(self.keys), cat(self.values), cat(self.ts),
                           offs.astype(np.int64),
                           tuple(self.frag_order[i] for i in idx),
                           None if self.single_hop is None
                           else cat(self.single_hop))

    def densify(self, blk: int = CSR_BLK) -> Tuple[np.ndarray, np.ndarray,
                                               np.ndarray]:
        """(n_frags, p_max) rectangles, value-0 padded, p_max % blk == 0.

        ``p_max`` is rounded up to the next power of two (>= blk) so the
        jit'd kernel sees few distinct shapes across epochs.  The dense
        rectangle is a transient — deliberately NOT cached: under skewed
        per-switch loads it is n_frags x pow2(hottest segment), far
        larger than the compact packed representation, and retaining one
        per epoch would accumulate gigabytes.
        """
        lens = self.seg_lengths()
        p_max = max(int(lens.max(initial=0)), blk)
        p_max = 1 << int(np.ceil(np.log2(p_max)))
        p_max += (-p_max) % blk
        f = self.n_frags
        keys = np.zeros((f, p_max), np.uint32)
        vals = np.zeros((f, p_max), np.float32)
        ts = np.zeros((f, p_max), np.uint32)
        for i in range(f):
            lo, hi = int(self.offsets[i]), int(self.offsets[i + 1])
            keys[i, :hi - lo] = self.keys[lo:hi]
            vals[i, :hi - lo] = self.values[lo:hi]
            ts[i, :hi - lo] = self.ts[lo:hi]
        return keys, vals, ts


def pack_streams(streams: Dict[int, "SwitchStream"],
                 frag_order: Sequence[int]) -> FleetPacket:
    """Concatenate per-switch streams into a fragment-major FleetPacket.

    The §4.4 ``single_hop`` flags ride along when any stream carries
    them (missing streams contribute all-False segments), so the fleet
    packer can fold them into the packed timestamps for
    mitigation-enabled fleets.
    """
    ks, vs, tss, shs, offs = [], [], [], [], [0]
    any_sh = any(st is not None and st.single_hop is not None
                 for st in streams.values())
    for sw in frag_order:
        st = streams.get(sw)
        n = 0 if st is None else len(st.keys)
        if n:
            ks.append(np.asarray(st.keys, np.uint32))
            vs.append(np.asarray(st.values, np.int64))
            tss.append(np.asarray(st.ts, np.int64))
            if any_sh:
                shs.append(np.zeros(n, bool) if st.single_hop is None
                           else np.asarray(st.single_hop, bool))
        offs.append(offs[-1] + n)
    cat = (lambda xs, dt: np.concatenate(xs) if xs else np.zeros(0, dt))
    return FleetPacket(cat(ks, np.uint32), cat(vs, np.int64),
                       cat(tss, np.int64), np.asarray(offs, np.int64),
                       tuple(frag_order),
                       cat(shs, bool) if any_sh else None)


def fold_packet_flags(packet: FleetPacket, log2_te: int, *,
                      n_levels: int = 1, level_seed: int = 0,
                      mitigation: bool = False) -> FleetPacket:
    """Fold per-packet UnivMon/§4.4 metadata into the high ts bits.

    The batched kernels read only timestamp bits ``[shift, log2_te)``
    (the Method-2 subepoch bit-slice), so the high bits of the packed
    uint32 ts word are free side-channels: this masks ts down to its low
    ``log2_te`` bits and ORs in the key's UnivMon level id (bits
    ``[LVL_SHIFT, LVL_SHIFT+5)``, computed once per packet with
    ``hashing.level_of``) and the single-hop flag (bit ``SH_SHIFT``).
    Returns the input packet unchanged when neither feature is active.
    Requires ``log2_te <= LVL_SHIFT`` for levels (``<= SH_SHIFT`` for
    mitigation alone) — enforced by ``FleetEpochRunner``.
    """
    from ..kernels.sketch_update.kernel import LVL_SHIFT, SH_SHIFT

    if n_levels <= 1 and not mitigation:
        return packet
    ts = np.asarray(packet.ts, np.int64) & ((1 << log2_te) - 1)
    if n_levels > 1:
        from . import hashing as H

        lvl = H.level_of(np.asarray(packet.keys, np.uint32), level_seed,
                         n_levels).astype(np.int64)
        ts = ts | (lvl << LVL_SHIFT)
    if mitigation and packet.single_hop is not None:
        ts = ts | (np.asarray(packet.single_hop, np.int64) << SH_SHIFT)
    return replace(packet, ts=ts)


def mask_fragment_values(packet: FleetPacket,
                         positions: Sequence[int]) -> FleetPacket:
    """Mask fragments out of a packed epoch by zeroing their segments'
    values: value-0 packets are kernel no-ops (the same property the blk
    padding relies on), so a masked fragment's counters come out exactly
    zero while every compiled shape (offsets, block map, packet count)
    stays unchanged — no re-pack, no re-compile.  ``positions`` are
    ``frag_order`` positions (a dead switch keeps *forwarding*; only its
    reclaimed sketch resource stops counting).  Keys/ts arrays are
    shared with the input packet; only ``values`` is copied."""
    if not len(positions):
        return packet
    vals = np.array(packet.values, copy=True)
    for i in positions:
        vals[int(packet.offsets[i]):int(packet.offsets[i + 1])] = 0
    return replace(packet, values=vals)


def parity_groups_chunked(frag_order: Sequence[int],
                          group_size: int) -> List[List[int]]:
    """Disjoint XOR-parity groups by chunking the fleet order: each
    group of ``group_size`` switches shares one parity row set (the last
    group may be smaller).  Any single lost fragment per group per epoch
    is then exactly reconstructible; group size trades parity memory
    (one fragment-equivalent per group) against the probability of a
    double loss."""
    if group_size < 1:
        raise ValueError(f"group_size must be >= 1, got {group_size}")
    order = list(frag_order)
    return [order[i:i + group_size]
            for i in range(0, len(order), group_size)]


def _bucket_blocks(nb: int, floor: int = 32) -> int:
    """Round a block count up to a shape bucket: exact below ``floor``,
    then 16 buckets per octave (padded blocks <= 6.25%), so the jit'd
    ragged kernel sees O(log P) distinct shapes across a replay instead
    of one compile per epoch."""
    if nb <= floor:
        return nb
    q = 1 << max(int(nb - 1).bit_length() - 5, 0)
    return -(-nb // q) * q


def pack_csr(packets: Sequence[FleetPacket], blk: int = CSR_BLK,
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized CSR packing for the ragged fleet kernel.

    Concatenates E epochs' ``FleetPacket``s into one flat stream whose
    *rows* are (epoch, fragment) pairs in epoch-major order
    (``row = e * n_frags + f``; E = 1 is the plain per-epoch case).
    Each row's segment is padded to a ``blk`` boundary with value-0
    packets and owns at least one block — empty rows cost exactly one
    zero block, which is what guarantees the kernel initializes every
    counter tile.  No per-fragment Python copies: destinations are
    computed with index arithmetic and one fancy-indexed scatter.

    Returns ``(keys, vals, ts, block_frag)``: ``(n_blocks * blk,)``
    uint32/float32/uint32 streams plus the non-decreasing
    ``(n_blocks,)`` int32 block->row map (trailing shape-bucket padding
    blocks map to the last row).
    """
    assert len(packets) >= 1
    with obs.span("fleet.pack_csr") as sp:
        n_rows = sum(p.n_frags for p in packets)
        lens = (np.concatenate([p.seg_lengths() for p in packets])
                .astype(np.int64))
        nblk = np.maximum(1, -(-lens // blk))
        row_blk_off = np.concatenate([[0], np.cumsum(nblk)])
        nb_live = int(row_blk_off[-1])
        nb = _bucket_blocks(nb_live)
        p_tot = nb * blk
        keys = np.zeros(p_tot, np.uint32)
        vals = np.zeros(p_tot, np.float32)
        ts = np.zeros(p_tot, np.uint32)
        src_keys = np.concatenate([p.keys for p in packets])
        src_vals = np.concatenate([p.values for p in packets])
        src_ts = np.concatenate([p.ts for p in packets])
        row_src_off = np.concatenate([[0], np.cumsum(lens)])
        dst = (np.arange(len(src_keys), dtype=np.int64)
               - np.repeat(row_src_off[:-1], lens)
               + np.repeat(row_blk_off[:-1] * blk, lens))
        keys[dst] = src_keys
        vals[dst] = src_vals
        ts[dst] = src_ts
        block_frag = np.full(nb, max(n_rows - 1, 0), np.int32)
        block_frag[:nb_live] = np.repeat(np.arange(n_rows, dtype=np.int32),
                                         nblk)
        sp.set_metadata(packets=len(src_keys), slots=p_tot,
                        slots_live=nb_live * blk)
    return keys, vals, ts, block_frag


def build_params(fragments: Dict[int, FragmentConfig], epoch: int,
                 ns: Dict[int, int],
                 frag_order: Sequence[int]) -> np.ndarray:
    """Per-row int32 parameter table for the fleet kernel.

    For cs/cms fleets: one row per fragment.  For UnivMon fleets every
    level is a *virtual fragment row* — fragment ``i`` owns rows
    ``[i*L, (i+1)*L)``, each carrying the level-mixed column/sign seeds
    (``level_seed_mix``, the same derivation the loop path and the query
    plane use) plus its ``PARAM_LEVEL``.  ``PARAM_MIT`` marks §4.4
    mitigation-enabled rows.
    """
    from ..kernels.sketch_update import fleet as FK

    n_levels = max((cfg.n_levels for cfg in fragments.values()
                    if cfg.kind == "um"), default=1)
    params = np.zeros((len(frag_order) * n_levels, FK.N_PARAMS), np.int32)
    for i, sw in enumerate(frag_order):
        cfg = fragments[sw]
        n = int(ns[sw])
        assert n & (n - 1) == 0, f"n_sub must be a power of two, got {n}"
        col = frag_seed(cfg.frag_id, epoch, _ROLE_COL, cfg.base_seed)
        sgn = frag_seed(cfg.frag_id, epoch, _ROLE_SIGN, cfg.base_seed)
        sub = frag_seed(cfg.frag_id, epoch, _ROLE_SUB, cfg.base_seed)
        for lvl in range(n_levels):
            r = i * n_levels + lvl
            if cfg.kind == "um":
                params[r, FK.PARAM_COL_SEED] = level_seed_mix(col, lvl)
                params[r, FK.PARAM_SIGN_SEED] = level_seed_mix(sgn, lvl)
            else:
                params[r, FK.PARAM_COL_SEED] = col
                params[r, FK.PARAM_SIGN_SEED] = sgn
            params[r, FK.PARAM_SUB_SEED] = sub
            params[r, FK.PARAM_WIDTH] = cfg.width
            params[r, FK.PARAM_N_SUB] = n
            params[r, FK.PARAM_LOG2_N_SUB] = n.bit_length() - 1
            params[r, FK.PARAM_LEVEL] = lvl
            params[r, FK.PARAM_MIT] = int(cfg.mitigation)
    return params


@functools.partial(jax.jit, static_argnums=2)
def _assemble_groups(outs, rows, shape):
    """Place every n_sub group's launch into one zero window stack of
    ``shape`` (group ``g`` fills rows ``rows[g]``, its own subepoch and
    column extents): one compiled program per window, where eager
    ``.at[].set`` compiles about ten small programs per group."""
    out = jnp.zeros(shape, jnp.float32)
    for r, o in zip(rows, outs):
        out = out.at[r, :o.shape[1], :o.shape[2]].set(o)
    return out


def dispatch_ragged_grouped(params: np.ndarray,
                            packets: Sequence[FleetPacket], *,
                            n_sub_max: int, width_max: int, log2_te: int,
                            signed: bool, blk: int = CSR_BLK,
                            w_blk: Optional[int] = None,
                            interpret="auto", value_mode: str = "auto",
                            n_levels: int = 1,
                            with_mitigation: bool = False):
    """Ragged CSR dispatch with fragments *grouped by subepoch count*.

    The kernel's lhs row count is ``n_sub_max * w_blk/LANE`` for every
    fragment in a launch, so one fragment running at ``n_sub = 16``
    makes every other fragment pay 16 subepoch rows of MXU work.
    Equalization (§4.2) deliberately spreads ``n`` across the fleet, so
    that padding is the common case, not the corner.  Grouping rows by
    their exact ``n_sub`` (and the group's own width ceiling) removes
    ALL row padding at the cost of <= log2(N_MAX) launches per dispatch
    instead of one — still O(1) in fleet size, and each launch is
    smaller.  Counters are bit-identical to the single-launch path
    (grouping only changes *which* zero rows are materialized).

    ``params`` rows are (epoch, fragment[, level]) tuples, epoch-major
    (``n_levels`` consecutive virtual level rows per fragment for
    UnivMon fleets), with the per-fragment ``n_sub``/``width`` columns
    identical across epochs and levels (``ns`` frozen — the
    ``run_window`` contract).  Returns the stacked device-resident
    ``(n_rows, n_sub_max, width_max)`` f32 counters, assembled from the
    groups' launches by one program (``_assemble_groups``).
    """
    from ..kernels.sketch_update import fleet as FK

    e_count = len(packets)
    n_frags = packets[0].n_frags
    L = n_levels
    n_rows = params.shape[0]
    assert n_rows == e_count * n_frags * L
    nsub_f = params[:n_frags * L:L, FK.PARAM_N_SUB].astype(np.int64)
    width_f = params[:n_frags * L:L, FK.PARAM_WIDTH].astype(np.int64)
    assert (params[:, FK.PARAM_N_SUB].reshape(e_count, n_frags, L)
            == nsub_f[None, :, None]).all(), \
        "grouped dispatch requires ns frozen"
    # widths must be frozen too: each group's launch sizes its output to
    # the epoch-0 group width, so a later-epoch growth would silently
    # drop columns >= w_g instead of erroring.
    assert (params[:, FK.PARAM_WIDTH].reshape(e_count, n_frags, L)
            == width_f[None, :, None]).all(), \
        "grouped dispatch requires widths frozen"

    kw = dict(log2_te=log2_te, signed=signed, blk=blk, w_blk=w_blk,
              interpret=interpret, value_mode=value_mode, n_levels=L,
              with_mitigation=with_mitigation)
    groups = [np.flatnonzero(nsub_f == n) for n in np.unique(nsub_f)]
    outs, out_rows = [], []
    for frag_idx in groups:
        n_g = int(nsub_f[frag_idx[0]])
        w_g = int(width_f[frag_idx].max(initial=4))
        # all L level rows of each group fragment, epoch-major — aligned
        # with the packet rows pack_csr emits for the selected segments
        rows = ((np.arange(e_count)[:, None] * n_frags
                 + frag_idx[None, :]).ravel()[:, None] * L
                + np.arange(L)[None, :]).ravel()
        with obs.span("fleet.select"):
            selected = [p.select(frag_idx) for p in packets]
        keys, vals, ts, block_frag = pack_csr(selected, blk)
        out_g = _launch_ragged(keys, vals, ts, params[rows], block_frag,
                               n_sub_max=n_g, width_max=w_g, **kw)
        if len(groups) == 1 and n_g == n_sub_max and w_g == width_max:
            return out_g
        outs.append(out_g)
        out_rows.append(rows.astype(np.int32))
    with obs.span("fleet.launch",
                  h2d_bytes=sum(r.nbytes for r in out_rows)):
        return _assemble_groups(tuple(outs), tuple(out_rows),
                                (n_rows, n_sub_max, width_max))


def _launch_ragged(keys, vals, ts, params, block_frag, **kw):
    """``fleet_update_ragged`` under a ``fleet.launch`` span that counts
    the bytes of its host arguments."""
    from ..kernels.sketch_update import fleet as FK

    h2d = sum(a.nbytes for a in (keys, vals, ts, params, block_frag))
    with obs.span("fleet.launch", h2d_bytes=h2d):
        return FK.fleet_update_ragged(keys, vals, ts, params, block_frag,
                                      **kw)


class _WindowBuffer:
    """Device-resident stacked counters for one epoch window.

    Holds the raw ``(E, F, n_sub_max, width_max)`` f32 device array; the
    host transfer + int64 conversion happens exactly once, on first
    ``host()`` call, after which the device buffer is released.  While
    the buffer is still ``resident``, ``device()`` exposes the stack to
    the batched on-device query plane (``kernels.sketch_query``) — point
    and window queries then never trigger the transfer at all.
    """

    def __init__(self, dev, shape: Tuple[int, ...],
                 logical_rows: Optional[int] = None):
        self._dev = dev
        self._shape = shape
        # Mesh-sharded stacks carry trailing pad rows (fragments padded
        # so rows divide the switch axis); ``host()`` slices the pad off
        # so the record plane and host oracle only ever see real rows.
        self._rows = logical_rows
        self._host: Optional[np.ndarray] = None

    @property
    def resident(self) -> bool:
        """True while the counters have not been transferred to host."""
        return self._dev is not None

    def device(self):
        """The still-resident ``(E, F, n_sub_max, width_max)`` f32 stack
        as a jax array (None once transferred)."""
        if self._dev is None:
            return None
        if tuple(self._dev.shape) != tuple(self._shape):
            # A mesh-sharded stack already has the right shape and must
            # NOT be reshaped (that would drop its NamedSharding).
            self._dev = self._dev.reshape(self._shape)
        return self._dev

    def host(self) -> np.ndarray:
        if self._host is None:
            arr = (np.asarray(self._dev).astype(np.int64)
                   .reshape(self._shape))
            if self._rows is not None and self._rows != self._shape[1]:
                arr = np.ascontiguousarray(arr[:, :self._rows])
            self._host = arr
            self._dev = None
        return self._host

    def epoch_view(self, e_idx: int) -> np.ndarray:
        """Host copy/view of one epoch's (R, S, W) slice without forcing
        the full-window transfer while still resident."""
        if self.resident:
            return np.asarray(self.device()[e_idx])
        return self.host()[e_idx]

    def patch(self, e_idx: int, row_lo: int, row_hi: int,
              counters: np.ndarray) -> None:
        """Overwrite rows ``[row_lo, row_hi)`` of one epoch with exact
        integer counters (XOR-parity recovery): patches the resident
        device array, or the already-transferred host copy *in place* so
        every existing record-plane view observes the reconstruction."""
        if self.resident:
            self._dev = self.device().at[e_idx, row_lo:row_hi].set(
                jnp.asarray(counters, jnp.float32))
        else:
            self._host[e_idx, row_lo:row_hi] = np.asarray(counters,
                                                          np.int64)


class WindowRecords(Mapping):
    """Lazy ``{switch: EpochRecords}`` view over one epoch of a window.

    The query plane consumes ``records[epoch][sw]``; materializing the
    records triggers the window's single host transfer (shared through
    ``_WindowBuffer``) and builds counters as *views* of the window
    stack — no per-fragment copies.  Epochs nobody queries never leave
    the device.
    """

    def __init__(self, buf: _WindowBuffer, e_idx: int, epoch: int,
                 fragments: Dict[int, FragmentConfig],
                 frag_order: Tuple[int, ...], n_arr: np.ndarray,
                 n_levels: int = 1):
        self._buf = buf
        self._e = e_idx
        self._epoch = epoch
        self._fragments = fragments
        self._order = frag_order
        self._n = n_arr
        self._levels = n_levels
        self._recs: Optional[Dict[int, EpochRecords]] = None

    def _materialize(self) -> Dict[int, EpochRecords]:
        if self._recs is None:
            stack = self._buf.host()[self._e]
            L = self._levels
            self._recs = {}
            for i, sw in enumerate(self._order):
                cfg = self._fragments[sw]
                n = int(self._n[i])
                counters = (stack[i * L:(i + 1) * L, :n, :cfg.width]
                            if cfg.kind == "um"
                            else stack[i, :n, :cfg.width])
                self._recs[sw] = EpochRecords(
                    cfg.frag_id, self._epoch, n, counters, cfg.kind,
                    cfg.mitigation, cfg.base_seed)
        return self._recs

    def __getitem__(self, sw: int) -> EpochRecords:
        return self._materialize()[sw]

    def __iter__(self):
        return iter(self._order)

    def __len__(self) -> int:
        return len(self._order)

    def __contains__(self, sw) -> bool:      # avoid materializing on `in`
        return sw in self._fragments


class FleetEpochRunner:
    """Batched replacement for the per-switch loop in ``run_epoch``.

    Holds the fleet's static configuration, packs each epoch's streams
    into the ragged CSR layout (``layout="dense"`` keeps the PR-1
    rectangle as an oracle), dispatches one ``fleet_update_ragged``, and
    unpacks ``EpochRecords`` + PEBs.  ``run_window`` batches E epochs
    into one super-dispatch with frozen ``ns`` and device-resident
    counters; window epochs are queryable via
    ``point_query``/``window_query`` straight from the resident device
    stack, no retention flag needed.  ``keep_stacked=True`` additionally
    retains per-epoch *host* stacks from ``run_epoch`` so the batched
    query ops also cover per-epoch dispatches (for window epochs, host
    stacks are cached lazily on first host-path access —
    ``run_window`` itself never forces the transfer).  Window stacks
    stay device-resident until the record plane or a host-path query
    materializes them; on accelerator deployments, materialize windows
    you are finished querying to release their HBM.
    ``interpret="auto"`` (default) compiles on
    TPU and interprets on CPU; ``value_mode="auto"`` picks the cheapest
    exact bf16/f32 contraction path per dispatch from the packed values
    (all modes are bit-identical — see kernels/sketch_update/kernel.py);
    ``w_blk=None`` defers to ``kernel.select_geometry``.

    UnivMon fleets (``kind="um"``) run every level as a virtual
    fragment row (homogeneous ``n_levels``/``level_seed`` required;
    the stacked outputs, ``_params_log`` and the query plane all live
    in row space — ``n_levels`` rows per fragment), and §4.4
    mitigation rides a per-row param flag + the folded single-hop ts
    bit — both bit-identical to the loop backend
    (tests/test_univmon_fleet.py).  ``layout="dense"`` remains a
    cs/cms-only oracle.
    """

    def __init__(self, fragments: Dict[int, FragmentConfig], log2_te: int,
                 *, blk: int = CSR_BLK, w_blk: Optional[int] = None,
                 interpret="auto", keep_stacked: bool = False,
                 layout: str = "ragged", value_mode: str = "auto",
                 group_by_n_sub: bool = True,
                 parity_groups: Optional[Sequence[Sequence[int]]] = None,
                 mesh=None):
        from ..kernels.sketch_update.kernel import (LVL_FIELD_MASK,
                                                    LVL_SHIFT, SH_SHIFT)

        if layout not in ("ragged", "dense"):
            raise ValueError(f"unknown layout {layout!r}")
        kinds = {cfg.kind for cfg in fragments.values()}
        if kinds - {"cs", "cms", "um"} or len(kinds) > 1:
            raise ValueError(
                f"fleet backend supports a homogeneous cs, cms or um "
                f"fleet, got {sorted(kinds)}; use backend='loop' for "
                "mixed kinds")
        self.fragments = fragments
        self.kind = next(iter(kinds)) if kinds else "cms"
        self.mitigation = any(cfg.mitigation for cfg in fragments.values())
        if self.kind == "um":
            levels = {cfg.n_levels for cfg in fragments.values()}
            seeds = {cfg.level_seed for cfg in fragments.values()}
            if len(levels) > 1 or len(seeds) > 1:
                raise ValueError(
                    "fleet backend requires a homogeneous UnivMon fleet "
                    f"(one n_levels/level_seed), got n_levels={sorted(levels)}"
                    f", level_seed={sorted(seeds)}")
            self.n_levels = levels.pop()
            self.level_seed = seeds.pop()
            if self.n_levels > LVL_FIELD_MASK + 1:
                raise ValueError(
                    f"fleet UnivMon supports n_levels <= "
                    f"{LVL_FIELD_MASK + 1}, got {self.n_levels}")
            if log2_te > LVL_SHIFT:
                raise ValueError(
                    f"fleet UnivMon requires log2_te <= {LVL_SHIFT} (the "
                    "level id rides the high ts bits), got "
                    f"{log2_te}")
        else:
            self.n_levels = 1
            self.level_seed = 0
        if self.mitigation and log2_te > SH_SHIFT:
            raise ValueError(
                f"fleet §4.4 mitigation requires log2_te <= {SH_SHIFT}, "
                f"got {log2_te}")
        if layout == "dense" and (self.n_levels > 1 or self.mitigation):
            raise ValueError(
                "layout='dense' (the PR-1 oracle rectangle) supports "
                "cs/cms without mitigation only; use the default "
                "layout='ragged'")
        self.log2_te = log2_te
        self.blk = blk
        self.w_blk = w_blk
        self.interpret = interpret
        self.keep_stacked = keep_stacked
        self.layout = layout
        self.value_mode = value_mode
        self.group_by_n_sub = group_by_n_sub
        self.frag_order: Tuple[int, ...] = tuple(sorted(fragments))
        self.widths = np.array([fragments[sw].width
                                for sw in self.frag_order], np.int64)
        # Per-*row* views (n_levels rows per fragment for UnivMon): the
        # stacked outputs, the params log, and the query plane all
        # operate in row space.
        self.row_widths = np.repeat(self.widths, self.n_levels)
        self.row_levels = np.tile(np.arange(self.n_levels),
                                  len(self.frag_order))
        self.stacked: Dict[int, np.ndarray] = {}
        self._params_log: Dict[int, np.ndarray] = {}
        # epoch -> (window buffer, epoch index within the window); filled
        # by run_window so queries can run on the still-resident stack.
        # The buffers are the same objects the returned WindowRecords
        # hold, so this registry does not extend their lifetime for
        # systems that retain records (DiSketchSystem always does).
        self._window_bufs: Dict[int, Tuple[_WindowBuffer, int]] = {}
        # --- fragment liveness under churn ------------------------------
        # epoch -> (n_rows,) bool row liveness; an absent entry means
        # every row is live (the no-failure fast path stays untouched).
        self._row_live: Dict[int, np.ndarray] = {}
        # epoch -> set of frag_order positions whose counters were lost
        # (reclaimed before the window export) — maskable, and
        # recoverable from parity while a single loss per group.
        self._lost: Dict[int, set] = {}
        # epoch -> set of frag_order positions staged by the export
        # plane but not yet delivered (runtime/export.py): their rows
        # are zeroed + masked like dead cells, but tracked in their own
        # domain — they are *in flight*, not dead, and flip back live
        # when the cell's export message arrives (``deliver_cell``).
        self._unexported: Dict[int, set] = {}
        # epoch -> per-group (n_levels, n_sub_max, width_max) int32 XOR
        # parity over the group members' rows (computed from the same
        # window dispatch, before lost cells are zeroed).
        self._parity: Dict[int, List[np.ndarray]] = {}
        self._frag_pos = {sw: i for i, sw in enumerate(self.frag_order)}
        self.parity_groups: Optional[List[np.ndarray]] = None
        self._group_of: Dict[int, int] = {}
        if parity_groups is not None:
            self.parity_groups = []
            for gi, group in enumerate(parity_groups):
                idx = []
                for sw in group:
                    if sw not in self._frag_pos:
                        raise ValueError(
                            f"parity group switch {sw} is not in the fleet")
                    i = self._frag_pos[sw]
                    if i in self._group_of:
                        raise ValueError(
                            f"switch {sw} appears in more than one parity "
                            "group")
                    self._group_of[i] = gi
                    idx.append(i)
                self.parity_groups.append(np.asarray(idx, np.int64))
        # --- device-mesh sharding (docs/sharding.md) --------------------
        # The fleet shards over contiguous *fragment* blocks of a 1-D
        # "switch" mesh axis: each shard packs + dispatches only its own
        # fragments' packets (update stays fully local), the window
        # stack is one row-sharded global array, and queries all_gather
        # only the gathered counter slices (kernels.sketch_query).
        self.mesh = mesh
        self.n_shards = 1
        self._frags_per_shard: Optional[int] = None
        self._shard_frag_bounds: Optional[List[Tuple[int, int]]] = None
        if mesh is not None:
            if "switch" not in mesh.axis_names:
                raise ValueError(
                    "fleet mesh needs a 'switch' axis, got "
                    f"{mesh.axis_names}")
            if layout == "dense":
                raise ValueError(
                    "mesh sharding requires layout='ragged' (the dense "
                    "rectangle is a single-device oracle)")
            self.n_shards = int(mesh.shape["switch"])
            n_frags = len(self.frag_order)
            f_pad = -(-max(n_frags, 1) // self.n_shards) * self.n_shards
            self._frags_per_shard = f_pad // self.n_shards
            self._shard_frag_bounds = [
                (s * self._frags_per_shard,
                 min((s + 1) * self._frags_per_shard, n_frags))
                for s in range(self.n_shards)]
            if self.parity_groups is not None:
                for gi, g in enumerate(self.parity_groups):
                    shards = {int(i) // self._frags_per_shard for i in g}
                    if len(shards) > 1:
                        raise ValueError(
                            f"parity group {gi} spans mesh shards "
                            f"{sorted(shards)}: XOR recovery reads whole "
                            "group rows, so groups must be shard-local "
                            "under a device mesh (docs/sharding.md)")
        # Observability accounting of the last window query (stamped by
        # ``_liveness_sels`` on every query entry point): how many of
        # the queried epochs had a live on-path fragment, and the
        # blind-epoch extrapolation scale that was applied.
        self.last_observability: Optional[Dict] = None
        # Device gather/merge launches of every window query so far.
        self.query_launches = 0

    # Exactness bound.  Counters are f32 accumulations: exact while
    # every intermediate magnitude stays below 2^24.  For unsigned (cms)
    # counters the final value is the peak, so a cheap output check
    # suffices (``_check_output_peak``); for signed (cs) counters
    # cancellation can hide an inexact intermediate peak, so bound it by
    # the only sound input-side quantity: the fragment's total |value|
    # mass (``_check_input_mass``).

    def _check_input_mass(self, packets: Sequence[FleetPacket]) -> None:
        # um levels are signed CS rows, each seeing a subset of the
        # fragment's stream, so the per-fragment mass bound covers them.
        if self.kind not in ("cs", "um"):
            return
        for packet in packets:
            if not len(packet.values):
                continue
            cum = np.concatenate([[0], np.cumsum(np.abs(packet.values))])
            seg_mass = cum[packet.offsets[1:]] - cum[packet.offsets[:-1]]
            if seg_mass.max(initial=0) >= 2 ** 24:
                raise OverflowError(
                    f"per-fragment |value| mass {seg_mass.max():.3g} "
                    "exceeds the f32 exact-integer range (2^24); use "
                    "backend='loop' or shorten the epoch")

    @staticmethod
    def _check_output_peak(peak: float) -> None:
        # Shared with the single-fragment wrapper (ops.sketch_update):
        # one exactness contract, enforced everywhere.
        from ..kernels.sketch_update.kernel import check_output_peak

        check_output_peak(peak)

    def _dispatch(self, params: np.ndarray, packets: Sequence[FleetPacket],
                  n_sub_max: int, width_max: int):
        """One device launch over the param table's rows; returns the
        still-on-device (n_rows, n_sub_max, width_max) f32 stack."""
        from ..kernels.sketch_update import fleet as FK

        # Fold per-packet UnivMon level ids / §4.4 flags into the high
        # ts bits (no-op for plain cs/cms fleets — the cached epoch
        # packets are shared across systems and must stay untouched).
        with obs.span("fleet.prepare"):
            packets = [fold_packet_flags(p, self.log2_te,
                                         n_levels=self.n_levels,
                                         level_seed=self.level_seed,
                                         mitigation=self.mitigation)
                       for p in packets]
        kw = dict(n_sub_max=n_sub_max, width_max=width_max,
                  log2_te=self.log2_te,
                  signed=self.kind in ("cs", "um"),
                  blk=self.blk, w_blk=self.w_blk, interpret=self.interpret,
                  value_mode=self.value_mode)
        if self.layout == "dense":
            if len(packets) != 1:
                raise ValueError("dense layout is per-epoch only; "
                                 "window dispatch requires layout='ragged'")
            keys, vals, ts = packets[0].densify(self.blk)
            return FK.fleet_update(keys, vals, ts, params, **kw)
        kw.update(n_levels=self.n_levels, with_mitigation=self.mitigation)
        if self.group_by_n_sub:
            del kw["n_sub_max"], kw["width_max"]
            return dispatch_ragged_grouped(
                params, packets, n_sub_max=n_sub_max, width_max=width_max,
                **kw)
        keys, vals, ts, block_frag = pack_csr(packets, self.blk)
        return _launch_ragged(keys, vals, ts, params, block_frag, **kw)

    # --- mesh-sharded dispatch (docs/sharding.md) ------------------------

    def _shard_dispatch_blocks(self, params: np.ndarray,
                               packets: Sequence[FleetPacket],
                               n_sub_max: int, width_max: int):
        """Yield ``(frag_lo, frag_hi, block)`` per non-empty shard, with
        ``block`` the shard's ``(E, (hi-lo)*L, S, W)`` f32 counters.

        Packets are routed at pack time (``FleetPacket.select`` of the
        shard's contiguous fragment positions) and each shard runs the
        ordinary grouped/flag-folding dispatch over its own rows only —
        per-row counters are bit-identical to the single-device launch
        by the same argument as ``dispatch_ragged_grouped``: a smaller
        launch only changes *which* zero rows/columns are materialized,
        never the hash arithmetic of a real row.
        """
        e_count = len(packets)
        n_frags = len(self.frag_order)
        L = self.n_levels
        for lo, hi in self._shard_frag_bounds:
            if lo >= hi:
                continue
            idx = np.arange(lo, hi)
            rows = ((np.arange(e_count)[:, None] * n_frags
                     + idx[None, :]).ravel()[:, None] * L
                    + np.arange(L)[None, :]).ravel()
            sub = [p.select(idx) for p in packets]
            blk = np.asarray(self._dispatch(params[rows], sub,
                                            n_sub_max, width_max),
                             np.float32)
            yield lo, hi, blk.reshape(e_count, (hi - lo) * L,
                                      n_sub_max, width_max)

    def _dispatch_mesh_host(self, params: np.ndarray,
                            packets: Sequence[FleetPacket],
                            n_sub_max: int, width_max: int) -> np.ndarray:
        """Per-epoch mesh leg: shard-local dispatches concatenated back
        to one host ``(n_rows, S, W)`` stack (``run_epoch`` is the
        host-centric path — per-epoch records materialize immediately,
        so there is nothing to keep sharded)."""
        e_count = len(packets)
        L = self.n_levels
        rows_per_epoch = len(self.frag_order) * L
        out = np.zeros((e_count, rows_per_epoch, n_sub_max, width_max),
                       np.float32)
        for lo, hi, blk in self._shard_dispatch_blocks(
                params, packets, n_sub_max, width_max):
            out[:, lo * L:hi * L] = blk
        return out.reshape(e_count * rows_per_epoch, n_sub_max, width_max)

    def _assemble_sharded(self, blocks: List[np.ndarray], e_count: int,
                          n_sub_max: int, width_max: int):
        """Commit per-shard blocks to their mesh devices as ONE global
        row-sharded ``(E, R_pad, S, W)`` array (zero rows pad the last /
        empty shards up to ``frags_per_shard``).  Built with
        ``make_array_from_single_device_arrays`` so no global host
        rectangle beyond the per-shard blocks is ever materialized."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        L = self.n_levels
        rps = self._frags_per_shard * L
        shape = (e_count, self.n_shards * rps, n_sub_max, width_max)
        sharding = NamedSharding(self.mesh, P(None, "switch", None, None))
        padded = []
        for blk in blocks:
            if blk.shape[1] != rps:
                blk = np.pad(blk, ((0, 0), (0, rps - blk.shape[1]),
                                   (0, 0), (0, 0)))
            padded.append(np.ascontiguousarray(blk, np.float32))
        arrays = []
        for d, idx in sharding.addressable_devices_indices_map(
                shape).items():
            s = (idx[1].start or 0) // rps
            arrays.append(jax.device_put(padded[s], d))
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        arrays)

    def _run_window_mesh(self, params: np.ndarray,
                         packets: Sequence[FleetPacket],
                         lost_sets: Sequence[set], n_arr: np.ndarray,
                         e_count: int, n_sub_max: int, width_max: int):
        """Mesh leg of ``run_window``: shard-local dispatch, with the
        peak / §4.2 PEBs / XOR parity / lost-row zeroing all computed on
        the per-shard blocks BEFORE the global sharded stack is
        assembled — nothing row-global ever crosses a device boundary.
        Returns ``(buf, pebs_all, parity_by_epoch, peak)``."""
        L = self.n_levels
        n_frags = len(self.frag_order)
        rows_per_epoch = n_frags * L
        blocks = [np.zeros((e_count, 0, n_sub_max, width_max), np.float32)
                  for _ in range(self.n_shards)]
        peak = 0.0
        pebs_all = np.zeros((e_count, n_frags))
        for lo, hi, blk in self._shard_dispatch_blocks(
                params, packets, n_sub_max, width_max):
            s = lo // self._frags_per_shard
            peak = max(peak, float(np.abs(blk).max(initial=0.0)))
            # §4.2 PEBs from the shard's level-0 rows (same formula as
            # the single-device path, evaluated per shard block).
            flat = blk.reshape(e_count * (hi - lo) * L,
                               n_sub_max, width_max)
            pebs_all[:, lo:hi] = np.asarray(equalize.peb_fleet_device(
                flat[::L], np.tile(n_arr[lo:hi], e_count),
                np.tile(self.widths[lo:hi], e_count),
                self.kind)).reshape(e_count, hi - lo)
            blocks[s] = blk
        # XOR parity per (epoch, group) before zeroing lost rows; groups
        # are shard-local (enforced at construction), so each reads one
        # shard's block only.
        parity_by_epoch = None
        if self.parity_groups is not None:
            per_group = []
            for g in self.parity_groups:
                s = int(g[0]) // self._frags_per_shard
                lo = self._shard_frag_bounds[s][0]
                acc = None
                for i in g:
                    j = int(i) - lo
                    cell = blocks[s][:, j * L:(j + 1) * L].astype(np.int32)
                    acc = cell if acc is None else acc ^ cell
                per_group.append(acc)               # (E, L, S, W) int32
            parity_by_epoch = [[pg[e] for pg in per_group]
                               for e in range(e_count)]
        for e, lost in enumerate(lost_sets):
            for sw in lost:
                i = self._frag_pos[sw]
                s = i // self._frags_per_shard
                j = i - self._shard_frag_bounds[s][0]
                if not blocks[s].flags.writeable:
                    # np.asarray of a device output is a read-only view
                    blocks[s] = blocks[s].copy()
                blocks[s][e, j * L:(j + 1) * L] = 0.0
        out = self._assemble_sharded(blocks, e_count, n_sub_max, width_max)
        buf = _WindowBuffer(
            out, (e_count, self._frags_per_shard * self.n_shards * L,
                  n_sub_max, width_max),
            logical_rows=rows_per_epoch)
        return buf, pebs_all, parity_by_epoch, peak

    def refresh_widths(self) -> None:
        """Recompute the cached width vectors after a resource-reclaim
        shrink replaced a ``FragmentConfig``.  Past epochs are
        unaffected: queries read their hash moduli from the per-epoch
        parameter tables, which are immutable once built."""
        self.widths = np.array([self.fragments[sw].width
                                for sw in self.frag_order], np.int64)
        self.row_widths = np.repeat(self.widths, self.n_levels)

    def run_epoch(self, epoch: int, ns: Dict[int, int],
                  streams: Dict[int, "SwitchStream"],
                  packet: Optional[FleetPacket] = None,
                  dead: Optional[Sequence[int]] = None,
                  ) -> Tuple[Dict[int, EpochRecords], Dict[int, float]]:
        from ..kernels.sketch_update.fleet import PARAM_N_SUB

        if packet is None:
            packet = pack_streams(streams, self.frag_order)
        assert packet.frag_order == self.frag_order
        # Dead switches keep forwarding but no longer hold sketch
        # memory: their segments become value-0 no-ops, their rows come
        # out exactly zero, and the liveness registry masks them from
        # every query path and from the §4.2 control (no record/PEB).
        dead_set = set(dead or ()) & set(self.frag_order)
        dead_pos = sorted(self._frag_pos[sw] for sw in dead_set)
        if dead_pos:
            packet = mask_fragment_values(packet, dead_pos)
        self._check_input_mass([packet])
        L = self.n_levels
        params = build_params(self.fragments, epoch, ns, self.frag_order)
        n_arr = params[::L, PARAM_N_SUB].astype(np.int64)  # per fragment
        n_sub_max = int(n_arr.max(initial=1))
        width_max = int(self.widths.max(initial=4))

        if self.mesh is None:
            stacked_f32 = np.asarray(self._dispatch(params, [packet],
                                                    n_sub_max, width_max))
        else:
            stacked_f32 = self._dispatch_mesh_host(params, [packet],
                                                   n_sub_max, width_max)
        self._check_output_peak(float(np.abs(stacked_f32).max(initial=0.0)))
        stacked = stacked_f32.astype(np.int64)

        # §4.2 PEBs come from level 0 for UnivMon (the ::L row slice is
        # exactly the level-0 rows; a no-op view for cs/cms).
        pebs_arr = equalize.peb_fleet(stacked[::L], n_arr, self.widths,
                                      self.kind)
        recs: Dict[int, EpochRecords] = {}
        pebs: Dict[int, float] = {}
        for i, sw in enumerate(self.frag_order):
            if sw in dead_set:
                continue      # no record, no PEB — matches the loop path
            cfg = self.fragments[sw]
            n = int(n_arr[i])
            counters = (stacked[i * L:(i + 1) * L, :n, :cfg.width].copy()
                        if cfg.kind == "um"
                        else stacked[i, :n, :cfg.width].copy())
            recs[sw] = EpochRecords(
                cfg.frag_id, epoch, n, counters, cfg.kind,
                cfg.mitigation, cfg.base_seed)
            pebs[sw] = float(pebs_arr[i])
        # A reprocessed epoch invalidates any window retention for it:
        # a stale resident buffer would silently answer queries with the
        # previous run's counters/seeds.
        self._window_bufs.pop(epoch, None)
        self._lost.pop(epoch, None)
        self._parity.pop(epoch, None)
        self._unexported.pop(epoch, None)
        if dead_pos:
            live = np.ones(len(self.frag_order) * L, bool)
            for i in dead_pos:
                live[i * L:(i + 1) * L] = False
            self._row_live[epoch] = live
        else:
            self._row_live.pop(epoch, None)
        if self.keep_stacked:
            self.stacked[epoch] = stacked
            self._params_log[epoch] = params
        else:
            self.stacked.pop(epoch, None)
            self._params_log.pop(epoch, None)
        return recs, pebs

    def run_window(self, epoch0: int, ns: Dict[int, int],
                   packets: Sequence[FleetPacket],
                   dead_by_epoch: Optional[Sequence[Sequence[int]]] = None,
                   lost_by_epoch: Optional[Sequence[Sequence[int]]] = None,
                   ) -> Tuple[List[WindowRecords], List[Dict[int, float]]]:
        """Epoch-window super-dispatch: E epochs x F fragments in ONE
        kernel launch (E*F virtual param rows), ``ns`` frozen for the
        window.

        Counters stay device-resident: only the overflow peak (one
        scalar) and the (E*F,) PEB vector cross the host boundary here;
        the full stack transfers lazily, once per window, when the query
        plane first touches a ``WindowRecords``.

        Churn plumbing (both optional, per-epoch switch-id sets):
        ``dead_by_epoch`` — switches holding no sketch memory during
        that epoch; their packets become value-0 no-ops and their rows
        are masked from queries/records/PEBs.  ``lost_by_epoch`` —
        switches that DID sketch the epoch but whose counters were
        reclaimed before the window export (a mid-window death loses its
        earlier in-window epochs): their rows are zeroed *after* the
        XOR parity of each configured group is computed, so a single
        loss per group per epoch stays exactly reconstructible
        (``recover``); until then the cells are masked like dead ones.
        """
        from ..kernels.sketch_update.fleet import PARAM_N_SUB

        with obs.span("fleet.run_window", epochs=len(packets)) as sp:
            e_count = len(packets)
            assert e_count >= 1
            for packet in packets:
                assert packet.frag_order == self.frag_order
            if self.layout != "ragged":
                raise ValueError("window dispatch requires layout='ragged'")
            fleet_set = set(self.frag_order)
            dead_sets = [set(d) & fleet_set for d in dead_by_epoch] \
                if dead_by_epoch is not None else [set()] * e_count
            lost_sets = [set(s) & fleet_set for s in lost_by_epoch] \
                if lost_by_epoch is not None else [set()] * e_count
            assert len(dead_sets) == e_count and len(lost_sets) == e_count
            n_frags = len(self.frag_order)
            L = self.n_levels
            rows_per_epoch = n_frags * L
            sp.set_metadata(rows=e_count * rows_per_epoch)
            with obs.span("fleet.prepare"):
                if any(dead_sets):
                    packets = [mask_fragment_values(
                        p, sorted(self._frag_pos[sw] for sw in dead))
                        for p, dead in zip(packets, dead_sets)]
                self._check_input_mass(packets)
                params = np.concatenate([
                    build_params(self.fragments, epoch0 + e, ns,
                                 self.frag_order)
                    for e in range(e_count)])
                n_arr = params[:rows_per_epoch:L, PARAM_N_SUB].astype(np.int64)
                n_sub_max = int(params[:, PARAM_N_SUB].max(initial=1))
                width_max = int(self.widths.max(initial=4))

            if self.mesh is not None:
                buf, pebs_all, parity_by_epoch, peak = self._run_window_mesh(
                    params, packets, lost_sets, n_arr, e_count,
                    n_sub_max, width_max)
                self._check_output_peak(peak)
            else:
                out = self._dispatch(params, packets, n_sub_max, width_max)
                peak = abs_peak(out) if out.size else 0.0
                with obs.span("fleet.sync"):
                    peak = float(peak)
                self._check_output_peak(peak)
                # §4.2 PEBs from the level-0 rows (::L is a no-op for
                # cs/cms) — computed before lost cells are zeroed (their
                # counters are genuine observations of epochs the switch did
                # sketch).
                pebs_dev = equalize.peb_fleet_device(
                    out[::L], np.tile(n_arr, e_count),
                    np.tile(self.widths, e_count), self.kind)
                with obs.span("fleet.sync"):
                    pebs_all = np.asarray(pebs_dev).reshape(e_count, n_frags)
                # XOR parity per (epoch, group) over the un-zeroed stack:
                # exact integers below 2^24 make the f32->int32 conversion
                # lossless, and XOR (unlike a sum) can neither overflow nor
                # round.
                parity_by_epoch = None
                if self.parity_groups is not None:
                    parity_by_epoch = self._window_parity(
                        out, e_count, rows_per_epoch, n_sub_max, width_max)
                if any(lost_sets):
                    rows = np.concatenate([
                        np.arange(i * L, (i + 1) * L) + e * rows_per_epoch
                        for e, lost in enumerate(lost_sets)
                        for i in sorted(self._frag_pos[sw] for sw in lost)]
                    ).astype(np.int32)
                    out = out.at[jnp.asarray(rows)].set(0.0)

                buf = _WindowBuffer(out, (e_count, rows_per_epoch, n_sub_max,
                                          width_max))
            with obs.span("fleet.records"):
                recs_list: List[WindowRecords] = []
                pebs_list: List[Dict[int, float]] = []
                # snapshot the config dict: a later shrink must not re-slice
                # this window's records with the new width
                frags_now = dict(self.fragments)
                for e in range(e_count):
                    ep = epoch0 + e
                    recs_list.append(WindowRecords(buf, e, ep, frags_now,
                                                   self.frag_order, n_arr,
                                                   n_levels=L))
                    pebs_list.append({sw: float(pebs_all[e, i])
                                      for i, sw in enumerate(self.frag_order)
                                      if sw not in dead_sets[e]})
                    # Point/window queries are served straight from the resident
                    # buffer (kernels.sketch_query) — no keep_stacked required,
                    # and no eager host() transfer: forcing the transfer here is
                    # exactly what window mode exists to avoid.  Host stacks
                    # materialize lazily (``_host_stack``) only if something
                    # transfers the buffer first.
                    self._window_bufs[ep] = (buf, e)
                    self._params_log[ep] = \
                        params[e * rows_per_epoch:(e + 1) * rows_per_epoch]
                    # drop any stale per-epoch retention from a previous run of
                    # the same epoch — its counters pair with the OLD seeds
                    self.stacked.pop(ep, None)
                    self._lost.pop(ep, None)
                    self._parity.pop(ep, None)
                    self._unexported.pop(ep, None)
                    if parity_by_epoch is not None:
                        self._parity[ep] = parity_by_epoch[e]
                    invalid = dead_sets[e] | lost_sets[e]
                    if invalid:
                        live = np.ones(rows_per_epoch, bool)
                        for sw in invalid:
                            i = self._frag_pos[sw]
                            live[i * L:(i + 1) * L] = False
                        self._row_live[ep] = live
                        self._lost[ep] = {self._frag_pos[sw]
                                          for sw in lost_sets[e]}
                    else:
                        self._row_live.pop(ep, None)
            return recs_list, pebs_list

    def _window_parity(self, out, e_count: int, rows_per_epoch: int,
                       n_sub_max: int, width_max: int,
                       ) -> List[List[np.ndarray]]:
        """Per-epoch, per-group XOR parity over the group members' rows
        of the (still possibly device-resident) window stack.  Returns
        ``[epoch][group] -> (n_levels, n_sub_max, width_max)`` int32 on
        host — total parity memory is one fragment-equivalent per group.
        Dead members' rows are exact zeros and XOR away, so the parity
        equation stays consistent for any liveness pattern."""
        L = self.n_levels
        a = out.reshape(e_count, rows_per_epoch, n_sub_max, width_max)
        per_group = []
        for g in self.parity_groups:
            acc = None
            for i in g:
                cell = a[:, i * L:(i + 1) * L].astype(jnp.int32)
                acc = cell if acc is None else acc ^ cell
            per_group.append(np.asarray(acc))   # (E, L, S, W) int32
        return [[pg[e] for pg in per_group] for e in range(e_count)]

    def point_query(self, epoch: int, keys: np.ndarray,
                    path: Optional[Sequence[int]] = None,
                    level: int = 0,
                    single_hop: bool = False,
                    failures: str = "mask") -> np.ndarray:
        """Batched epoch point-query over the retained stacked counters.

        ``path`` restricts the merge to the fragments the queried flows
        traverse (§4.3 Step 1); all queried keys must share the path.
        Omitting it merges every fleet fragment, which is only correct
        when flows traverse all of them (linear-path scenarios).
        ``level`` selects the UnivMon level row (ignored for cs/cms;
        level 0 — the full-stream level — answers frequency queries).
        ``single_hop`` applies the §4.4 second-subepoch average on
        mitigation-enabled fragments (all queried keys must share it,
        which they do per path group: single-hop == path length 1).
        ``failures`` is the churn query policy — see ``window_query``.
        """
        return self.window_query([epoch], keys, path=path, level=level,
                                 single_hop=single_hop, failures=failures)

    def has_device_window(self, epochs: Sequence[int]) -> bool:
        """True when every epoch's window stack is still device-resident,
        i.e. ``window_query`` will run entirely on device and transfer
        only the ``(K,)`` estimates."""
        return all(e in self._window_bufs
                   and self._window_bufs[e][0].resident for e in epochs)

    def _host_stack(self, epoch: int) -> np.ndarray:
        """Host counters for one retained epoch: the per-epoch
        ``keep_stacked`` copy, or the epoch's slice of an
        already-transferred window buffer."""
        stack = self.stacked.get(epoch)
        if stack is None:
            buf, e_idx = self._window_bufs[epoch]
            stack = buf.host()[e_idx]
            self.stacked[epoch] = stack
        return stack

    def frag_live(self, epoch: int) -> Optional[np.ndarray]:
        """(n_frags,) bool fragment liveness for a processed epoch, or
        None when no failure touched it (every fragment live)."""
        live = self._row_live.get(epoch)
        return None if live is None else live[::self.n_levels]

    # -- export-plane cell hooks (runtime/export.py) ---------------------
    # The durable export plane models collection as per-(epoch, switch)
    # *cells* of the retained window stack: ``cell_counters`` reads a
    # cell's exact payload, ``mark_unexported`` holds cells back (zero +
    # mask, own liveness domain) until their export message arrives, and
    # ``deliver_cell`` patches a delivered payload back in place and
    # flips the rows live — so late arrivals sharpen every subsequent
    # query through the ordinary ``failures="mask"`` machinery.

    def cell_counters(self, epoch: int, sw: int) -> np.ndarray:
        """One (epoch, fragment) cell of the retained window stack as an
        exact int32 copy — the export payload (lossless: counters are
        exact integers below 2^24)."""
        if epoch not in self._window_bufs:
            raise KeyError(f"epoch {epoch} has no retained window stack")
        buf, e_idx = self._window_bufs[epoch]
        i = self._frag_pos[sw]
        L = self.n_levels
        return (np.asarray(buf.epoch_view(e_idx)[i * L:(i + 1) * L])
                .astype(np.int32))

    def mark_unexported(self, epoch: int, sws: Sequence[int]) -> None:
        """Hold (epoch, switch) cells back from the query plane: zero
        their window-stack rows and mask them via the liveness registry.
        Deliberately NOT the ``_lost`` domain — that is parity's (a
        pending cell is in flight, not reclaimed)."""
        if epoch not in self._window_bufs:
            raise KeyError(f"epoch {epoch} has no retained window stack")
        buf, e_idx = self._window_bufs[epoch]
        L = self.n_levels
        live = self._row_live.get(epoch)
        if live is None:
            live = np.ones(len(self.frag_order) * L, bool)
            self._row_live[epoch] = live
        pend = self._unexported.setdefault(epoch, set())
        _, _, n_sub_max, width_max = buf._shape
        zeros = np.zeros((L, n_sub_max, width_max), np.int64)
        for sw in sws:
            i = self._frag_pos[sw]
            buf.patch(e_idx, i * L, (i + 1) * L, zeros)
            live[i * L:(i + 1) * L] = False
            pend.add(i)

    def deliver_cell(self, epoch: int, sw: int,
                     counters: np.ndarray) -> None:
        """Patch one delivered cell's exact integer counters back into
        the window stack and flip its rows live — the inverse of
        ``mark_unexported``.  Once every row of the epoch is live again
        the liveness entry is dropped entirely, restoring the
        no-failure fast path bit-identically."""
        buf, e_idx = self._window_bufs[epoch]
        i = self._frag_pos[sw]
        L = self.n_levels
        buf.patch(e_idx, i * L, (i + 1) * L,
                  np.asarray(counters).astype(np.int64))
        pend = self._unexported.get(epoch)
        if pend is not None:
            pend.discard(i)
            if not pend:
                del self._unexported[epoch]
        live = self._row_live.get(epoch)
        if live is not None:
            live[i * L:(i + 1) * L] = True
            if live.all():
                del self._row_live[epoch]

    def recoverable(self, epochs: Optional[Sequence[int]] = None,
                    ) -> Dict[int, List[int]]:
        """The lost cells XOR parity can reconstruct: {epoch: [switch]}.

        A lost (epoch, fragment) cell is recoverable iff the fragment
        belongs to a parity group, the epoch's parity was captured, and
        no OTHER member of its group is lost at that epoch (dead-all-
        epoch members hold exact-zero rows and XOR away, so they do not
        block recovery — only a second *loss* does)."""
        out: Dict[int, List[int]] = {}
        for e in (sorted(self._lost) if epochs is None else epochs):
            lost = self._lost.get(e)
            if not lost or e not in self._parity:
                continue
            for i in sorted(lost):
                gi = self._group_of.get(i)
                if gi is None:
                    continue
                if any(j != i and j in lost for j in self.parity_groups[gi]):
                    continue
                out.setdefault(e, []).append(self.frag_order[i])
        return out

    def recover(self, epochs: Optional[Sequence[int]] = None,
                ) -> Dict[int, List[int]]:
        """Reconstruct every recoverable lost cell from XOR parity and
        patch it back into the window stack, in place.

        For a lost fragment ``i`` of group ``G`` at epoch ``e``:
        ``C_i = parity[e][G] XOR (XOR of the surviving members' rows)``
        — exact (counters are exact integers; XOR neither overflows nor
        rounds), so the round trip is bit-identical to the counters the
        switch held before the reclaim.  Recovered rows flip back to
        live: subsequent masked queries and the record plane use the
        reconstruction as if the fragment had exported normally.
        Returns {epoch: [switch]} of what was actually recovered;
        unrecoverable cells (no group / double loss) stay masked.
        """
        recovered: Dict[int, List[int]] = {}
        L = self.n_levels
        for e, sws in self.recoverable(epochs).items():
            buf, e_idx = self._window_bufs[e]
            live = self._row_live[e]
            lost = self._lost[e]
            parity = self._parity[e]
            stack_e = buf.epoch_view(e_idx)     # (R, S, W) host
            patches = []
            for sw in sws:
                i = self._frag_pos[sw]
                gi = self._group_of[i]
                acc = parity[gi].copy()         # (L, S, W) int32
                for j in self.parity_groups[gi]:
                    if j != i:
                        acc ^= np.asarray(
                            stack_e[j * L:(j + 1) * L]).astype(np.int32)
                patches.append((i, acc))
            for i, counters in patches:
                buf.patch(e_idx, i * L, (i + 1) * L,
                          counters.astype(np.int64))
                live[i * L:(i + 1) * L] = True
                lost.discard(i)
                recovered.setdefault(e, []).append(self.frag_order[i])
        return recovered

    def _row_sel(self, path: Optional[Sequence[int]],
                 level: int) -> Optional[np.ndarray]:
        """(n_rows_per_epoch,) bool row mask: the §4.3 on-path fragment
        restriction intersected with the UnivMon level-row selection.
        None when every row participates (cs/cms, no path)."""
        if path is None and self.n_levels == 1:
            return None
        sel = np.ones(len(self.frag_order) * self.n_levels, bool)
        if path is not None:
            on_path = set(path)
            sel &= np.repeat(np.array([sw in on_path
                                       for sw in self.frag_order]),
                             self.n_levels)
        if self.n_levels > 1:
            sel &= self.row_levels == level
        return sel

    def _route_epochs(self, epochs: Sequence[int]):
        """Partition queried epochs between the device and host query
        paths — the single source of the retention check, the
        same-buffer grouping, and the device-side epoch gather, shared
        by every window-query entry point.

        Returns ``(device_groups, host_epochs)`` where each device
        group is ``(stack, epochs)`` with ``stack`` the still-resident
        (possibly epoch-gathered) device array for those epochs.
        """
        missing = [e for e in epochs
                   if e not in self.stacked and e not in self._window_bufs]
        if missing:
            raise KeyError(
                f"epochs {missing} not retained (process them with "
                "run_window, or construct with keep_stacked=True for "
                "per-epoch runs)")
        host_epochs: List[int] = []
        by_buf: Dict[int, Tuple[_WindowBuffer, List[int]]] = {}
        for e in epochs:
            ent = self._window_bufs.get(e)
            if ent is not None and ent[0].resident:
                by_buf.setdefault(id(ent[0]), (ent[0], []))[1].append(e)
            else:
                host_epochs.append(e)
        device_groups = []
        for buf, es in by_buf.values():
            stack = buf.device()
            idx = np.array([self._window_bufs[e][1] for e in es], np.int64)
            if len(idx) != stack.shape[0] \
                    or (idx != np.arange(len(idx))).any():
                stack = stack[idx]          # device-side epoch gather
            device_groups.append((stack, es))
        return device_groups, host_epochs

    def _liveness_sels(self, epochs: Sequence[int],
                       base: Optional[np.ndarray], failures: str):
        """Shared churn-masking front end for the window-query entry
        points: intersect the structural row selection with per-epoch
        liveness, drop epochs with zero on-path survivors (blind
        epochs), and return ``(epochs, sel_by_epoch, scale)``.

        ``sel_by_epoch`` is None when no queried epoch was touched by a
        failure (the original uniform-selection fast path).  ``scale``
        is the §4.3-style blind-spot extrapolation factor E/E_observable
        — unobservable epochs take the mean of the observable ones.
        Raises ``ValueError`` when the policy is unknown or every epoch
        is blind (the flow is unobservable under the failure schedule).
        """
        if failures not in ("oblivious", "mask", "recover"):
            raise ValueError(f"unknown failures policy {failures!r}; "
                             "expected 'oblivious', 'mask' or 'recover'")
        if failures == "recover":
            self.recover(epochs)
            failures = "mask"
        if failures != "mask" or not any(e in self._row_live
                                         for e in epochs):
            self.last_observability = {
                "epochs": len(list(epochs)),
                "observable_epochs": len(list(epochs)), "scale": 1.0}
            return list(epochs), None, 1.0
        n_rows = len(self.frag_order) * self.n_levels
        base_arr = np.ones(n_rows, bool) if base is None else base
        sel_by_e = {e: base_arr & live
                    if (live := self._row_live.get(e)) is not None
                    else base_arr
                    for e in epochs}
        obs = [e for e in epochs if sel_by_e[e].any()]
        if not obs:
            raise ValueError(
                "window query: no epoch in the window has a live "
                "on-path fragment — the flow is unobservable under the "
                "failure schedule")
        self.last_observability = {
            "epochs": len(list(epochs)), "observable_epochs": len(obs),
            "scale": len(epochs) / len(obs)}
        return obs, sel_by_e, len(epochs) / len(obs)

    def window_query(self, epochs: Sequence[int], keys: np.ndarray,
                     path: Optional[Sequence[int]] = None,
                     level: int = 0,
                     single_hop: bool = False,
                     failures: str = "mask") -> np.ndarray:
        """Batched point-query summed over a query window (O_Q = Sum(O))
        — the fleet twin of ``query.query_window(merge="fragment")``.

        Epochs processed through ``run_window`` are served **on device**
        while their window stack is still resident
        (``query.fleet_query_window_device``: hashes, the gather, and
        the §4.3 min/median merge all run next to the counters, and only
        the ``(K,)`` estimate vector crosses the host boundary).  Epochs
        whose counters already live on the host — per-epoch
        ``keep_stacked`` runs, or windows the record plane has
        materialized — go through the numpy oracle
        ``query.fleet_query_window``.  The two paths agree within f32
        rounding (a few ULPs) and may be mixed freely in one call.

        For UnivMon fleets ``level`` selects which virtual level rows
        answer (level 0 = frequency queries); ``single_hop`` enables the
        §4.4 second-subepoch average on mitigation rows (uniform per
        call — query_flows passes it per path group).

        ``failures`` is the churn query policy: ``"mask"`` (default)
        intersects the on-path selection with each epoch's fragment
        liveness — a dead/lost fragment never enters the merge, and
        blind epochs (zero on-path survivors) are extrapolated from the
        observable ones; ``"recover"`` first reconstructs recoverable
        lost cells from XOR parity (``recover``), then masks whatever
        remains; ``"oblivious"`` ignores liveness — the failure-unaware
        baseline whose min/median is poisoned by the dead rows' zeros.
        With no failures in the queried epochs all three are identical.
        """
        from . import query as Q

        with obs.span("query.prep"):
            keys = np.asarray(keys, np.uint32)
            base = self._row_sel(path, level)
            epochs, sel_by_e, scale = self._liveness_sels(epochs, base,
                                                          failures)
            device_groups, host_epochs = self._route_epochs(epochs)
        out = np.zeros(len(keys))
        if len(keys):
            self.query_launches += len(device_groups)
        for stack, es in device_groups:
            sel = base if sel_by_e is None else \
                np.stack([sel_by_e[e] for e in es])
            out += Q.fleet_query_window_device(
                stack, [self._params_log[e] for e in es], keys, self.kind,
                frag_sel=sel, single_hop=single_hop, mesh=self.mesh)
        if host_epochs:
            sel = base if sel_by_e is None else \
                [sel_by_e[e] for e in host_epochs]
            out += Q.fleet_query_window(
                [self._host_stack(e) for e in host_epochs],
                [self._params_log[e] for e in host_epochs],
                None, keys, self.kind, frag_sel=sel,
                single_hop=single_hop)
        return out * scale if scale != 1.0 else out

    def batches_paths(self, epochs: Sequence[int], failures: str) -> bool:
        """True when ``window_query_paths`` can answer a multi-path
        request over ``epochs``: every epoch device-resident, no mesh,
        and no churn mask touching a queried epoch (``failures`` other
        than ``"oblivious"`` and a liveness entry for one of them)."""
        return (self.mesh is None and self.has_device_window(epochs)
                and (failures == "oblivious"
                     or not any(e in self._row_live for e in epochs)))

    def window_query_paths(self, epochs: Sequence[int], keys: np.ndarray,
                           paths: Sequence[Sequence[int]],
                           path_id: np.ndarray,
                           level: int = 0) -> np.ndarray:
        """``window_query`` of keys on many paths in one batched call per
        resident stack and key chunk (``sketch_query.
        fleet_window_query_paths``): key ``i`` is merged over the rows of
        ``paths[path_id[i]]`` at UnivMon ``level``, with the §4.4 average
        where its path is single-hop.  Bit-identical to one
        ``window_query(path=p, single_hop=len(p) == 1)`` per path; only
        for requests ``batches_paths`` admits."""
        from ..kernels.sketch_query import (fleet_window_query_paths,
                                            key_chunk)

        with obs.span("query.prep"):
            keys = np.asarray(keys, np.uint32)
            on_path = [sorted({self._frag_pos[sw] for sw in p
                               if sw in self._frag_pos}) for p in paths]
            path_rows = np.full((len(paths), max(map(len, on_path),
                                                 default=1)), -1, np.int32)
            for j, frags in enumerate(on_path):
                path_rows[j, :len(frags)] = np.asarray(
                    frags, np.int32) * self.n_levels + level
            n = len(list(epochs))
            self.last_observability = {"epochs": n, "observable_epochs": n,
                                       "scale": 1.0}
            device_groups, host_epochs = self._route_epochs(epochs)
            assert not host_epochs, "window_query_paths needs resident epochs"
        if len(keys):
            self.query_launches += len(device_groups) * -(
                -len(keys) // key_chunk(len(keys)))
        return fleet_window_query_paths(
            [(stack, [self._params_log[e] for e in es])
             for stack, es in device_groups],
            keys, path_rows, path_id, self.kind,
            single_hop=np.array([len(p) == 1 for p in paths], bool))

    def um_level_window_query(self, epochs: Sequence[int],
                              keys: np.ndarray,
                              path: Optional[Sequence[int]] = None,
                              failures: str = "mask") -> np.ndarray:
        """All ``n_levels`` UnivMon Count-Sketch window estimates for a
        key batch in one batched call — the per-level inputs of the
        §6.2 G-sum/entropy estimators.

        Returns ``(n_levels, K)`` float64 ``merge="fragment"`` window
        estimates (level ``l``'s row is only meaningful for keys with
        ``level_of(key) >= l`` — the G-sum recursion masks the rest).
        Device-resident window epochs are answered by one jitted
        gather/merge over the still-resident stack
        (``query.um_fleet_query_window_device``); host-materialized
        epochs fall back to per-level numpy queries.  Both paths mix
        freely per epoch, as in ``window_query``; ``failures`` is the
        same churn query policy (liveness is per *fragment* — a dead
        switch masks all its level rows at once).
        """
        from . import query as Q

        assert self.kind == "um", "um_level_window_query is UnivMon-only"
        keys = np.asarray(keys, np.uint32)
        frag_sel = None
        if path is not None:
            on_path = set(path)
            frag_sel = np.array([sw in on_path for sw in self.frag_order])
        # Liveness intersection in ROW space (shared helper), projected
        # back to fragment space for the device um path — level rows of
        # one fragment are all-live or all-masked together.
        row_base = None if frag_sel is None \
            else np.repeat(frag_sel, self.n_levels)
        epochs, row_sel_by_e, scale = self._liveness_sels(
            epochs, row_base, failures)
        device_groups, host_epochs = self._route_epochs(epochs)
        out = np.zeros((self.n_levels, len(keys)))
        for stack, es in device_groups:
            sel = frag_sel if row_sel_by_e is None else \
                np.stack([row_sel_by_e[e][::self.n_levels] for e in es])
            out += Q.um_fleet_query_window_device(
                stack, [self._params_log[e] for e in es], keys,
                self.n_levels, frag_sel=sel, mesh=self.mesh)
        for level in range(self.n_levels) if host_epochs else ():
            lvl_rows = self.row_levels == level
            sel = self._row_sel(path, level) if row_sel_by_e is None else \
                [row_sel_by_e[e] & lvl_rows for e in host_epochs]
            out[level] += Q.fleet_query_window(
                [self._host_stack(e) for e in host_epochs],
                [self._params_log[e] for e in host_epochs],
                None, keys, "um", frag_sel=sel)
        return out * scale if scale != 1.0 else out
