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

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..kernels.sketch_query import MAX_FLAT, FlatStack
from ..kernels.sketch_update.kernel import MIN_BLK
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


@dataclass(frozen=True)
class LaunchGroup:
    """One subepoch group of a window's launches: its fragments (positions
    among the fragments laid out), their common ``n`` and the group's
    width (its widest fragment's)."""

    frags: np.ndarray
    n: int
    width: int


@dataclass(frozen=True)
class WindowLayout:
    """Where a window's counters lie in its flat f32 buffer.

    Each subepoch group's launch keeps its own ``(rows, n, width)``
    extent and the groups follow one another, so the counter of (epoch
    ``e``, row ``r``, subepoch ``s``, column ``c``) is
    ``flat[base[e, r] + s * stride[r] + c]``, with ``stride[r]`` the
    width of the row's group.  The L level rows of one (epoch, fragment)
    cell are consecutive.  A window's buffer is bounded by its groups'
    rectangles, never by E·F·n_max·w_max; offsets fit int32, since a
    buffer holds fewer than 2^31 counters (``MAX_FLAT``, checked by
    ``window_layout``).
    """

    base: np.ndarray            # (E, R) int64 cell offsets
    stride: np.ndarray          # (R,) int64 counters between subepochs
    ns: np.ndarray              # (R,) int64 subepoch counts
    widths: np.ndarray          # (R,) int64 columns
    size: int                   # counters of the buffer
    groups: Tuple[LaunchGroup, ...] = ()

    @property
    def counter_bytes(self) -> int:
        """Bytes of the window's own counters: 4 · Σ n·width."""
        return 4 * self.base.shape[0] * int((self.ns * self.widths).sum())

    def cell(self, e_idx: int, row_lo: int, row_hi: int):
        """Offset and ``(rows, n, stride)`` extent of rows
        ``[row_lo, row_hi)`` of one epoch — the level rows of one
        fragment, consecutive in the buffer."""
        return (int(self.base[e_idx, row_lo]),
                (row_hi - row_lo, int(self.ns[row_lo]),
                 int(self.stride[row_lo])))


def window_layout(ns_f: np.ndarray, widths_f: np.ndarray, e_count: int,
                  n_levels: int = 1) -> WindowLayout:
    """The layout of a window whose fragments (``ns_f``/``widths_f``, in
    fleet order) are launched grouped by subepoch count: one launch per
    distinct ``n``, in ascending ``n``, whose rows are (epoch, fragment,
    level) in that order (``dispatch_ragged_grouped``)."""
    L = n_levels
    ns_f = np.asarray(ns_f, np.int64)
    widths_f = np.asarray(widths_f, np.int64)
    base = np.zeros((e_count, len(ns_f) * L), np.int64)
    stride = np.zeros(len(ns_f) * L, np.int64)
    groups, off = [], 0
    for n in np.unique(ns_f):
        frags = np.flatnonzero(ns_f == n)
        w = int(widths_f[frags].max(initial=4))
        rows = (frags[:, None] * L + np.arange(L)).ravel()
        launch_row = np.arange(e_count * len(rows)).reshape(e_count, -1)
        base[:, rows] = off + launch_row * (int(n) * w)
        stride[rows] = w
        groups.append(LaunchGroup(frags, int(n), w))
        off += e_count * len(rows) * int(n) * w
    if off > MAX_FLAT:
        raise ValueError(f"a window buffer of {off} counters does not fit "
                         "int32 offsets; use a shorter window")
    return WindowLayout(base, stride, np.repeat(ns_f, L),
                        np.repeat(widths_f, L), off, tuple(groups))


def dispatch_ragged_grouped(params: np.ndarray,
                            packets: Sequence[FleetPacket],
                            layout: Optional[WindowLayout] = None, *,
                            frag_lo: int = 0, log2_te: int, signed: bool,
                            blk: int = CSR_BLK, w_blk: Optional[int] = None,
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
    ``run_window`` contract).  ``layout`` (``window_layout``) lays out
    the fragments launched — by default every fragment of ``params``; a
    mesh shard's, at packet positions ``frag_lo + group.frags``.
    Launches go to the default device.
    Returns ``(outs, layout, h2d_bytes, packets)``: each group's
    device-resident ``(rows, n, width)`` f32 counters in
    ``layout.groups`` order, the layout, the bytes sent and the
    packets launched.
    """
    from ..kernels.sketch_update import fleet as FK

    e_count = len(packets)
    n_frags = packets[0].n_frags
    L = n_levels
    assert params.shape[0] == e_count * n_frags * L
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
    if layout is None:
        layout = window_layout(nsub_f, width_f, e_count, L)
    kw = dict(log2_te=log2_te, signed=signed, blk=blk, w_blk=w_blk,
              interpret=interpret, value_mode=value_mode, n_levels=L,
              with_mitigation=with_mitigation)
    outs, h2d, n_packets = [], 0, 0
    for g in layout.groups:
        frag_idx = frag_lo + g.frags
        # all L level rows of each group fragment, epoch-major — aligned
        # with the packet rows pack_csr emits for the selected segments
        rows = ((np.arange(e_count)[:, None] * n_frags
                 + frag_idx[None, :]).ravel()[:, None] * L
                + np.arange(L)[None, :]).ravel()
        with obs.span("fleet.select"):
            selected = (packets if len(frag_idx) == n_frags
                        else [p.select(frag_idx) for p in packets])
        keys, vals, ts, block_frag = pack_csr(selected, blk)
        prm = params[rows]
        sent = sum(a.nbytes for a in (keys, vals, ts, prm, block_frag))
        h2d += sent
        n_packets += sum(len(p.keys) for p in selected)
        with obs.span("fleet.launch", h2d_bytes=sent):
            outs.append(FK.fleet_update_ragged(
                keys, vals, ts, prm, block_frag, n_sub_max=g.n,
                width_max=g.width, **kw))
    return outs, layout, h2d, n_packets


@functools.partial(jax.jit, static_argnames=("kind", "n_levels", "size"))
def _join_groups(outs, widths, *, kind: str, n_levels: int, size: int):
    """One program per window and shard: the subepoch groups' launches
    joined into one flat buffer of ``size`` counters (a zero tail pads a
    mesh shard to the longest shard), with the counter peak and the §4.2
    PEB of every (epoch, fragment) read off the groups on the way (level
    0 rows; ``widths`` gives each group's fragment widths in launch
    order).  The PEBs come out in group, then launch order."""
    parts = [o.reshape(-1) for o in outs]
    used = sum(p.shape[0] for p in parts)
    if size > used:
        parts.append(jnp.zeros(size - used, jnp.float32))
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    peak = functools.reduce(jnp.maximum,
                            [jnp.max(jnp.abs(o)) for o in outs])
    pebs = [equalize.peb_jnp(o[::n_levels],
                             jnp.full(w.shape, o.shape[1], jnp.int32), w,
                             kind)
            for o, w in zip(outs, widths)]
    return flat, peak, jnp.concatenate(pebs)


class _WindowBuffer:
    """Device-resident counters of one epoch window, in one flat f32
    buffer laid out by a ``WindowLayout``.

    The host transfer + int64 conversion happens exactly once, on first
    ``host()`` call, after which the device buffer is released.  While
    the buffer is still ``resident``, ``view()`` exposes it to the
    batched on-device query plane (``kernels.sketch_query``) — point and
    window queries then never trigger the transfer at all.  On a mesh
    the buffer is one equal block per shard (``shard_len`` counters
    each, the longest shard's; ``layout.base`` holds global offsets) and
    ``rows_per_shard`` rows of the fleet's padded row space per block.
    """

    def __init__(self, dev, layout: WindowLayout,
                 shard_len: Optional[int] = None,
                 rows_per_shard: Optional[int] = None):
        self._dev = dev
        self.layout = layout
        self.shard_len = shard_len
        self.rows_per_shard = rows_per_shard
        self.stack_bytes = 4 * int(dev.size)
        self.counter_bytes = layout.counter_bytes
        self._host: Optional[np.ndarray] = None

    @property
    def resident(self) -> bool:
        """True while the counters have not been transferred to host."""
        return self._dev is not None

    def device(self):
        """The still-resident flat f32 buffer (None once transferred)."""
        return self._dev

    def view(self, e_idx: Sequence[int]) -> FlatStack:
        """The resident buffer's cells of epochs ``e_idx`` for the device
        query plane; on a mesh with offsets into each row's own shard
        block and the fleet's pad rows (base 0) appended."""
        lay = self.layout
        base = lay.base[np.asarray(e_idx, np.int64)]
        if self.shard_len is None:
            return FlatStack(self._dev, base, lay.stride)
        n_rows = lay.base.shape[1]
        shard = np.arange(n_rows) // self.rows_per_shard
        n_pad = (int(self._dev.size) // self.shard_len
                 * self.rows_per_shard - n_rows)
        base = np.pad(base - shard * self.shard_len, ((0, 0), (0, n_pad)))
        stride = np.pad(lay.stride, (0, n_pad), constant_values=1)
        return FlatStack(self._dev, base, stride)

    def host(self) -> np.ndarray:
        """The flat int64 counters on the host (one transfer)."""
        if self._host is None:
            self._host = np.asarray(self._dev).astype(np.int64)
            self._dev = None
        return self._host

    def host_epoch(self, e_idx: int) -> FlatStack:
        """One epoch's cells of the host counters, for the host oracle."""
        return FlatStack(self.host(), self.layout.base[e_idx],
                         self.layout.stride)

    def cell(self, e_idx: int, row_lo: int, row_hi: int) -> np.ndarray:
        """Rows ``[row_lo, row_hi)`` of one epoch (one fragment's level
        rows) as ``(rows, n, width)`` counters: read from the device
        while resident (that cell alone), else a view of the host copy."""
        start, shape = self.layout.cell(e_idx, row_lo, row_hi)
        width = int(self.layout.widths[row_lo])
        src = self._host if self._host is not None else self._dev
        seg = src[start:start + int(np.prod(shape))]
        if self._host is None:
            seg = np.asarray(seg)
        return seg.reshape(shape)[:, :, :width]

    def patch(self, e_idx: int, row_lo: int, row_hi: int,
              counters) -> None:
        """Overwrite rows ``[row_lo, row_hi)`` of one epoch (one
        fragment's level rows) with exact integer ``(rows, n, width)``
        counters (or a scalar): patches the resident device buffer, or
        the already-transferred host copy *in place* so every existing
        record-plane view observes it."""
        start, shape = self.layout.cell(e_idx, row_lo, row_hi)
        width = int(self.layout.widths[row_lo])
        size = int(np.prod(shape))
        vals = np.broadcast_to(np.asarray(counters),
                               shape[:2] + (width,))
        if self.resident:
            seg = self._dev[start:start + size].reshape(shape)
            seg = seg.at[:, :, :width].set(jnp.asarray(vals, jnp.float32))
            self._dev = self._dev.at[start:start + size].set(
                seg.reshape(-1))
        else:
            self._host[start:start + size].reshape(shape)[:, :, :width] = \
                vals.astype(np.int64)


class WindowRecords(Mapping):
    """Lazy ``{switch: EpochRecords}`` view over one epoch of a window.

    The query plane consumes ``records[epoch][sw]``; materializing the
    records triggers the window's single host transfer (shared through
    ``_WindowBuffer``) and builds counters as *views* of the window
    buffer — no per-fragment copies.  Epochs nobody queries never leave
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
            self._buf.host()
            L = self._levels
            self._recs = {}
            for i, sw in enumerate(self._order):
                cfg = self._fragments[sw]
                cell = self._buf.cell(self._e, i * L, (i + 1) * L)
                self._recs[sw] = EpochRecords(
                    cfg.frag_id, self._epoch, int(self._n[i]),
                    cell if cfg.kind == "um" else cell[0], cfg.kind,
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


def _zero_cells(flat, layout: WindowLayout, cells, n_levels: int):
    """``flat`` with the (epoch, fragment position) ``cells`` of its
    layout zeroed, in one scatter on the buffer's device."""
    L = n_levels
    spans = [layout.cell(e, f * L, (f + 1) * L) for e, f in cells]
    idx = np.concatenate([np.arange(start, start + int(np.prod(shape)))
                          for start, shape in spans])
    return flat.at[jnp.asarray(idx, jnp.int32)].set(0.0)


class FleetEpochRunner:
    """Batched replacement for the per-switch loop in ``run_epoch``.

    Holds the fleet's static configuration, packs each epoch's streams
    into the ragged CSR layout (``layout="dense"`` keeps the PR-1
    rectangle as an oracle), dispatches one ``fleet_update_ragged``, and
    unpacks ``EpochRecords`` + PEBs.  ``run_window`` batches E epochs
    into one super-dispatch with frozen ``ns`` and device-resident
    counters; window epochs are queryable via
    ``point_query``/``window_query`` straight from the resident device
    buffer, no retention flag needed.  A window's counters are one flat
    buffer per device whose n_sub groups keep their own extents
    (``WindowLayout``); on a ``switch`` mesh each shard's update runs on
    its own chip and its block of the buffer stays there.
    ``keep_stacked=True`` additionally retains per-epoch *host* counters
    from ``run_epoch`` so the batched query ops also cover per-epoch
    dispatches (for window epochs, host counters are cached lazily on
    first host-path access — ``run_window`` itself never forces the
    transfer).  Window buffers stay device-resident until the record
    plane or a host-path query materializes them; on accelerator
    deployments, materialize windows you are finished querying to
    release their HBM (``stack_bytes`` counts what is resident).
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
        # epoch -> ({parity group: (E, L, n, w) int32 device XOR parity
        # over its members' cells}, epoch index within the window),
        # computed from the same window dispatch before lost cells are
        # zeroed.
        self._parity: Dict[int, Tuple[Dict[int, object], int]] = {}
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

    def _resident_bufs(self) -> List[_WindowBuffer]:
        bufs = {id(b): b for b, _ in self._window_bufs.values()}
        return [b for b in bufs.values() if b.resident]

    @property
    def stack_bytes(self) -> int:
        """Bytes of the resident window buffers (with each group's and
        each mesh shard's padding)."""
        return sum(b.stack_bytes for b in self._resident_bufs())

    @property
    def counter_bytes(self) -> int:
        """Bytes of the resident windows' own counters, 4 · Σ n·width."""
        return sum(b.counter_bytes for b in self._resident_bufs())

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

    def _dispatch_window(self, params: np.ndarray,
                         packets: Sequence[FleetPacket],
                         lost_sets: Sequence[set]):
        """The update of one window (E = 1 for a single epoch): every
        shard's grouped launches on its own device — the default device
        without a mesh — each issued before anything is read.  On each
        shard's device its groups are joined into the shard's flat buffer
        with the counter peak and the §4.2 PEBs, the XOR parity of its
        parity groups is taken, and then its lost cells are zeroed.  The
        host reads every shard's peak and PEBs once, in one
        ``fleet.sync``; no counter block crosses to the host.

        Returns ``(buf, pebs (E, F), parity, peak)``: ``parity`` maps a
        parity group's index to its (E, L, n, w) int32 device parity, or
        is None without parity groups.
        """
        from ..kernels.sketch_update.fleet import PARAM_N_SUB, PARAM_WIDTH

        with obs.span("fleet.prepare"):
            # Fold per-packet UnivMon level ids / §4.4 flags into the high
            # ts bits (no-op for plain cs/cms fleets — the cached epoch
            # packets are shared across systems and must stay untouched).
            packets = [fold_packet_flags(p, self.log2_te,
                                         n_levels=self.n_levels,
                                         level_seed=self.level_seed,
                                         mitigation=self.mitigation)
                       for p in packets]
        e_count = len(packets)
        n_frags = len(self.frag_order)
        L = self.n_levels
        ns_f = params[:n_frags * L:L, PARAM_N_SUB].astype(np.int64)
        widths_f = params[:n_frags * L:L, PARAM_WIDTH].astype(np.int64)
        bounds = self._shard_frag_bounds or [(0, n_frags)]
        layouts = [window_layout(ns_f[lo:hi], widths_f[lo:hi], e_count, L)
                   for lo, hi in bounds]
        shard_len = max(lay.size for lay in layouts)
        devices = (self._shard_devices(shard_len) if self.mesh is not None
                   else [jax.devices()[0]])
        kw = dict(log2_te=self.log2_te, signed=self.kind in ("cs", "um"),
                  blk=self.blk, w_blk=self.w_blk, interpret=self.interpret,
                  value_mode=self.value_mode, n_levels=L,
                  with_mitigation=self.mitigation)
        flats, reads, parity = [], [], {}
        for s, ((lo, hi), lay, dev) in enumerate(zip(bounds, layouts,
                                                     devices)):
            if lo >= hi:
                flats.append(jnp.zeros(shard_len, jnp.float32, device=dev))
                continue
            # One device: no device context, which would key every
            # compiled program apart from the same program outside it.
            on_dev = (contextlib.nullcontext() if self.mesh is None
                      else jax.default_device(dev))
            with obs.span("fleet.shard", shard=s, device=dev.id,
                          rows=e_count * (hi - lo) * L) as sp, on_dev:
                outs, _, h2d, n_packets = dispatch_ragged_grouped(
                    params, packets, lay, frag_lo=lo, **kw)
                widths = tuple(np.tile(widths_f[lo + g.frags], e_count)
                               .astype(np.int32) for g in lay.groups)
                flat, peak, pebs = _join_groups(
                    tuple(outs), widths, kind=self.kind, n_levels=L,
                    size=shard_len)
                if self.parity_groups is not None:
                    parity.update(self._group_parity(outs, lay, lo, hi))
                lost = [(e, self._frag_pos[sw] - lo)
                        for e, sws in enumerate(lost_sets) for sw in sws
                        if lo <= self._frag_pos[sw] < hi]
                if lost:
                    flat = _zero_cells(flat, lay, lost, L)
                sp.set_metadata(packets=n_packets, h2d_bytes=h2d)
            flats.append(flat)
            reads.append((lo, lay, peak, pebs))
        with obs.span("fleet.sync"):
            got = jax.device_get([(peak, pebs) for _, _, peak, pebs in reads])
        pebs_all = np.zeros((e_count, n_frags))
        for (lo, lay, _, _), (_, pebs) in zip(reads, got):
            at = 0
            for g in lay.groups:
                k = e_count * len(g.frags)
                pebs_all[:, lo + g.frags] = pebs[at:at + k].reshape(
                    e_count, len(g.frags))
                at += k
        peak = max(float(p) for p, _ in got)
        if self.mesh is None:
            return (_WindowBuffer(flats[0], layouts[0]), pebs_all,
                    parity or None, peak)
        base = np.concatenate([lay.base + s * shard_len
                               for s, lay in enumerate(layouts)], axis=1)
        layout = WindowLayout(
            base, *(np.concatenate([getattr(lay, k) for lay in layouts])
                    for k in ("stride", "ns", "widths")),
            self.n_shards * shard_len)
        sharding = NamedSharding(self.mesh, P("switch"))
        shape = (self.n_shards * shard_len,)
        by_block = {dev: flat for dev, flat in zip(devices, flats)}
        arrays = [by_block[d] for d in
                  sharding.addressable_devices_indices_map(shape)]
        out = jax.make_array_from_single_device_arrays(shape, sharding,
                                                       arrays)
        buf = _WindowBuffer(out, layout, shard_len=shard_len,
                            rows_per_shard=self._frags_per_shard * L)
        return buf, pebs_all, parity or None, peak

    def _shard_devices(self, shard_len: int) -> list:
        """The device that holds each shard's block of a sharded window
        buffer of ``shard_len`` counters a shard."""
        sharding = NamedSharding(self.mesh, P("switch"))
        where = sharding.addressable_devices_indices_map(
            (self.n_shards * shard_len,))
        by_block = {(idx[0].start or 0) // shard_len: d
                    for d, idx in where.items()}
        return [by_block[s] for s in range(self.n_shards)]

    def _group_parity(self, outs, lay: WindowLayout, lo: int, hi: int):
        """XOR parity of the parity groups whose members are fragments
        ``[lo, hi)``, over the un-zeroed launches: one (E, L, n, w) int32
        array per group, each member's cells zero-padded to the group's
        largest n and width.  Exact integers below 2^24 make the
        f32->int32 conversion lossless, and XOR (unlike a sum) can
        neither overflow nor round; dead members' cells are exact zeros
        and XOR away, so the parity equation holds for any liveness."""
        L = self.n_levels
        e_count = lay.base.shape[0]
        where = {int(f): (k, j) for k, g in enumerate(lay.groups)
                 for j, f in enumerate(g.frags)}
        out = {}
        for gi, members in enumerate(self.parity_groups):
            if not lo <= int(members[0]) < hi:
                continue
            cells = []
            for i in members:
                k, j = where[int(i) - lo]
                g = lay.groups[k]
                width = int(lay.widths[(int(i) - lo) * L])
                cells.append(outs[k].reshape(e_count, len(g.frags), L, g.n,
                                             g.width)[:, j, :, :, :width])
            n_p = max(c.shape[2] for c in cells)
            w_p = max(c.shape[3] for c in cells)
            acc = None
            for c in cells:
                c = jnp.pad(c, ((0, 0), (0, 0), (0, n_p - c.shape[2]),
                                (0, w_p - c.shape[3]))).astype(jnp.int32)
                acc = c if acc is None else acc ^ c
            out[gi] = acc
        return out

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

        if self.layout == "dense":
            from ..kernels.sketch_update import fleet as FK

            keys, vals, ts = packet.densify(self.blk)
            out = np.asarray(FK.fleet_update(
                keys, vals, ts, params, n_sub_max=int(n_arr.max(initial=1)),
                width_max=int(self.widths.max(initial=4)),
                log2_te=self.log2_te, signed=self.kind in ("cs", "um"),
                blk=self.blk, w_blk=self.w_blk, interpret=self.interpret,
                value_mode=self.value_mode))
            peak = float(np.abs(out).max(initial=0.0))
            n_rows, n_sub, width = out.shape
            cells = FlatStack(out.astype(np.int64).reshape(-1),
                              np.arange(n_rows) * n_sub * width,
                              np.full(n_rows, width))
        else:
            # Per-epoch records are host records: the window dispatch of
            # E = 1, read back whole.
            buf, _, _, peak = self._dispatch_window(params, [packet],
                                                    [set()])
            cells = buf.host_epoch(0)
        self._check_output_peak(peak)

        recs: Dict[int, EpochRecords] = {}
        pebs: Dict[int, float] = {}
        for i, sw in enumerate(self.frag_order):
            if sw in dead_set:
                continue      # no record, no PEB — matches the loop path
            cfg = self.fragments[sw]
            n = int(n_arr[i])
            start = int(cells.base[i * L])
            w = int(cells.stride[i * L])
            cell = (cells.flat[start:start + L * n * w]
                    .reshape(L, n, w)[:, :, :cfg.width].copy())
            recs[sw] = EpochRecords(
                cfg.frag_id, epoch, n, cell if cfg.kind == "um" else cell[0],
                cfg.kind, cfg.mitigation, cfg.base_seed)
            pebs[sw] = equalize.peb_epoch(recs[sw])
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
            self.stacked[epoch] = cells
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
        """Epoch-window super-dispatch: E epochs x F fragments in one
        kernel launch per subepoch group (E*F virtual param rows), ``ns``
        frozen for the window; on a mesh, per group and shard, each
        shard on its own chip (``_dispatch_window``).

        Counters stay device-resident: only the overflow peak (one
        scalar) and the (E*F,) PEB vector cross the host boundary here,
        in one read; the whole buffer transfers lazily, once per window,
        when the query plane first touches a ``WindowRecords``.

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

            buf, pebs_all, parity, peak = self._dispatch_window(
                params, packets, lost_sets)
            self._check_output_peak(peak)
            sp.set_metadata(stack_bytes=buf.stack_bytes,
                            counter_bytes=buf.counter_bytes)
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
                    if parity is not None:
                        self._parity[ep] = (parity, e)
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

    def _host_stack(self, epoch: int) -> FlatStack:
        """Host counters of one retained epoch, as the host oracle reads
        them: the per-epoch ``keep_stacked`` copy, or the epoch's cells
        of an already-transferred window buffer."""
        stack = self.stacked.get(epoch)
        if stack is None:
            buf, e_idx = self._window_bufs[epoch]
            stack = buf.host_epoch(e_idx)
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
        """One (epoch, fragment) cell of the retained window buffer as
        an exact ``(n_levels, n, width)`` int32 copy — the export payload
        (lossless: counters are exact integers below 2^24)."""
        if epoch not in self._window_bufs:
            raise KeyError(f"epoch {epoch} has no retained window stack")
        buf, e_idx = self._window_bufs[epoch]
        i = self._frag_pos[sw]
        L = self.n_levels
        return buf.cell(e_idx, i * L, (i + 1) * L).astype(np.int32)

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
        for sw in sws:
            i = self._frag_pos[sw]
            buf.patch(e_idx, i * L, (i + 1) * L, 0)
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
            parity, p_idx = self._parity[e]
            patches = []
            for sw in sws:
                i = self._frag_pos[sw]
                gi = self._group_of[i]
                # (L, n, w) int32: the group's largest n and width
                acc = np.array(jax.device_get(parity[gi][p_idx]))
                for j in self.parity_groups[gi]:
                    if j != i:
                        c = buf.cell(e_idx, j * L, (j + 1) * L)
                        acc[:, :c.shape[1], :c.shape[2]] ^= c.astype(np.int32)
                c = buf.cell(e_idx, i * L, (i + 1) * L)
                patches.append((i, acc[:, :c.shape[1], :c.shape[2]]))
            for i, counters in patches:
                buf.patch(e_idx, i * L, (i + 1) * L, counters)
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
        buffer's ``FlatStack`` of those epochs' cells.
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
        device_groups = [
            (buf.view([self._window_bufs[e][1] for e in es]), es)
            for buf, es in by_buf.values()]
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
