"""Pallas TPU kernel: batched sketch-fragment update (the data-plane hot path).

The PISA switch updates one SRAM counter per packet.  A TPU has no cheap
scatter; the TPU-native recast is a *one-hot matmul histogram* on the MXU:

    contribution[s, c] = sum_p onehot_sub[s, p] * (value*sign*mask)[p]
                                 * onehot_col[p, c]

i.e. a (n_sub x BLK) @ (BLK x W_BLK) matmul per packet block, accumulated
into a VMEM-resident (n_sub, width)-tile of the fragment counters.  All
hashing (column, sign, subepoch of both packet and flow) happens in-kernel
in uint32 arithmetic (VPU), so the only HBM traffic is the packet stream in
and the counters out.

Value modes (the bf16 limb-split engine)
----------------------------------------
One-hots are 0/1 — exact in any float dtype — so the contraction dtype is
a free knob.  bf16 halves the dominant VMEM buffer (the (BLK, W_BLK)
column one-hot) and runs the MXU at its native bf16 rate (an f32 HIGHEST
matmul costs ~6 bf16 passes); the MXU accumulates bf16 x bf16 products in
f32, so exactness only needs each *operand* to be exact in bf16's 8-bit
mantissa.  Three statically-selected paths:

  * ``"count"`` — |val'| <= 256 (pure packet counting, the dominant
    workload): val' itself is exact in bf16, one bf16 contraction.
  * ``"limb"``  — |val'| < 2^16: split ``val' = hi*256 + lo`` with
    ``hi = trunc(val'/256)``, ``lo = val' - 256*hi``; both limbs are
    integers in [-256, 256], exact in bf16, and two bf16 contractions
    recombine as ``acc_hi*256 + acc_lo`` (the scale is a power of two,
    exact in f32).
  * ``"f32"``   — the original HIGHEST-precision f32 contraction; the
    fallback for per-packet |values| >= 2^16 or non-integer values.

All three are bit-identical to the jnp scatter oracle while counters obey
the repo-wide exactness contract (|counter| < 2^24, enforced by
``check_output_peak``); ``resolve_value_mode`` picks the cheapest sound
path from concrete input values at trace time.

Grid: (width_blocks, packet_blocks); the packet axis is the inner
(sequential) reduction axis, so each counter tile is initialized once and
revisited across packet blocks.  The width axis is declared ``parallel``
(``dimension_semantics``) so Mosaic may split it across megacore
TensorCores.  All-zero packet blocks (padding) skip the contraction
entirely (``pl.when`` on a VPU reduction of the value block).

The column one-hot itself is *factored* into quotient/residue limbs
(``col = q * LANE + r``, LANE = 128) with the quotient fused into the
subepoch row id, so each 128-packet row of the lane-major
``(BLK/LANE, LANE)`` packet tile contracts as
``(N_SUB*J, 128) x (LANE, 128)^T`` (J = W_BLK/LANE) and the old dominant
``(BLK, W_BLK)`` one-hot buffer never exists — see ``block_contrib`` and
docs/kernels.md §1.  Every kernel writes ``(..., N_SUB, W/LANE, LANE)``
counter tiles, which the wrappers reshape to ``(N_SUB, W)`` for free.

VMEM budget per step (``vmem_bytes`` is the single source of truth):
the per-row lhs/rhs operands and the block accumulator, plus the
double-buffered packet and counter tiles.  ``select_geometry`` picks the
largest (BLK, W_BLK) under ``VMEM_BUDGET_BYTES``.  On the chip both BLK
and W_BLK are multiples of ``MIN_BLK`` = 1024 (a 32-bit tile is 8 x 128)
and the parameter table lives in SMEM (docs/kernels.md §2e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# Numerical contract constants.

#: f32 accumulates integers exactly while |counter| stays below this.
EXACT_BOUND = 1 << 24
#: |value| bound for the single-contraction bf16 "count" path (integers
#: up to 2^8 are exact in bf16's 8-bit mantissa).
COUNT_BOUND = 1 << 8
#: |value| bound for the two-limb bf16 "limb" path (hi*256 + lo, each
#: limb exact in bf16).
LIMB_BOUND = 1 << 16

VALUE_MODES = ("count", "limb", "f32")

#: Residue width of the factored column one-hot — the TPU lane width.
LANE = 128
LANE_BITS = 7

#: One 32-bit (8, 128) tile.  On the chip a packet block is an
#: ``(blk / LANE, LANE)`` tile and a counter block an ``(n_sub, w_blk /
#: LANE, LANE)`` tile, so both ``blk`` and ``w_blk`` are multiples of
#: ``MIN_BLK`` (interpret mode accepts any multiple of LANE).
MIN_BLK = 8 * LANE

# Packed-ts field layout (UnivMon / §4.4 on the fleet).  The kernel only
# reads timestamp bits [shift, log2_te) — the subepoch bit-slice — so the
# high bits of the uint32 ts word are free side-channels.  The fleet
# packer (``repro.core.fleet.fold_packet_flags``) masks ts to its low
# ``log2_te`` bits and folds in per-packet metadata the batched kernels
# consume via the parameter table:
#
#   * bits [LVL_SHIFT, LVL_SHIFT+5): the packet key's UnivMon level id
#     (``hashing.level_of``, computed once per packet on the host) — a
#     virtual level row ``l`` monitors the packet iff ``lvl >= l``;
#   * bit SH_SHIFT: the §4.4 single-hop flag — mitigation-enabled rows
#     additionally monitor flagged packets in the flow's second subepoch.
#
# Consequences: UnivMon on the fleet requires log2_te <= LVL_SHIFT and
# n_levels <= 32; mitigation alone requires log2_te <= SH_SHIFT.
LVL_SHIFT = 24
LVL_FIELD_MASK = 0x1F
SH_SHIFT = 31

#: Default VMEM budget for geometry selection: leave ~4 MiB of the
#: 16 MiB/core for Mosaic's own double-buffering and spills.
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def pow2_width_cap(width: int) -> int:
    """Power-of-two ceiling of a hash width, floored at one ``MIN_BLK``
    tile — the cap every wrapper applies to ``w_blk`` so narrow
    fragments never allocate wider blocks than that."""
    return int(2 ** np.ceil(np.log2(max(width, MIN_BLK))))


def resolve_interpret(interpret) -> bool:
    """Resolve the ``interpret`` knob shared by every kernel wrapper.

    ``"auto"`` compiles through Mosaic on TPU and falls back to the
    Pallas interpreter everywhere else (CPU CI, local dev).  Booleans
    pass through for explicit override (tests pin ``True``).
    """
    if interpret == "auto":
        return jax.default_backend() != "tpu"
    return bool(interpret)


def resolve_value_mode(value_mode, vals, interpret: bool = False) -> str:
    """Resolve the ``value_mode`` knob shared by every kernel wrapper.

    ``"auto"`` inspects *concrete* value arrays (the common case: the
    public wrappers are plain functions called with host numpy / device
    arrays) and picks the cheapest exact path: ``"count"`` for integer
    |v| <= 256, ``"limb"`` for integer |v| < 2^16, ``"f32"`` otherwise.
    Under an outer trace (values are abstract) it conservatively falls
    back to ``"f32"`` — callers inside jit should pass an explicit mode.

    ``interpret=True`` (the CPU fallback) also resolves to ``"f32"``:
    off-TPU there is no MXU rate or VMEM budget to win back and XLA CPU
    emulates bf16 matmuls slowly.  Explicit modes are always honored
    (that is how the CPU test suite pins the bf16 paths).
    """
    if value_mode != "auto":
        if value_mode not in VALUE_MODES:
            raise ValueError(f"unknown value_mode {value_mode!r}; "
                             f"expected one of {VALUE_MODES} or 'auto'")
        return value_mode
    if interpret or isinstance(vals, jax.core.Tracer):
        return "f32"
    if (isinstance(vals, jax.Array)
            and next(iter(vals.devices())).platform != "cpu"):
        # Don't drag an accelerator-resident stream to host just to
        # inspect it — callers holding device arrays pass an explicit
        # mode to opt into the bf16 paths.
        return "f32"
    v = np.asarray(vals)
    if v.size == 0:
        return "count"
    if not np.all(v == np.trunc(v)):
        return "f32"
    m = float(np.max(np.abs(v)))
    if m <= COUNT_BOUND:
        return "count"
    if m < LIMB_BOUND:
        return "limb"
    return "f32"


#: ``max |counter|`` of an output stack as one compiled program.
abs_peak = jax.jit(lambda o: jnp.max(jnp.abs(o)))


def check_output_peak(peak: float) -> None:
    """Enforce the f32 exact-integer contract on a counter peak.

    Shared by the fleet runner and the single-fragment wrapper: every
    path that hands counters to the query plane must refuse to return
    silently-inexact values.
    """
    if peak >= EXACT_BOUND:
        raise OverflowError(
            f"counter magnitude {peak:.3g} exceeds the f32 exact-integer "
            "range (2^24); shorten the epoch or split the stream")


def _elem_bytes(value_mode: str) -> int:
    return 2 if value_mode in ("count", "limb") else 4


def vmem_bytes(blk: int, w_blk: int, n_sub: int,
               value_mode: str = "f32") -> int:
    """Working set per grid step for one (BLK, W_BLK) geometry.

    ``block_contrib`` contracts one 128-packet sublane row of the packet
    tile at a time, so the operand buffers are per row, independent of
    ``blk``: the combined-row lhs ``(n_sub * W_BLK/LANE, LANE)`` (twice
    for the limb mode), the residue one-hot ``(LANE, LANE)``, and the
    f32 block accumulator of the same shape as the counter tile.  The
    packet tiles (keys/vals/ts) and the counter tile are double-buffered
    by the Pallas pipeline.  The single source of truth for the budget —
    ``benchmarks.kernel_bench`` and docs/kernels.md both defer to it.
    """
    eb = _elem_bytes(value_mode)
    rows = n_sub * max(w_blk // LANE, 1)
    keys_vals_ts = 2 * 3 * blk * 4
    lhs = rows * LANE * eb * (2 if value_mode == "limb" else 1)
    rhs = LANE * LANE * eb
    acc = rows * LANE * 4
    counters = 2 * n_sub * w_blk * 4
    return keys_vals_ts + lhs + rhs + acc + counters


def select_geometry(width: int, n_sub: int, value_mode: str = "count",
                    budget: int = VMEM_BUDGET_BYTES):
    """Largest (blk, w_blk) block geometry that fits the VMEM budget.

    Preference order: maximize ``w_blk`` first (each width block re-reads
    the whole packet stream from HBM, so fewer width blocks is the
    bigger lever), then ``blk`` (amortizes per-grid-step overhead).
    ``w_blk`` is capped at the padded width so narrow fragments spend
    the budget on ``blk`` instead.  Both are multiples of ``MIN_BLK``
    (the chip's tile rule), so a fragment narrower than 1024 columns is
    padded to one 1024-wide block.  Extreme subepoch counts shrink the
    geometry automatically (the lhs row count scales with
    ``n_sub * w_blk``).
    """
    w_cap = pow2_width_cap(width)
    for w_blk in (4096, 2048, MIN_BLK):
        if w_blk > w_cap:
            continue
        for blk in (2048, MIN_BLK):
            if vmem_bytes(blk, w_blk, n_sub, value_mode) <= budget:
                return blk, w_blk
    return MIN_BLK, MIN_BLK


def lane_tiles(x, dtype):
    """``(P,)`` packet stream -> ``(P / LANE, LANE)`` lane-major tile rows
    (P must be a multiple of LANE).  Host arrays are reshaped before the
    transfer."""
    if isinstance(x, jax.Array):
        return x.astype(dtype).reshape(-1, LANE)
    return jnp.asarray(np.reshape(x, (-1, LANE)), dtype)


# Avalanche constants (must match repro.core.hashing).
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_SEED_MULT = np.uint32(2654435769)


def _mix32(x):
    x = (x ^ (x >> np.uint32(16))) * _M1
    x = (x ^ (x >> np.uint32(15))) * _M2
    return x ^ (x >> np.uint32(16))


def _hash_u32(keys, seed):
    return _mix32(keys * _SEED_MULT + seed)


def _hash_mod(keys, seed, mod):
    """Lemire-style fast-range in two 16-bit limbs (matches hashing.py).

    ``mod`` may be a static Python int or a traced uint32 scalar (the
    fleet kernel hashes modulo a per-fragment width read in-kernel).
    """
    h = _hash_u32(keys, seed)
    mod_u = jnp.uint32(mod)
    hi = h >> np.uint32(16)
    lo = h & np.uint32(0xFFFF)
    t = hi * mod_u + ((lo * mod_u) >> np.uint32(16))
    return (t >> np.uint32(16)).astype(jnp.int32)


def _is_zero(x) -> bool:
    return isinstance(x, int) and x == 0


def block_contrib(keys, vals, ts, *, col_seed, sign_seed, sub_seed,
                  width, n_mask, shift, wi, w_blk, n_sub_rows, signed,
                  value_mode: str = "f32", level=0, mit=0):
    """Shared per-packet-block body: hashes -> §4.1 monitored mask ->
    factored one-hots -> MXU dots (see the module doc's value modes).
    The single source of truth for the sketch update arithmetic; the
    single-fragment and fleet kernels both call it.  Hash scalars may be
    static Python ints (single-fragment) or traced uint32 scalars
    (per-fragment table, fleet); ``n_sub_rows`` (the output row count)
    and ``value_mode`` are always static.

    ``keys``/``vals``/``ts`` are one lane-major packet tile of shape
    ``(blk / LANE, LANE)``: hashing runs elementwise on the whole tile,
    and every per-packet vector stays in lane orientation — the chip
    never relays a packet vector from lanes to sublanes.

    ``level``/``mit`` extend the §4.1 monitored mask for the fleet's
    virtual UnivMon level rows and the §4.4 single-hop mitigation.  Both
    read per-packet metadata the packer folded into the high ts bits
    (see the packed-ts layout above): a level row monitors only packets
    whose key's level id (ts bits [LVL_SHIFT, LVL_SHIFT+5)) is >= the
    row's ``level``, and a mitigation row additionally monitors
    single-hop packets (ts bit SH_SHIFT) in the flow's *second* subepoch
    ``(sub_flow + n/2) & (n-1)``.  Static Python zeros (the default, and
    the single-fragment path) skip the extra VPU work entirely.

    The column one-hot is *factored* into quotient/residue limbs,
    ``local_col = q * LANE + r``: the quotient is fused with the
    subepoch id into one combined row id ``cid = sub * J + q``
    (J = W_BLK / LANE).  For each 128-packet sublane row of the tile the
    contraction is

        (N_SUB*J, 128 pkts) x (LANE, 128 pkts)^T   # contract packets

    with lhs = (cid one-hot) * val' and the residue one-hot transposed
    so both operands keep packets on lanes.  Returns the
    ``(n_sub_rows * J, LANE)`` block contribution: row ``s*J + j`` holds
    columns ``[j*LANE, (j+1)*LANE)`` of subepoch ``s``, so the callers'
    ``(n_sub_rows, J, LANE)`` counter tiles take it by a sublane split
    (tile-aligned on the chip, where J is a multiple of 8).
    """
    n_tile_rows = keys.shape[0]
    j_rows = w_blk // LANE
    rows = n_sub_rows * j_rows
    # Subepoch of the packet: Method 2 bit-slice of the timestamp.
    sub_pkt = ((ts >> shift) & n_mask).astype(jnp.int32)
    # Subepoch the flow is monitored in (temporal sampling, §4.1).
    sub_flow = (_hash_u32(keys, sub_seed) & n_mask).astype(jnp.int32)
    monitored = sub_pkt == sub_flow
    if not _is_zero(mit):
        # §4.4: single-hop flows (ts bit SH_SHIFT, folded by the packer)
        # carry a second subepoch record at sub_flow + n/2.  Boolean OR,
        # so n = 1 (sub2 == sub_flow) degenerates to a no-op exactly as
        # in the numpy path's `n >= 2` guard.  ``mit`` scales the flag
        # bit as an integer so a traced per-row flag stays a vector op.
        n_i = jnp.asarray(n_mask).astype(jnp.int32)
        sub2 = (sub_flow + ((n_i + 1) >> 1)) & n_i
        sh = ((ts >> np.uint32(SH_SHIFT)).astype(jnp.int32) * mit) != 0
        monitored = monitored | (sh & (sub_pkt == sub2))
    if not _is_zero(level):
        # UnivMon virtual level row: the packer folded level_of(key)
        # into ts bits [LVL_SHIFT, LVL_SHIFT+5); level l sees only keys
        # with lvl >= l (level 0 — and every non-UnivMon row — passes
        # everything, garbage high bits included, since lvl_pkt >= 0).
        lvl_pkt = ((ts >> np.uint32(LVL_SHIFT))
                   & np.uint32(LVL_FIELD_MASK)).astype(jnp.int32)
        monitored = monitored & (lvl_pkt >= level)

    col = _hash_mod(keys, col_seed, width)          # in [0, width)
    vals = jnp.where(monitored, vals, jnp.float32(0.0))
    if signed:
        neg = (_hash_u32(keys, sign_seed) & np.uint32(1)) != 0
        vals = jnp.where(neg, -vals, vals)

    # Quotient/residue factorization of this width block's columns.
    # Packets whose column lives in another width block get cid = -1
    # (matches no row; q alone could alias a neighbouring (sub, q) row).
    local_col = col - wi * w_blk
    in_block = (local_col >= 0) & (local_col < w_blk)
    cid = jnp.where(in_block, sub_pkt * j_rows + (local_col >> LANE_BITS),
                    -1)
    r = local_col & (LANE - 1)

    if value_mode == "f32":
        cdt, limbs = jnp.float32, ((vals, None),)
        precision = jax.lax.Precision.HIGHEST
    elif value_mode == "count":
        # |val'| <= 256: exact in bf16, single contraction.
        cdt, limbs, precision = jnp.bfloat16, ((vals, None),), None
    elif value_mode == "limb":
        # |val'| < 2^16: two exact 8-bit limbs, hi*256 + lo with
        # hi = trunc(val'/256) (floor of |val'| with val's sign; the
        # power-of-two scale is exact in f32).  Limb signs match val's
        # sign so |partial hi-sums|*256 never exceed the input mass.
        a = jnp.floor(jnp.abs(vals) * jnp.float32(1.0 / 256.0))
        hi = jnp.where(vals < 0, -a, a)
        lo = vals - hi * jnp.float32(256.0)
        cdt, precision = jnp.bfloat16, None
        limbs = ((hi, jnp.float32(256.0)), (lo, None))
    else:
        raise ValueError(f"unknown value_mode {value_mode!r}")

    # One-hots are built as f32 selects and cast once: 0/1 and the
    # bf16-exact values survive the cast bit for bit, and the masks keep
    # the 32-bit layout of the comparisons that produced them.
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0)
    res_iota = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
    nt = (((1,), (1,)), ((), ()))
    acc = None
    for s in range(n_tile_rows):
        row_sel = cid[s:s + 1] == row_iota          # (N_SUB*J, 128 pkts)
        rhs_t = jnp.where(r[s:s + 1] == res_iota, jnp.float32(1.0),
                          jnp.float32(0.0)).astype(cdt)   # (LANE, 128 pkts)
        for limb, scale in limbs:
            lhs = jnp.where(row_sel, limb[s:s + 1],
                            jnp.float32(0.0)).astype(cdt)
            part = jax.lax.dot_general(lhs, rhs_t, nt, precision=precision,
                                       preferred_element_type=jnp.float32)
            if scale is not None:
                part = part * scale
            acc = part if acc is None else acc + part
    return acc


def sketch_update_kernel(keys_ref, vals_ref, ts_ref, out_ref, *,
                         hash_width: int, w_blk: int, n_sub: int,
                         log2_te: int, col_seed: int, sign_seed: int,
                         sub_seed: int, signed: bool, value_mode: str,
                         level: int = 0, mitigation: bool = False):
    wi = pl.program_id(0)   # width-block index
    pj = pl.program_id(1)   # packet-block index (sequential reduction)

    @pl.when(pj == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[...]

    # All-zero value blocks (tail padding) contribute nothing: skip the
    # one-hot build + contraction on a cheap VPU reduction.
    @pl.when(jnp.max(jnp.abs(vals)) > 0.0)
    def _accum():
        out_ref[...] += block_contrib(
            keys_ref[...], vals, ts_ref[...],
            col_seed=np.uint32(col_seed), sign_seed=np.uint32(sign_seed),
            sub_seed=np.uint32(sub_seed), width=hash_width,
            n_mask=np.uint32(n_sub - 1),
            shift=np.uint32(log2_te - (n_sub.bit_length() - 1)),
            wi=wi, w_blk=w_blk, n_sub_rows=n_sub, signed=signed,
            value_mode=value_mode, level=level,
            mit=1 if mitigation else 0).reshape(out_ref.shape)


def sketch_update_pallas(keys, vals, ts, *, hash_width: int,
                         padded_width: int, n_sub: int,
                         log2_te: int, col_seed: int, sign_seed: int,
                         sub_seed: int, signed: bool, blk: int = MIN_BLK,
                         w_blk: int = 2048, value_mode: str = "f32",
                         level: int = 0, mitigation: bool = False,
                         interpret: bool = False):
    """Lowered pallas_call.  ``keys``/``vals``/``ts`` are lane-major
    ``(P / LANE, LANE)`` tiles (``lane_tiles``) with P a multiple of
    ``blk``; padded_width a multiple of w_blk (ops.py handles padding).
    Columns are hashed modulo the *true* hash_width <= padded_width.
    ``level``/``mitigation`` select the UnivMon-level / §4.4
    monitored-mask terms (static; require the packer's folded ts — see
    the packed-ts layout).

    The output uses the factored ``(n_sub, padded_width/LANE, LANE)``
    layout — counters for subepoch s, column c live at
    ``[s, c // LANE, c % LANE]`` — so callers reshape to
    (n_sub, padded_width) for free outside the kernel.
    """
    p = keys.shape[0] * LANE
    assert blk % LANE == 0 and p % blk == 0 and padded_width % w_blk == 0
    grid = (padded_width // w_blk, p // blk)
    j_rows = w_blk // LANE
    kernel = functools.partial(
        sketch_update_kernel, hash_width=hash_width, w_blk=w_blk,
        n_sub=n_sub, log2_te=log2_te, col_seed=col_seed,
        sign_seed=sign_seed, sub_seed=sub_seed, signed=signed,
        value_mode=value_mode, level=level, mitigation=mitigation)
    pkt = pl.BlockSpec((blk // LANE, LANE), lambda i, j: (j, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pkt, pkt, pkt],
        out_specs=pl.BlockSpec((n_sub, j_rows, LANE),
                               lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_sub, padded_width // LANE, LANE), jnp.float32),
        # Width blocks touch disjoint counter tiles: parallel (megacore
        # may split them across TensorCores); the packet axis is the
        # sequential accumulation.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(keys, vals, ts)
