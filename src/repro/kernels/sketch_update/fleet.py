"""Fleet-level Pallas kernels: update *all* fragments of a network epoch
(or a whole multi-epoch *window*) in one device dispatch.

``kernel.py`` updates one fragment per ``pallas_call``; a network has
hundreds of fragments and a Python loop over them serializes the epoch
(host dispatch latency dominates, and no cross-fragment batching reaches
the MXU).  Two batched layouts live here, both reusing the same
``block_contrib`` one-hot-matmul body (including its bf16 count/limb
value modes — see kernel.py).

**Ragged CSR layout (``fleet_update_ragged``, the hot path).**  Every
fragment's stream is a *segment* of one flat ``(P_total,)`` packet
stream, padded only to a ``blk`` boundary (waste <= blk per fragment),
and the grid is::

    grid = (width_blocks, packet_blocks_total)

A scalar-prefetched ``block_frag`` map (``(packet_blocks_total,)``
int32, non-decreasing) names the fragment that owns each packet block;
the BlockSpec index maps gather that fragment's parameter row and
counter tile, so heterogeneous fragments never pay for the hottest
fragment's padding (the dense rectangle's ``pad_work_x``).  A counter
tile is zero-initialized when its first packet block arrives (the map
changes value), which requires every fragment to own >= 1 block — the
host-side packer (``repro.core.fleet.pack_csr``) guarantees it.

Because per-fragment parameters (seeds, width, n_sub) are just rows of
the table, E epochs x F fragments are simply E*F rows: the *epoch-window
super-dispatch* reuses this kernel unchanged with virtual rows
``e * n_frags + f`` (see ``repro.core.fleet.FleetEpochRunner.run_window``).

**UnivMon virtual level rows (``n_levels > 1``).**  A UnivMon fragment
is ``n_levels`` independent Count-Sketch rows sharing the fragment's
subepoch hash, with level ``l`` seeing only keys whose level hash gives
``level_of(key) >= l``.  On the fleet each (row, level) pair is a
*virtual param row* — table row ``r * n_levels + l`` carries the
level-mixed column/sign seeds (``fragment.level_seed_mix``, applied at
param-build time) plus the row's ``PARAM_LEVEL`` — while the packet
stream is packed ONCE per fragment: the grid grows a leading level axis
(``grid = (n_levels, width_blocks, packet_blocks_total)``) that fans
every packet block out to its fragment's L counter tiles, and the §4.1
monitored mask is extended in-kernel by the per-packet level id the
host packer folded into the high ts bits
(``repro.core.fleet.fold_packet_flags`` — layout in kernel.py).  The
§4.4 single-hop mitigation rides the same mechanism: ``PARAM_MIT`` rows
additionally monitor packets flagged in ts bit 31 during the flow's
second subepoch.  ``n_levels = 1`` (cs/cms) keeps the exact PR-2/3
behavior — the level axis has extent 1 and the extra mask terms are
statically compiled out unless ``with_mitigation`` is set.

**Dense rectangle (``fleet_update``, kept as oracle/baseline).**  The
PR-1 layout: packets packed into a ``(n_frags, p_max)`` rectangle with
``grid = (n_frags, width_blocks, packet_blocks)``; every fragment pays
``pow2(hottest segment)`` padded packets (cheaply — see the dead-block
skip below — but still as HBM traffic and grid steps).  Bit-identical
to the ragged path (same param table, same in-kernel hashing) and
benchmarked against it in benchmarks/kernel_bench.py.

Shared machinery:

  * per-fragment parameters — the three hash seeds, the hash width, the
    subepoch count — ride in a small ``(n_rows, 8)`` int32 table and are
    read in-kernel as traced scalars;
  * columns are hashed modulo the fragment's true width (Lemire
    fast-range works unchanged with a dynamic modulus), so columns
    beyond ``width[f]`` are never written;
  * the packet/flow subepoch ids are masked by ``n_sub[f] - 1``, so rows
    beyond ``n_sub[f]`` are never written;
  * the stacked output is ``(n_rows, n_sub_max, width_max)`` with exact
    zeros outside each fragment's live ``[:n_sub[f], :width[f]]`` block;
  * padding packets carry ``value = 0`` and contribute nothing
    (one-hot x 0 = 0);
  * **dead-work skips**: a width block entirely beyond the fragment's
    true width (``wi * w_blk >= width[f]``) and an all-zero value block
    (pure padding) both skip the one-hot build + contraction under
    ``pl.when`` — heterogeneous fleets no longer pay the hottest
    fragment's width in compute, only in layout.

VMEM budget per grid step is unchanged from the single-fragment kernel
(the fragment axis only selects which counter tile is resident); the
ragged path adds the block->fragment map in SMEM (4 B per packet block).
See docs/kernels.md for the full derivation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import sanitize
from .kernel import (LANE, LVL_FIELD_MASK, LVL_SHIFT, MIN_BLK,
                     block_contrib, lane_tiles, pow2_width_cap,
                     resolve_interpret, resolve_value_mode, select_geometry)

# Columns of the per-fragment int32 parameter table.
PARAM_COL_SEED = 0
PARAM_SIGN_SEED = 1
PARAM_SUB_SEED = 2
PARAM_WIDTH = 3
PARAM_N_SUB = 4
PARAM_LOG2_N_SUB = 5
PARAM_LEVEL = 6   # UnivMon virtual level row id (0 for cs/cms)
PARAM_MIT = 7     # §4.4 single-hop mitigation enabled for this row
N_PARAMS = 8


def _frag_contrib(param, keys, vals, ts, *, wi, w_blk, n_sub_max,
                  log2_te, signed, value_mode, with_levels=False,
                  with_mitigation=False):
    """One fragment's packet-block contribution, parameters read through
    ``param(column)`` from its row of the SMEM table.
    ``with_levels``/``with_mitigation`` (static) gate the extended
    monitored-mask terms so cs/cms fleets compile the exact pre-UnivMon
    kernel body."""
    return block_contrib(
        keys, vals, ts,
        col_seed=param(PARAM_COL_SEED).astype(jnp.uint32),
        sign_seed=param(PARAM_SIGN_SEED).astype(jnp.uint32),
        sub_seed=param(PARAM_SUB_SEED).astype(jnp.uint32),
        width=param(PARAM_WIDTH).astype(jnp.uint32),
        n_mask=(param(PARAM_N_SUB) - 1).astype(jnp.uint32),
        shift=(jnp.uint32(log2_te)
               - param(PARAM_LOG2_N_SUB).astype(jnp.uint32)),
        wi=wi, w_blk=w_blk, n_sub_rows=n_sub_max, signed=signed,
        value_mode=value_mode,
        level=param(PARAM_LEVEL) if with_levels else 0,
        mit=param(PARAM_MIT) if with_mitigation else 0)


def _row_params(params_ref, row):
    """Accessor for one row of the flattened ``(n_rows * N_PARAMS,)``
    int32 table held in SMEM (scalar-prefetched)."""
    base = row * N_PARAMS
    return lambda k: params_ref[base + k]


def _has_values(vals):
    return jnp.max(jnp.abs(vals)) > 0.0


def fleet_update_kernel(params_ref, keys_ref, vals_ref, ts_ref, out_ref, *,
                        w_blk: int, n_sub_max: int, log2_te: int,
                        signed: bool, value_mode: str):
    f = pl.program_id(0)    # fragment index
    wi = pl.program_id(1)   # width-block index
    pj = pl.program_id(2)   # packet-block index (sequential reduction)

    @pl.when(pj == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # This fragment's hash parameters, read from SMEM as scalars.
    param = _row_params(params_ref, f)
    vals = vals_ref[0]
    # Dead-work skip: width blocks beyond this fragment's true width
    # write nothing, and all-zero value blocks (packet padding — most of
    # the dense rectangle under skew) contribute nothing.
    live = ((wi * w_blk) < param(PARAM_WIDTH)) & _has_values(vals)

    @pl.when(live)
    def _accum():
        out_ref[...] += _frag_contrib(
            param, keys_ref[0], vals, ts_ref[0], wi=wi,
            w_blk=w_blk, n_sub_max=n_sub_max, log2_te=log2_te,
            signed=signed, value_mode=value_mode).reshape(out_ref.shape)


def fleet_update_pallas(keys, vals, ts, params, *, n_sub_max: int,
                        padded_width: int, log2_te: int, signed: bool,
                        blk: int, w_blk: int, value_mode: str,
                        interpret: bool = False):
    """Lowered pallas_call over the (fragment, width, packet) grid.

    ``keys``/``vals``/``ts``: (n_frags, p_max / LANE, LANE) lane-major
    rectangles with p_max % blk == 0; ``params``: (n_frags, N_PARAMS)
    int32, scalar-prefetched into SMEM.  The packet axis is the inner
    sequential reduction, so each (fragment, width-block) counter tile
    is initialized once and revisited across packet blocks.  Returns
    ``(n_frags, n_sub_max, padded_width / LANE, LANE)`` counter tiles.
    """
    n_frags, p_rows, _ = keys.shape
    assert blk % LANE == 0 and (p_rows * LANE) % blk == 0
    assert padded_width % w_blk == 0
    if isinstance(keys, jax.core.Tracer):
        # Counts jit cache misses only (the wrapper is also callable
        # eagerly, e.g. under eval_shape by the contract verifier).
        sanitize.note_trace("sketch_update.fleet_update_pallas")
    grid = (n_frags, padded_width // w_blk, p_rows * LANE // blk)
    j_rows = w_blk // LANE
    kernel = functools.partial(
        fleet_update_kernel, w_blk=w_blk, n_sub_max=n_sub_max,
        log2_te=log2_te, signed=signed, value_mode=value_mode)
    pkt = pl.BlockSpec((1, blk // LANE, LANE),
                       lambda f, i, j, prm: (f, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pkt, pkt, pkt],
        out_specs=pl.BlockSpec((1, n_sub_max, j_rows, LANE),
                               lambda f, i, j, prm: (f, 0, i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_frags, n_sub_max, padded_width // LANE, LANE), jnp.float32),
        # Fragment and width axes touch disjoint counter tiles: parallel
        # (megacore); the packet axis is the sequential accumulation.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(params.reshape(-1), keys, vals, ts)


_fleet_update_jit = jax.jit(
    fleet_update_pallas,
    static_argnames=("n_sub_max", "padded_width", "log2_te", "signed",
                     "blk", "w_blk", "value_mode", "interpret"))


def fleet_update(keys, vals, ts, params, *, n_sub_max: int, width_max: int,
                 log2_te: int, signed: bool = True,
                 blk: Optional[int] = None, w_blk: Optional[int] = None,
                 value_mode: str = "auto", interpret="auto"):
    """Compute all subepoch-record counters for a whole fleet epoch.

    Args:
      keys/vals/ts: (n_frags, p_max) dense packet rectangle (rows are
        per-fragment streams, padded with value-0 packets).
      params: (n_frags, N_PARAMS) int32 per-fragment parameter table
        (see ``repro.core.fleet.build_params``).
      n_sub_max: max subepoch count across the fleet (power of two).
      width_max: max hash width across the fleet.
      value_mode: contraction path ("auto" resolves from concrete
        values — see ``kernel.resolve_value_mode``).

    Returns (n_frags, n_sub_max, width_max) float32 counters (exact
    integers while |c| < 2^24); entries outside a fragment's live
    ``[:n_sub[f], :width[f]]`` block are exactly zero.
    """
    interpret = resolve_interpret(interpret)
    value_mode = resolve_value_mode(value_mode, vals, interpret)
    if blk is None or w_blk is None:
        g_blk, g_w_blk = select_geometry(width_max, n_sub_max, value_mode)
        blk = g_blk if blk is None else blk
        w_blk = g_w_blk if w_blk is None else w_blk
    n_frags, p = np.shape(keys)
    pad = ((0, 0), (0, (-p) % blk))
    keys = jnp.pad(jnp.asarray(keys, jnp.uint32), pad)
    vals = jnp.pad(jnp.asarray(vals, jnp.float32), pad)
    ts = jnp.pad(jnp.asarray(ts, jnp.uint32), pad)
    w_blk = min(w_blk, pow2_width_cap(width_max))
    pad_w = (-width_max) % w_blk
    out = _fleet_update_jit(
        keys.reshape(n_frags, -1, LANE), vals.reshape(n_frags, -1, LANE),
        ts.reshape(n_frags, -1, LANE), jnp.asarray(params, jnp.int32),
        n_sub_max=n_sub_max, padded_width=width_max + pad_w,
        log2_te=log2_te, signed=signed, blk=blk, w_blk=w_blk,
        value_mode=value_mode, interpret=interpret)
    # Undo the kernel's factored (.., W/LANE, LANE) layout: free reshape.
    return (out.reshape(n_frags, n_sub_max, width_max + pad_w)
            [:, :, :width_max])


def fleet_ragged_kernel(block_frag_ref, params_ref, keys_ref, vals_ref,
                        ts_ref, out_ref, *, w_blk: int, n_sub_max: int,
                        log2_te: int, signed: bool, value_mode: str,
                        n_levels: int, with_mitigation: bool):
    """Ragged CSR body: one packet block of the flat stream, applied to
    its owning row's counter tile (selected by the BlockSpec index maps
    from the scalar-prefetched ``block_frag`` map; with UnivMon level
    rows, the leading level grid axis fans the same packet block out to
    the fragment's ``n_levels`` tiles)."""
    lvl = pl.program_id(0)  # UnivMon level row
    wi = pl.program_id(1)   # width-block index
    pj = pl.program_id(2)   # packet-block index (sequential reduction)

    cur = block_frag_ref[pj]
    prev = block_frag_ref[jnp.maximum(pj - 1, 0)]

    # First packet block of this fragment: zero its counter tile.  The
    # map is non-decreasing and every fragment owns >= 1 block, so every
    # output tile is initialized exactly once per (level, width) block.
    @pl.when((pj == 0) | (cur != prev))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    param = _row_params(params_ref, cur * n_levels + lvl)
    vals = vals_ref[...]
    # Dead-work skip: width blocks beyond this fragment's true width and
    # all-zero value blocks (blk-alignment / shape-bucket padding).
    live = ((wi * w_blk) < param(PARAM_WIDTH)) & _has_values(vals)
    with_levels = n_levels > 1
    if with_levels:
        # Level rows see a ~2^-level subsample: skip blocks with no key
        # at this row's level (the packer folded level_of into ts).
        lvl_pkt = ((ts_ref[...] >> np.uint32(LVL_SHIFT))
                   & np.uint32(LVL_FIELD_MASK)).astype(jnp.int32)
        live = live & (jnp.max(lvl_pkt) >= param(PARAM_LEVEL))

    @pl.when(live)
    def _accum():
        out_ref[...] += _frag_contrib(
            param, keys_ref[...], vals, ts_ref[...], wi=wi, w_blk=w_blk,
            n_sub_max=n_sub_max, log2_te=log2_te, signed=signed,
            value_mode=value_mode, with_levels=with_levels,
            with_mitigation=with_mitigation).reshape(out_ref.shape)


def fleet_update_ragged_pallas(keys, vals, ts, params, block_frag, *,
                               n_sub_max: int, padded_width: int,
                               log2_te: int, signed: bool, blk: int,
                               w_blk: int, value_mode: str,
                               n_levels: int = 1,
                               with_mitigation: bool = False,
                               interpret: bool = False):
    """Lowered pallas_call over the (level, width, packet-block) grid.

    ``keys``/``vals``/``ts``: the flat CSR stream as lane-major
    ``(n_blocks * blk / LANE, LANE)`` tiles; ``block_frag``:
    ``(n_blocks,)`` non-decreasing int32 block->*packet row* map
    (``repro.core.fleet.pack_csr`` builds both).  ``params`` has
    ``n_levels`` virtual rows per packet row — table/output row
    ``bf[pj] * n_levels + l`` — so the packet stream is packed once per
    fragment and the level axis fans it out in-grid.  Both the map and
    the flattened table are scalar-prefetched into SMEM.  The packet
    axis is the inner sequential reduction, so each row's counter tile
    is visited over a consecutive ``pj`` range and stays VMEM-resident
    while its blocks stream through.  Returns
    ``(n_rows, n_sub_max, padded_width / LANE, LANE)`` counter tiles.
    """
    n_rows = params.shape[0]
    nb = block_frag.shape[0]
    assert blk % LANE == 0 and keys.shape == (nb * blk // LANE, LANE)
    assert padded_width % w_blk == 0 and n_rows % n_levels == 0
    if isinstance(keys, jax.core.Tracer):
        # Retrace probe: bumps only when _fleet_update_ragged_jit
        # misses its compile cache (see repro.sanitize).
        sanitize.note_trace("sketch_update.fleet_update_ragged_pallas")
    grid = (n_levels, padded_width // w_blk, nb)
    j_rows = w_blk // LANE
    kernel = functools.partial(
        fleet_ragged_kernel, w_blk=w_blk, n_sub_max=n_sub_max,
        log2_te=log2_te, signed=signed, value_mode=value_mode,
        n_levels=n_levels, with_mitigation=with_mitigation)
    pkt = pl.BlockSpec((blk // LANE, LANE),
                       lambda l, i, j, bf, prm: (j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[pkt, pkt, pkt],
        out_specs=pl.BlockSpec(
            (1, n_sub_max, j_rows, LANE),
            lambda l, i, j, bf, prm: (bf[j] * n_levels + l, 0, i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_rows, n_sub_max, padded_width // LANE, LANE), jnp.float32),
        # Level and width blocks touch disjoint counter tiles: parallel
        # (megacore); the packet axis accumulates per row: sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_frag, params.reshape(-1), keys, vals, ts)


# Buffer donation of the per-window packet streams was evaluated and
# rejected: XLA can only reuse a donated buffer by aliasing it to an
# output of matching shape/dtype, and the uint32/f32 packet streams
# never match the f32 counter stack — donation would just emit
# "donated buffers were not usable" warnings every window.  The streams
# are transient Python references; they free as soon as the dispatch
# consumes them.
@functools.partial(jax.jit, static_argnames=(
    "n_sub_max", "width_max", "padded_width", "log2_te", "signed", "blk",
    "w_blk", "value_mode", "n_levels", "with_mitigation", "interpret"))
def _fleet_update_ragged_jit(keys, vals, ts, params, block_frag, *,
                             width_max: int, **kw):
    """The ragged launch and the reshape/slice that undoes its factored
    (.., W/LANE, LANE) layout, compiled as one program."""
    out = fleet_update_ragged_pallas(keys, vals, ts, params, block_frag,
                                     **kw)
    return (out.reshape(out.shape[0], kw["n_sub_max"], kw["padded_width"])
            [:, :, :width_max])


def fleet_update_ragged(keys, vals, ts, params, block_frag, *,
                        n_sub_max: int, width_max: int, log2_te: int,
                        signed: bool = True, blk: int = MIN_BLK,
                        w_blk: Optional[int] = None,
                        value_mode: str = "auto", n_levels: int = 1,
                        with_mitigation: bool = False, interpret="auto"):
    """Compute all subepoch-record counters for a CSR-packed fleet epoch
    (or epoch window — rows are (epoch, fragment) pairs, see module doc).

    Args:
      keys/vals/ts: (n_blocks * blk,) flat CSR packet stream, fragment
        segments blk-aligned and value-0 padded (``pack_csr``).
      params: (n_rows, N_PARAMS) int32 parameter table; with
        ``n_levels > 1`` each packet row owns ``n_levels`` consecutive
        virtual level rows (``n_rows = n_packet_rows * n_levels``).
      block_frag: (n_blocks,) int32 non-decreasing block->packet-row
        map; every packet row must own at least one block.
      blk: must match the packer's block size (the CSR alignment, which
        is also the kernel's packet tile: a multiple of
        ``kernel.MIN_BLK`` on the chip, of LANE in interpret mode).
      value_mode: contraction path ("auto" resolves from concrete
        values — see ``kernel.resolve_value_mode``).
      n_levels: UnivMon level rows per packet row (1 = cs/cms).
      with_mitigation: compile the §4.4 second-subepoch mask term
        (PARAM_MIT rows; requires the packer's folded ts).

    Returns (n_rows, n_sub_max, width_max) float32 counters (exact
    integers while |c| < 2^24); entries outside a row's live
    ``[:n_sub[r], :width[r]]`` block are exactly zero.
    """
    interpret = resolve_interpret(interpret)
    value_mode = resolve_value_mode(value_mode, vals, interpret)
    if w_blk is None:
        _, w_blk = select_geometry(width_max, n_sub_max, value_mode)
    w_blk = min(w_blk, pow2_width_cap(width_max))
    pad_w = (-width_max) % w_blk
    return _fleet_update_ragged_jit(
        lane_tiles(keys, jnp.uint32), lane_tiles(vals, jnp.float32),
        lane_tiles(ts, jnp.uint32), jnp.asarray(params, jnp.int32),
        jnp.asarray(block_frag, jnp.int32), n_sub_max=n_sub_max,
        width_max=width_max, padded_width=width_max + pad_w,
        log2_te=log2_te, signed=signed, blk=blk, w_blk=w_blk,
        value_mode=value_mode, n_levels=n_levels,
        with_mitigation=with_mitigation, interpret=interpret)


def fleet_update_loop(keys, vals, ts, params, *, n_sub_max: int,
                      width_max: int, log2_te: int, signed: bool = True,
                      backend: str = "ref", **kw):
    """Per-row loop baseline (and oracle): one ``sketch_update`` dispatch
    per parameter row, results padded into the stacked layout.

    ``backend="ref"`` gives the jnp scatter-add oracle; ``"pallas"`` gives
    the loop-of-kernels baseline the fleet path replaces (benchmarked in
    benchmarks/kernel_bench.py).  With UnivMon virtual level rows,
    ``params`` has ``n_levels`` rows per packet row of ``keys`` (inferred
    from the shape ratio) and row ``f * n_levels + l`` re-dispatches
    packet row ``f`` at its own level/mitigation parameters.
    """
    from .ops import sketch_update

    params = np.asarray(params)
    n_rows = params.shape[0]
    assert n_rows % keys.shape[0] == 0
    n_levels = n_rows // keys.shape[0]
    out = np.zeros((n_rows, n_sub_max, width_max), np.float32)
    for r in range(n_rows):
        f = r // n_levels
        width = int(params[r, PARAM_WIDTH])
        n_sub = int(params[r, PARAM_N_SUB])
        o = sketch_update(
            jnp.asarray(keys[f]), jnp.asarray(vals[f]), jnp.asarray(ts[f]),
            width=width, n_sub=n_sub, log2_te=log2_te,
            col_seed=int(params[r, PARAM_COL_SEED]),
            sign_seed=int(params[r, PARAM_SIGN_SEED]),
            sub_seed=int(params[r, PARAM_SUB_SEED]),
            level=int(params[r, PARAM_LEVEL]),
            mitigation=bool(params[r, PARAM_MIT]),
            signed=signed, backend=backend, **kw)
        out[r, :n_sub, :width] = np.asarray(o)
    return out
