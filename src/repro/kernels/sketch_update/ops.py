"""Public wrapper for the sketch_update kernel: padding + mode/geometry
resolution + dispatch + the output-side overflow guard.

On CPU (this container) the Pallas body runs in interpret mode; on TPU the
same call lowers to Mosaic.  ``backend="ref"`` selects the pure-jnp oracle.

Why a matmul and not a scatter
------------------------------
A sketch update is a histogram: ``counters[sub(p), col(p)] += val(p)`` for
every packet ``p``.  TPUs have no efficient data-dependent scatter, but
they have an MXU that multiplies (8,128)-tiled matrices at full rate.
The kernel therefore recasts the histogram as two one-hot contractions:

    contribution[s, c] = sum_p onehot_sub[s, p] * val'[p] * onehot_col[p, c]

where ``val' = value * sign * monitored`` folds in the Count-Sketch sign
and the §4.1 temporal-sampling mask.  Building the one-hots is cheap VPU
work (an iota compare); the contraction is a single
(n_sub x BLK) @ (BLK x W_BLK) matmul per packet block.  Because every
hash (column, sign, packet/flow subepoch) is computed in-kernel in uint32
arithmetic, HBM traffic is exactly: packet stream in, counters out.

Padding contract
----------------
Packet arrays are padded to a BLK multiple with ``value = 0`` entries —
a zero value times any one-hot contributes nothing, so padding needs no
masking (and the kernel skips all-zero value blocks outright).  The width
is padded to a W_BLK multiple but columns are hashed modulo the *true*
width, so padded columns are never written and the wrapper can slice them
off.

Numerical contract
------------------
Counters are f32 accumulations of integer contributions: exact while
|counter| < 2^24 (``kernel.EXACT_BOUND``), which this wrapper now
*enforces* — it raises ``OverflowError`` instead of returning
silently-inexact counters (``check_overflow=False`` opts out; the check
is skipped automatically under an outer trace).  The contraction dtype
is a free knob on top of that contract: one-hots are 0/1 (exact in any
float dtype) and ``value_mode="auto"`` picks the cheapest exact path —
a single bf16 contraction for pure counting workloads (integer
|v| <= 256), a two-limb bf16 split (``val = hi*256 + lo``) for integer
|v| < 2^16, and the original f32 HIGHEST contraction otherwise.  All
three agree bit-for-bit with ref.py's jnp scatter oracle and the numpy
fragment path in core/fragment.py (tests/test_kernels.py,
tests/test_properties.py).

Fleet variant
-------------
``fleet.py`` batches the same kernel body across every fragment of a
network epoch — the default *ragged CSR* layout streams blk-aligned
per-fragment segments with a scalar-prefetched block->fragment map (one
dispatch can even cover a multi-epoch window: rows of the per-fragment
parameter table are (epoch, fragment) pairs), and the dense-rectangle
layout survives as the oracle.  See docs/kernels.md for the packing
layouts and the VMEM budget derivation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ... import sanitize
from .kernel import (abs_peak, check_output_peak, lane_tiles,
                     pow2_width_cap, resolve_interpret, resolve_value_mode,
                     select_geometry, sketch_update_pallas)
from .ref import sketch_update_ref


def _pad_to(x, m):
    p = (-x.shape[0]) % m
    if p == 0:
        return x
    return jnp.pad(x, (0, p))


def _guard_peak(out, check_overflow: bool):
    """Output-side exactness guard (shared contract with the fleet
    runner's peak check).  Skipped under an outer trace, where the peak
    is abstract."""
    if check_overflow and not isinstance(out, jax.core.Tracer):
        peak = float(abs_peak(out)) if out.size else 0.0
        check_output_peak(peak)
    return out


@functools.partial(jax.jit, static_argnames=(
    "width", "n_sub", "log2_te", "col_seed", "sign_seed", "sub_seed",
    "signed", "blk", "w_blk", "value_mode", "level", "mitigation",
    "interpret"))
def _sketch_update_jit(keys, vals, ts, *, width: int, n_sub: int,
                       log2_te: int, col_seed: int, sign_seed: int,
                       sub_seed: int, signed: bool, blk: int, w_blk: int,
                       value_mode: str, level: int, mitigation: bool,
                       interpret: bool):
    sanitize.note_trace("sketch_update._sketch_update_jit")
    keys = lane_tiles(_pad_to(keys.astype(jnp.uint32), blk), jnp.uint32)
    vals = lane_tiles(_pad_to(vals.astype(jnp.float32), blk), jnp.float32)
    ts = lane_tiles(_pad_to(ts.astype(jnp.uint32), blk), jnp.uint32)
    w_blk = min(w_blk, pow2_width_cap(width))
    pad_w = (-width) % w_blk
    out = sketch_update_pallas(
        keys, vals, ts, hash_width=width, padded_width=width + pad_w,
        n_sub=n_sub, log2_te=log2_te, col_seed=col_seed,
        sign_seed=sign_seed, sub_seed=sub_seed, signed=signed, blk=blk,
        w_blk=w_blk, value_mode=value_mode, level=level,
        mitigation=mitigation, interpret=interpret)
    # Undo the kernel's factored (n_sub, W/LANE, LANE) layout: a free
    # contiguous reshape outside the kernel.
    return out.reshape(n_sub, width + pad_w)[:, :width]


def sketch_update(keys, vals, ts, *, width: int, n_sub: int, log2_te: int,
                  col_seed: int, sign_seed: int, sub_seed: int,
                  signed: bool = True, backend: str = "pallas",
                  blk: Optional[int] = None, w_blk: Optional[int] = None,
                  value_mode: str = "auto", level: int = 0,
                  mitigation: bool = False, interpret="auto",
                  check_overflow: bool = True):
    """Compute all subepoch-record counters for one fragment epoch.

    Returns (n_sub, width) float32 counters (exact integers < 2^24,
    enforced via ``check_overflow``).  Padding keys with value 0
    contributes nothing (one-hot x 0 = 0).  ``blk``/``w_blk`` default to
    ``kernel.select_geometry`` for the resolved value mode;
    ``interpret="auto"`` (default) compiles on TPU and interprets on CPU.
    ``level``/``mitigation`` select the UnivMon-level / §4.4 monitored
    terms; both require ``ts`` with the packer's folded high bits
    (``core.fleet.fold_packet_flags`` — see the packed-ts layout in
    kernel.py).
    """
    if backend == "ref":
        out = sketch_update_ref(
            keys, vals, ts, width=width, n_sub=n_sub, log2_te=log2_te,
            col_seed=col_seed, sign_seed=sign_seed, sub_seed=sub_seed,
            signed=signed, level=level, mitigation=mitigation)
        return _guard_peak(out, check_overflow)
    interpret = resolve_interpret(interpret)
    value_mode = resolve_value_mode(value_mode, vals, interpret)
    if blk is None or w_blk is None:
        g_blk, g_w_blk = select_geometry(width, n_sub, value_mode)
        blk = g_blk if blk is None else blk
        w_blk = g_w_blk if w_blk is None else w_blk
    out = _sketch_update_jit(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ts), width=width,
        n_sub=n_sub, log2_te=log2_te, col_seed=col_seed,
        sign_seed=sign_seed, sub_seed=sub_seed, signed=signed, blk=blk,
        w_blk=w_blk, value_mode=value_mode, level=level,
        mitigation=mitigation, interpret=interpret)
    return _guard_peak(out, check_overflow)
