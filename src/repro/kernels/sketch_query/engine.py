"""Device-resident batched query plane (paper §4.3): gather + merge over a
window's stacked counters, without the bulk device->host transfer.

The fleet update path (``kernels/sketch_update/fleet.py``) leaves a whole
epoch window's counters on device as one ``(E, F, n_sub_max, width_max)``
f32 stack.  Until now, answering a single point query forced the entire
stack across the host boundary (megabytes per window) so the numpy query
plane could gather a handful of counters from it.  FPGA/switch sketch
accelerators answer queries *next to the counters* for exactly this
reason — the query is a tiny gather, the transfer is the whole sketch.

This module is the TPU twin: one jitted fused pass that

  1. recomputes every fragment's column/sign/subepoch hashes for the key
     batch on device (same uint32 avalanche arithmetic as
     ``repro.core.hashing`` — the hashing module is backend-polymorphic
     via its ``xp`` parameter, so the *same code* runs here under jnp);
  2. gathers each (epoch, fragment)'s raw estimate
     ``stack[e, f, sub(e,f,k), col(e,f,k)]`` for all keys at once (one
     XLA gather over the resident stack);
  3. applies the §4.3 fragment-merge per epoch — min across fragments for
     Count-Min, a masked median for Count Sketch (``frag_sel`` restricts
     the merge to the queried flows' on-path fragments, §4.3 Step 1);
  4. sums the per-epoch estimates over the window (O_Q = Sum(O)).

A request whose keys lie on many paths goes through
``fleet_window_query_paths`` instead: one launch per resident stack and
key chunk for every path at once.  The §4.3 Step-1 selection is then per
key, as row indices (``path_rows[path_id]``), so the gather reads only
each key's L on-path rows — ``(E, L, K)`` counters, not the ``(E, R, K)``
a mask would keep — and the merge runs over those L slots with the key's
own on-path count.

Only the key batch and the small per-epoch seed tables cross *into* the
device, and only the ``(K,)`` estimate vector crosses *back* — the
counter stack never moves.  A hand-written Pallas kernel buys nothing
here: the work is a data-dependent gather plus tiny reductions (no MXU
contraction to feed), which XLA already lowers well, and the jnp form
runs identically on CPU where the update kernels use interpret mode.

Exactness: counters are exact integers in f32 (the update path enforces
``|c| < 2^24``) and the x``n`` proportional scaling (§1) multiplies by a
power of two, so every per-fragment estimate is exact in f32; min/median
*selection* is therefore identical to the float64 host oracle
(``repro.core.query.fleet_query_window``), and only the CS median's
midpoint average and the final window sum accumulate f32 rounding —
within a few ULPs (<< 1e-6 relative), which is the documented contract.

Key batches are padded to power-of-two buckets so a replay's varying
query sizes trigger O(log K) compiles instead of one per batch size; the
multi-path entry pads larger requests to whole chunks of ``KEY_CHUNK``
keys, so one compiled shape serves them all.

UnivMon rides the same engine: the window stack's rows are virtual
(fragment, level) pairs whose per-level mixed seeds were baked into the
parameter table at build time, so ``fleet_window_query_device`` with a
level-row selection answers level-l (e.g. frequency = level-0) queries
unchanged, ``um_window_query_device`` answers ALL levels in one batched
gather/merge (the §6.2 G-sum inputs), and ``um_gsum_device`` runs the
top-down Y-recursion next to them.  §4.4 mitigation is a second gather
at ``sub + n/2`` averaged on PARAM_MIT rows (``single_hop=True``).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import obs, sanitize
from ...core import hashing as H
from ..sketch_update.fleet import (PARAM_COL_SEED, PARAM_MIT, PARAM_N_SUB,
                                   PARAM_SIGN_SEED, PARAM_SUB_SEED,
                                   PARAM_WIDTH)

#: Smallest compiled key-batch size (batches are padded up to the next
#: power of two — O(log K) compiled variants across a replay).
KEY_BUCKET_MIN = 8

#: Keys per launch of a multi-path request (``fleet_window_query_paths``):
#: larger requests are padded to whole chunks, so one compiled shape
#: serves every large request.
KEY_CHUNK = 1 << 15


def key_bucket(n_keys: int) -> int:
    """Power-of-two key-batch bucket, floored at ``KEY_BUCKET_MIN``."""
    return max(KEY_BUCKET_MIN, 1 << max(int(n_keys) - 1, 0).bit_length())


def _raw_at(stack, rows, col_seeds, sign_seeds, sub_seeds, ns, widths,
            use, keys, *, signed: bool):
    """The gather at given rows: ``rows`` and every per-row argument
    broadcast to ``(E, X, K)`` (X fleet rows, or a key's path slots);
    ``use`` is None (no §4.4 average) or the bool mask of the elements
    that take it.  Returns the (E, X, K) raw estimates (signed,
    §4.4-averaged, x n scaled)."""
    k = keys[None, None, :]                               # (1, 1, K)
    col = H.hash_mod(k, col_seeds, widths, xp=jnp)        # (E, X, K)
    sub = H.hash_pow2(k, sub_seeds, ns, xp=jnp)
    e_idx = jnp.arange(stack.shape[0])[:, None, None]
    raw = stack[e_idx, rows, sub, col]                    # (E, X, K)
    if use is not None:
        # §4.4: single-hop flows carry a second subepoch record at
        # sub + n/2 on mitigation rows; average the two (counters are
        # exact f32 integers, so the /2 midpoint is within the same
        # rounding contract as the CS median midpoint).
        sub2 = (sub + (ns >> 1)) & (ns - 1)
        raw2 = stack[e_idx, rows, sub2, col]
        raw = jnp.where(use, 0.5 * (raw + raw2), raw)
    if signed:
        raw = raw * H.hash_sign(k, sign_seeds, xp=jnp).astype(jnp.float32)
    # Proportional scaling to the epoch (x n, §1): n is a power of two,
    # so the product stays exact in f32.
    return raw * ns.astype(jnp.float32)


def _gather_raw(stack, col_seeds, sign_seeds, sub_seeds, ns, widths,
                mit_rows, keys, *, signed: bool, mitigate: bool):
    """Shared gather over every row: (E, R, S, W) stack + (K,) keys ->
    (E, R, K) raw per-row estimates."""
    ns = ns[None, :, None]
    use = (mit_rows[None, :, None] & (ns >= 2)) if mitigate else None
    return _raw_at(stack, jnp.arange(stack.shape[1])[None, :, None],
                   col_seeds[:, :, None], sign_seeds[:, :, None],
                   sub_seeds[:, :, None], ns, widths[None, :, None], use,
                   keys, signed=signed)


def _masked_merge(raw, frag_sel, *, kind: str):
    """§4.3 merge across the row axis (axis 1) with the on-path
    selection passed as data: min for CMS, masked median otherwise.

    ``frag_sel`` is (R,) for a window-uniform selection, or (E, R) when
    the on-path set differs per epoch (fragment churn: a switch that
    dies mid-window is live for some epochs and masked for the rest).
    Every epoch must keep at least one selected row — the entry points
    raise before tracing otherwise (an all-masked epoch would min/median
    over +inf and poison the window sum).
    """
    sel = frag_sel if frag_sel.ndim == 2 else frag_sel[None, :]
    masked = jnp.where(sel[:, :, None], raw, jnp.inf)
    if kind == "cms":
        return jnp.min(masked, axis=1)                    # (E, K)
    # Masked median: +inf-masked entries sort to the top, so ranks
    # (m-1)//2 and m//2 of the ascending sort are the two middle
    # *selected* values (m = number of on-path rows in that epoch).
    srt = jnp.sort(masked, axis=1)
    m = jnp.sum(sel, axis=1).astype(jnp.int32)[:, None, None]  # (E', 1, 1)
    shape = (srt.shape[0], 1, srt.shape[2])
    lo = jnp.take_along_axis(srt, jnp.broadcast_to((m - 1) // 2, shape),
                             axis=1)
    hi = jnp.take_along_axis(srt, jnp.broadcast_to(m // 2, shape),
                             axis=1)
    return (0.5 * (lo + hi))[:, 0, :]


@functools.partial(jax.jit, static_argnames=("kind", "mitigate"))
def _gather_merge(stack, col_seeds, sign_seeds, sub_seeds, ns, widths,
                  frag_sel, mit_rows, keys, *, kind: str, mitigate: bool):
    """Fused device pass: (E, R, S, W) stack + (K,) keys -> (K,) window
    estimates (R = fleet rows; fragments, or fragment×level pairs).

    ``col_seeds``/``sign_seeds``/``sub_seeds`` are (E, R) uint32 (seeds
    are per-epoch); ``ns``/``widths`` are (R,) int32 (frozen across the
    window — the ``run_window`` contract); ``frag_sel`` is (R,) bool, or
    (E, R) when liveness differs per epoch; ``mit_rows`` is (R,) bool.
    Passing the selection as data (rather than slicing rows out) keeps
    the compiled shape independent of the queried path.
    """
    sanitize.note_trace("sketch_query._gather_merge")
    raw = _gather_raw(stack, col_seeds, sign_seeds, sub_seeds, ns, widths,
                      mit_rows, keys, signed=kind in ("cs", "um"),
                      mitigate=mitigate)
    return _masked_merge(raw, frag_sel, kind=kind).sum(axis=0)  # (K,)


def _prep_window_params(stack, params_by_epoch, allow_row_pad: bool = False):
    """Stack + frozen-ns validation shared by the window-query entry
    points.  Returns (params (E, R, N_PARAMS), ns, widths).

    ``allow_row_pad``: a mesh-sharded stack may carry trailing pad rows
    (fragments padded so rows divide the switch axis); the param table
    still covers only the real rows and the merge slices the pad off.
    """
    params = np.stack([np.asarray(p, np.int32) for p in params_by_epoch])
    e_count, n_rows = params.shape[:2]
    if allow_row_pad:
        assert stack.shape[0] == e_count and stack.shape[1] >= n_rows, \
            f"stack {stack.shape} does not cover params ({e_count}, {n_rows})"
    else:
        assert tuple(stack.shape[:2]) == (e_count, n_rows), \
            f"stack {stack.shape} does not match params ({e_count}, {n_rows})"
    ns = params[0, :, PARAM_N_SUB]
    widths = params[0, :, PARAM_WIDTH]
    assert (params[:, :, PARAM_N_SUB] == ns).all() and \
        (params[:, :, PARAM_WIDTH] == widths).all(), \
        "device window query requires ns/widths frozen across the window"
    return params, ns, widths


def fleet_window_query_device(stack, params_by_epoch: Sequence[np.ndarray],
                              keys: np.ndarray, kind: str,
                              frag_sel: Optional[np.ndarray] = None,
                              single_hop: bool = False,
                              mesh=None) -> np.ndarray:
    """Batched window point-query on a still-resident window stack.

    Args:
      stack: ``(E, R, n_sub_max, width_max)`` f32 counter stack — a
        device array on TPU (the point: it never transfers), any
        jnp-compatible array on CPU.  R is the fleet's row count
        (fragments; fragment×level pairs for UnivMon).
      params_by_epoch: E host ``(R, N_PARAMS)`` int32 fleet parameter
        tables (seeds differ per epoch; ``n_sub``/``width`` columns must
        be frozen across the window, as ``run_window`` guarantees).
      keys: (K,) uint32 key batch.
      kind: "cs" | "cms" | "um" (um rows are signed CS levels; pass the
        queried level's rows via ``frag_sel``).
      frag_sel: optional (R,) bool on-path row mask (§4.3 Step 1), or
        (E, R) when the selection differs per epoch (fragment liveness
        under churn).  Every epoch must select at least one row —
        raises ``ValueError`` otherwise; an all-masked epoch has no
        survivor to merge and would silently return an inf-poisoned
        (cms) or padded-rank (cs) estimate.
      single_hop: apply the §4.4 second-subepoch average on PARAM_MIT
        rows (the queried flows are single-hop — uniform per path
        group).
      mesh: optional ``("switch",)`` device mesh.  When given, the stack
        is treated as row-sharded over the switch axis (possibly with
        trailing pad rows so rows divide the axis) and the merge runs as
        a ``shard_map``: each shard gathers its own rows' raw estimates
        locally and ``all_gather``s only the ``(E, R, K)`` estimate
        slices — never the counter shards — into the same masked
        min/median merge.  Bit-identical to ``mesh=None`` on the
        un-padded rows (docs/sharding.md).

    Returns the (K,) float64 window estimates — numerically within a few
    f32 ULPs of ``repro.core.query.fleet_query_window`` on the host copy
    of the same stack (exact-selection argument in the module doc).
    """
    with obs.span("query.prep"):
        keys = np.asarray(keys, dtype=np.uint32)
        n_keys = len(keys)
        params, ns, widths = _prep_window_params(
            stack, params_by_epoch, allow_row_pad=mesh is not None)
        n_rows = params.shape[1]
        if frag_sel is None:
            frag_sel = np.ones(n_rows, bool)
        frag_sel = np.asarray(frag_sel, bool)
        sel2 = np.atleast_2d(frag_sel)
        if not sel2.any(axis=1).all():
            bad = np.flatnonzero(~sel2.any(axis=1))
            raise ValueError(
                "fleet_window_query_device: no on-path fragment selected "
                f"(epoch offsets {bad.tolist()} of {len(params_by_epoch)}) "
                "— an all-masked merge has no survivor and would poison "
                "the window sum; drop these epochs (blind-epoch "
                "extrapolation) or widen the selection")
        if n_keys == 0:
            return np.zeros(n_keys)
        mit_rows = params[0, :, PARAM_MIT] != 0
        mitigate = bool(single_hop) and bool(mit_rows.any())
        kb = key_bucket(n_keys)
        keys_pad = np.zeros(kb, np.uint32)
        keys_pad[:n_keys] = keys
        if mesh is None:
            host_args = (params[:, :, PARAM_COL_SEED].astype(np.uint32),
                         params[:, :, PARAM_SIGN_SEED].astype(np.uint32),
                         params[:, :, PARAM_SUB_SEED].astype(np.uint32),
                         ns.astype(np.int32), widths.astype(np.int32),
                         frag_sel, mit_rows, keys_pad)
            # a device-resident stack crosses nothing
            h2d = sum(a.nbytes for a in host_args) + (
                stack.nbytes if isinstance(stack, np.ndarray) else 0)
    if mesh is not None:
        est = _sharded_window_query(mesh, stack, params, ns, widths, sel2,
                                    mit_rows, keys_pad, kind=kind,
                                    mitigate=mitigate)
        return est[:n_keys].astype(np.float64)
    # Everything inside the guard is device compute with *explicit*
    # boundary crossings only (jnp.asarray in, jax.device_get out):
    # under REPRO_SANITIZE=1 any implicit transfer raises.  The padded
    # (KB,) estimate vector is fetched whole and sliced host-side — an
    # eager device-array slice would dispatch a dynamic_slice whose
    # start index is itself an implicit host->device transfer.
    with sanitize.transfer_guard():
        with obs.span("query.launch", h2d_bytes=h2d, keys=n_keys):
            out = _gather_merge(
                jnp.asarray(stack), *(jnp.asarray(a) for a in host_args),
                kind=kind, mitigate=mitigate)
        # KB floats across the boundary — the only counters-derived
        # bytes that ever leave the device on this path
        with obs.span("query.sync"):
            est = jax.device_get(out)
    return est[:n_keys].astype(np.float64)


def key_chunk(n_keys: int) -> int:
    """Keys per launch of ``fleet_window_query_paths``: ``KEY_CHUNK``
    for a request of more keys (padded to whole chunks), else the
    request's pow2 ``key_bucket``."""
    return min(KEY_CHUNK, key_bucket(n_keys))


def _slot_merge(raw, valid, *, kind: str):
    """§4.3 merge across each key's path slots (axis 1 of the (E, L, K)
    raw estimates), ``valid`` the (L, K) mask of the slots that hold an
    on-path row: min for CMS, else the median of the valid slots."""
    masked = jnp.where(valid[None], raw, jnp.inf)
    if kind == "cms":
        return jnp.min(masked, axis=1)                    # (E, K)
    # An odd-even transposition network of min/max sorts the L slots
    # elementwise (exact selections; +inf-masked slots end on top), so
    # ranks (m-1)//2 and m//2 are the two middle on-path values (m = the
    # key's on-path row count), picked by a select per slot.
    srt = [masked[:, slot] for slot in range(masked.shape[1])]
    for rnd in range(len(srt)):
        for i in range(rnd % 2, len(srt) - 1, 2):
            srt[i], srt[i + 1] = (jnp.minimum(srt[i], srt[i + 1]),
                                  jnp.maximum(srt[i], srt[i + 1]))
    m = jnp.sum(valid, axis=0, dtype=jnp.int32)           # (K,)
    lo_rank, hi_rank = (m - 1) // 2, m // 2
    lo = hi = srt[0]
    for slot in range(1, len(srt)):
        lo = jnp.where(lo_rank == slot, srt[slot], lo)
        hi = jnp.where(hi_rank == slot, srt[slot], hi)
    return 0.5 * (lo + hi)


@functools.partial(jax.jit, static_argnames=("kind", "mitigate"))
def _gather_merge_rows(stack, row_tab, path_rows, path_hop, keys, path_id,
                       *, kind: str, mitigate: bool):
    """Fused device pass over each key's own on-path rows: (E, R, S, W)
    stack + (K,) keys of many paths -> (K,) window estimates.

    ``row_tab`` is the (R, 3E + 4) uint32 per-row table (``_row_table``);
    ``path_rows`` the (P, L) int32 fleet rows of each path, -1 in unused
    slots; ``path_hop`` the (P,) bool single-hop flag of each path;
    ``path_id`` the (K,) int32 path of each key.  Each key's rows, seeds,
    ``n`` and width come from one row gather of its path's (L, columns)
    block, so the stack gather reads (E, L, K) counters, L the longest
    path, never the (E, R, K) of every row.
    """
    sanitize.note_trace("sketch_query._gather_merge_rows")
    e_count = stack.shape[0]
    n_paths, n_slots = path_rows.shape
    valid = path_rows >= 0
    ptab = jnp.concatenate(
        [row_tab[jnp.maximum(path_rows, 0)],              # (P, L, C)
         valid[..., None].astype(jnp.uint32),
         (valid & path_hop[:, None])[..., None].astype(jnp.uint32)],
        axis=-1)
    n_cols = ptab.shape[-1]
    per_key = ptab.reshape(n_paths, n_slots * n_cols)[path_id]
    cols = per_key.T.reshape(n_slots, n_cols, -1).transpose(1, 0, 2)
    col_seeds, sign_seeds, sub_seeds = (
        cols[i * e_count:(i + 1) * e_count] for i in range(3))  # (E, L, K)
    ns, widths, mit, rows, valid, hop = (
        cols[3 * e_count + i] for i in range(6))          # (L, K) each
    ns = ns.astype(jnp.int32)[None]
    use = ((mit != 0) & (hop != 0))[None] & (ns >= 2) if mitigate else None
    raw = _raw_at(stack, rows.astype(jnp.int32)[None], col_seeds,
                  sign_seeds, sub_seeds, ns, widths.astype(jnp.int32)[None],
                  use, keys, signed=kind in ("cs", "um"))
    return _slot_merge(raw, valid != 0, kind=kind).sum(axis=0)  # (K,)


def _row_table(params, ns, widths) -> np.ndarray:
    """(R, 3E + 4) uint32 table of one window stack's rows: the E column,
    sign and subepoch seeds, then ``n``, width, the §4.4 mitigation flag
    and the row's own index."""
    e_count, n_rows = params.shape[:2]
    per_epoch = [params[:, :, c].T for c in (PARAM_COL_SEED, PARAM_SIGN_SEED,
                                             PARAM_SUB_SEED)]
    per_row = [ns, widths, params[0, :, PARAM_MIT] != 0, np.arange(n_rows)]
    return np.concatenate(per_epoch + [np.stack(per_row, axis=1)],
                          axis=1).astype(np.uint32)


def fleet_window_query_paths(stacks, keys: np.ndarray,
                             path_rows: np.ndarray, path_id: np.ndarray,
                             kind: str,
                             single_hop: Optional[np.ndarray] = None,
                             ) -> np.ndarray:
    """Window point-query of keys on many paths at once: one gather/merge
    launch per resident stack and key chunk, each key merged over its
    own path's rows only (§4.3 Step 1 as per-key row indices).

    Args:
      stacks: the request's ``(stack, params_by_epoch)`` pairs, as
        ``fleet_window_query_device`` takes them one at a time; the
        estimates are summed over them in this order.
      keys: (K,) uint32 key batch.
      path_rows: (P, L) int fleet rows of each path (fragments, or a
        UnivMon level's rows), -1 in a path's unused slots.  Every path
        needs at least one row — raises ``ValueError`` otherwise.
      path_id: (K,) int index into ``path_rows`` of each key's path.
      kind: "cs" | "cms" | "um".
      single_hop: optional (P,) bool: the §4.4 second-subepoch average
        applies to that path's keys on PARAM_MIT rows.

    Keys are padded to whole launches of ``key_chunk(K)`` keys and paths
    to ``key_bucket(P)``; keys, paths and the path table cross to the
    device once and serve every stack.  Returns the (K,) float64 window
    estimates, bit-identical to one ``fleet_window_query_device`` call
    per path with that path's row selection.
    """
    with obs.span("query.prep"):
        keys = np.asarray(keys, dtype=np.uint32)
        n_keys = len(keys)
        path_rows = np.asarray(path_rows, np.int32)
        n_paths, n_slots = path_rows.shape
        hop = (np.zeros(n_paths, bool) if single_hop is None
               else np.asarray(single_hop, bool))
        path_id = np.asarray(path_id)
        if len(path_id) != n_keys or (
                n_keys and not 0 <= path_id.min() <= path_id.max() < n_paths):
            raise ValueError(
                f"fleet_window_query_paths: path_id must give each of the "
                f"{n_keys} keys one of the {n_paths} paths")
        empty = ~(path_rows >= 0).any(axis=1)
        if empty.any():
            raise ValueError(
                "fleet_window_query_paths: no on-path fragment selected "
                f"for paths {np.flatnonzero(empty).tolist()} — an "
                "all-masked merge has no survivor and would poison the "
                "window sum")
        if n_keys == 0:
            return np.zeros(0)
        chunk = key_chunk(n_keys)
        n_chunks = -(-n_keys // chunk)
        keys_pad = np.zeros(n_chunks * chunk, np.uint32)
        keys_pad[:n_keys] = keys
        pid_pad = np.zeros(n_chunks * chunk, np.int32)
        pid_pad[:n_keys] = path_id
        pb = key_bucket(n_paths)
        rows_pad = np.full((pb, n_slots), -1, np.int32)
        rows_pad[:n_paths] = path_rows
        hop_pad = np.zeros(pb, bool)
        hop_pad[:n_paths] = hop
        plans = []
        for stack, params_by_epoch in stacks:
            params, ns, widths = _prep_window_params(stack, params_by_epoch)
            if path_rows.max() >= params.shape[1]:
                raise ValueError(
                    f"fleet_window_query_paths: path rows exceed the "
                    f"stack's {params.shape[1]} rows")
            mit_rows = params[0, :, PARAM_MIT] != 0
            plans.append((stack, _row_table(params, ns, widths),
                          bool(hop.any()) and bool(mit_rows.any())))
    outs = []
    keys_dev: list = [None] * n_chunks
    pid_dev: list = [None] * n_chunks
    paths_dev = None
    # Explicit crossings only (jnp.asarray in, one jax.device_get out),
    # as in fleet_window_query_device.
    with sanitize.transfer_guard():
        for stack, row_tab, mitigate in plans:
            tab_dev = None
            for c in range(n_chunks):
                real = min(chunk, n_keys - c * chunk)
                with obs.span("query.launch", keys=real,
                              paths=n_paths) as sp:
                    sent = [stack] if isinstance(stack, np.ndarray) else []
                    if paths_dev is None:
                        sent += [rows_pad, hop_pad]
                        paths_dev = (jnp.asarray(rows_pad),
                                     jnp.asarray(hop_pad))
                    if tab_dev is None:
                        sent.append(row_tab)
                        tab_dev = jnp.asarray(row_tab)
                    if keys_dev[c] is None:
                        part = slice(c * chunk, (c + 1) * chunk)
                        sent += [keys_pad[part], pid_pad[part]]
                        keys_dev[c] = jnp.asarray(keys_pad[part])
                        pid_dev[c] = jnp.asarray(pid_pad[part])
                    outs.append(_gather_merge_rows(
                        jnp.asarray(stack), tab_dev, *paths_dev,
                        keys_dev[c], pid_dev[c], kind=kind,
                        mitigate=mitigate))
                    sp.set_metadata(h2d_bytes=sum(a.nbytes for a in sent))
        with obs.span("query.sync"):
            ests = jax.device_get(outs)
    out = np.zeros(n_keys)
    for g in range(len(plans)):
        out += np.concatenate(
            ests[g * n_chunks:(g + 1) * n_chunks])[:n_keys].astype(np.float64)
    return out


@functools.partial(jax.jit, static_argnames=("n_levels",))
def _gather_merge_um(stack, col_seeds, sign_seeds, sub_seeds, ns, widths,
                     frag_sel, keys, *, n_levels: int):
    """All-levels UnivMon pass: (E, F*L, S, W) stack + (K,) keys ->
    (L, K) per-level window estimates.

    One gather covers every (epoch, fragment, level) row at once —
    the per-level seed mixing already happened at param-build time
    (``core.fleet.build_params``), so each virtual row's seeds are just
    its table entries.  The §4.3 masked median then merges the
    *fragment* axis independently per level (``frag_sel`` is the (F,)
    on-path mask), and the window sum is O_Q = Sum(O) per level.
    """
    sanitize.note_trace("sketch_query._gather_merge_um")
    e_count, n_rows = stack.shape[:2]
    n_frags = n_rows // n_levels
    raw = _gather_raw(stack, col_seeds, sign_seeds, sub_seeds, ns, widths,
                      None, keys, signed=True, mitigate=False)
    # (E, F, L, K) -> merge over fragments per level: move L into the
    # epoch axis so the shared (axis-1) masked median applies unchanged.
    raw = (raw.reshape(e_count, n_frags, n_levels, -1)
           .transpose(0, 2, 1, 3)
           .reshape(e_count * n_levels, n_frags, -1))
    if frag_sel.ndim == 2:
        # per-epoch liveness: expand (E, F) to the (E*L, F) row layout
        # (epoch-major, level within — matches the reshape above)
        frag_sel = jnp.repeat(frag_sel, n_levels, axis=0)
    merged = _masked_merge(raw, frag_sel, kind="um")      # (E*L, K)
    return merged.reshape(e_count, n_levels, -1).sum(axis=0)  # (L, K)


def um_window_query_device(stack, params_by_epoch: Sequence[np.ndarray],
                           keys: np.ndarray, n_levels: int,
                           frag_sel: Optional[np.ndarray] = None,
                           mesh=None) -> np.ndarray:
    """All ``n_levels`` UnivMon Count-Sketch window estimates for a key
    batch in ONE batched device call (the §6.2 G-sum inputs).

    Args:
      stack: ``(E, F * n_levels, n_sub_max, width_max)`` still-resident
        window stack (virtual level rows, fragment-major).
      params_by_epoch: E host ``(F * n_levels, N_PARAMS)`` tables with
        per-level mixed seeds (``core.fleet.build_params``).
      keys: (K,) uint32 key batch.
      frag_sel: optional (F,) bool on-path *fragment* mask — the level
        selection is structural here, not a mask.  May be (E, F) when
        fragment liveness differs per epoch; every epoch must keep at
        least one selected fragment (raises ``ValueError`` otherwise).

    Returns (n_levels, K) float64 ``merge="fragment"`` window estimates;
    level ``l``'s row is meaningful for keys with ``level_of >= l`` (the
    G-sum recursion masks the rest).  Mitigation averaging is not
    applied — the G-sum path queries without single-hop records, exactly
    like the host ``um_gsum_window``.
    """
    keys = np.asarray(keys, dtype=np.uint32)
    n_keys = len(keys)
    params, ns, widths = _prep_window_params(stack, params_by_epoch,
                                             allow_row_pad=mesh is not None)
    n_rows = params.shape[1]
    assert n_rows % n_levels == 0
    n_frags = n_rows // n_levels
    if frag_sel is None:
        frag_sel = np.ones(n_frags, bool)
    frag_sel = np.asarray(frag_sel, bool)
    sel2 = np.atleast_2d(frag_sel)
    if not sel2.any(axis=1).all():
        bad = np.flatnonzero(~sel2.any(axis=1))
        raise ValueError(
            "um_window_query_device: no on-path fragment selected "
            f"(epoch offsets {bad.tolist()} of {len(params_by_epoch)}) — "
            "an all-masked merge has no survivor; drop these epochs or "
            "widen the selection")
    if n_keys == 0:
        return np.zeros((n_levels, n_keys))
    kb = key_bucket(n_keys)
    keys_pad = np.zeros(kb, np.uint32)
    keys_pad[:n_keys] = keys
    if mesh is not None:
        est = _sharded_um_query(mesh, stack, params, ns, widths, sel2,
                                keys_pad, n_levels=n_levels)
        return est[:, :n_keys].astype(np.float64)
    # Same explicit-boundary discipline as fleet_window_query_device:
    # device compute under the (opt-in) transfer guard, one device_get
    # out, host-side slicing.
    with sanitize.transfer_guard():
        out = _gather_merge_um(
            jnp.asarray(stack),
            jnp.asarray(params[:, :, PARAM_COL_SEED].astype(np.uint32)),
            jnp.asarray(params[:, :, PARAM_SIGN_SEED].astype(np.uint32)),
            jnp.asarray(params[:, :, PARAM_SUB_SEED].astype(np.uint32)),
            jnp.asarray(ns.astype(np.int32)),
            jnp.asarray(widths.astype(np.int32)),
            jnp.asarray(frag_sel), jnp.asarray(keys_pad), n_levels=n_levels)
        # (L, KB) floats across the boundary — no counter-stack bytes
        est = jax.device_get(out)
    return est[:, :n_keys].astype(np.float64)


# --- cross-device sharded merge (the "switch" mesh axis) -------------------
#
# The fleet runner can shard a window stack's rows over a 1-D ("switch",)
# device mesh (fragments are the shard unit — a fragment's n_levels
# virtual rows never split; trailing *pad fragments* make the row count
# divide the axis).  The merge below is the cross-device twin of
# `_gather_merge`: every shard runs `_gather_raw` on its LOCAL rows only,
# then `all_gather`s the tiny (E, R_local, K) raw per-row estimate slices
# — never the (E, R_local, S, W) counter shards — so the full-row masked
# min/median merge (and nothing else) is replicated.  The gather is
# elementwise per row and `all_gather(tiled=True)` concatenates shard
# blocks in exactly the single-device row order, so the merged estimates
# are bit-identical to the unsharded path (docs/sharding.md).


def shard_padded_rows(n_rows: int, n_shards: int, n_levels: int = 1) -> int:
    """Padded row count for sharding ``n_rows`` fleet rows over
    ``n_shards`` devices: fragments (groups of ``n_levels`` rows) pad up
    to a multiple of the shard count, keeping level blocks intact."""
    n_frags, rem = divmod(int(n_rows), int(n_levels))
    assert rem == 0, (n_rows, n_levels)
    f_pad = -(-n_frags // int(n_shards)) * int(n_shards)
    return f_pad * int(n_levels)


def _pad_rows(a, r_pad: int, fill, axis: int = -1):
    """Zero-cost when already padded; else np.pad with ``fill``."""
    a = np.asarray(a)
    if a.shape[axis] == r_pad:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, r_pad - a.shape[axis])
    return np.pad(a, pad, constant_values=fill)


@functools.lru_cache(maxsize=None)
def _sharded_gather_merge(mesh, kind: str, mitigate: bool, n_rows: int):
    """jit(shard_map) merge for (mesh, kind, mitigate, real row count).

    Cached per mesh so steady-state replays hit the compile cache; the
    padded row count and key bucket are shape-keyed by jit itself.
    """
    row = P(None, "switch")
    per_row = P("switch")

    def body(stack, col_seeds, sign_seeds, sub_seeds, ns, widths,
             frag_sel, mit_rows, keys):
        sanitize.note_trace("sketch_query._sharded_gather_merge")
        raw = _gather_raw(stack, col_seeds, sign_seeds, sub_seeds, ns,
                          widths, mit_rows, keys,
                          signed=kind in ("cs", "um"), mitigate=mitigate)
        # Only the (E, R_local, K) raw estimates cross devices.
        raw = jax.lax.all_gather(raw, "switch", axis=1, tiled=True)
        return _masked_merge(raw[:, :n_rows], frag_sel,
                             kind=kind).sum(axis=0)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "switch", None, None), row, row, row,
                  per_row, per_row, P(), per_row, P()),
        out_specs=P(), check_vma=False))


def _sharded_window_query(mesh, stack, params, ns, widths, sel2, mit_rows,
                          keys_pad, *, kind: str, mitigate: bool):
    """Mesh leg of ``fleet_window_query_device``: pad the per-row param
    columns to the stack's padded row count, commit every input to the
    mesh explicitly (legal under the armed transfer guard), run the
    shard_map merge, fetch the (KB,) estimates."""
    n_shards = mesh.shape["switch"]
    e_count, n_rows = params.shape[:2]
    want = shard_padded_rows(n_rows, n_shards)
    if int(stack.shape[1]) < want:
        # unpadded (host) caller: zero rows shard like any other pad
        stack = _pad_rows(stack, want, 0.0, axis=1)
    r_pad = int(stack.shape[1])
    if r_pad % n_shards or r_pad < n_rows:
        raise ValueError(
            f"sharded stack rows {r_pad} do not cover {n_rows} param rows "
            f"in multiples of the switch axis ({n_shards})")
    col = _pad_rows(params[:, :, PARAM_COL_SEED].astype(np.uint32), r_pad, 0)
    sign = _pad_rows(params[:, :, PARAM_SIGN_SEED].astype(np.uint32), r_pad, 0)
    sub = _pad_rows(params[:, :, PARAM_SUB_SEED].astype(np.uint32), r_pad, 0)
    # Pad rows carry (n=1, width=4) so their hash math stays defined; the
    # merge slices them off right after the all_gather.
    ns_p = _pad_rows(ns.astype(np.int32), r_pad, 1)
    w_p = _pad_rows(widths.astype(np.int32), r_pad, 4)
    mit_p = _pad_rows(mit_rows, r_pad, False)
    sel_full = np.ascontiguousarray(
        np.broadcast_to(sel2, (e_count, n_rows)))
    row_sh = NamedSharding(mesh, P(None, "switch"))
    per_row_sh = NamedSharding(mesh, P("switch"))
    rep = NamedSharding(mesh, P())
    fn = _sharded_gather_merge(mesh, kind, bool(mitigate), n_rows)
    with sanitize.transfer_guard():
        out = fn(
            jax.device_put(jnp.asarray(stack),
                           NamedSharding(mesh, P(None, "switch", None, None))),
            jax.device_put(col, row_sh), jax.device_put(sign, row_sh),
            jax.device_put(sub, row_sh), jax.device_put(ns_p, per_row_sh),
            jax.device_put(w_p, per_row_sh), jax.device_put(sel_full, rep),
            jax.device_put(mit_p, per_row_sh), jax.device_put(keys_pad, rep))
        return jax.device_get(out)


@functools.lru_cache(maxsize=None)
def _sharded_gather_merge_um(mesh, n_levels: int, n_rows: int):
    """jit(shard_map) all-levels UnivMon merge (cross-device twin of
    ``_gather_merge_um``; fragment shard unit keeps level blocks local)."""
    row = P(None, "switch")
    per_row = P("switch")

    def body(stack, col_seeds, sign_seeds, sub_seeds, ns, widths,
             frag_sel, keys):
        sanitize.note_trace("sketch_query._sharded_gather_merge_um")
        e_count = stack.shape[0]
        n_frags = n_rows // n_levels
        raw = _gather_raw(stack, col_seeds, sign_seeds, sub_seeds, ns,
                          widths, None, keys, signed=True, mitigate=False)
        raw = jax.lax.all_gather(raw, "switch", axis=1, tiled=True)
        raw = (raw[:, :n_rows]
               .reshape(e_count, n_frags, n_levels, -1)
               .transpose(0, 2, 1, 3)
               .reshape(e_count * n_levels, n_frags, -1))
        sel = jnp.repeat(frag_sel, n_levels, axis=0)      # (E*L, F)
        merged = _masked_merge(raw, sel, kind="um")       # (E*L, K)
        return merged.reshape(e_count, n_levels, -1).sum(axis=0)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "switch", None, None), row, row, row,
                  per_row, per_row, P(), P()),
        out_specs=P(), check_vma=False))


def _sharded_um_query(mesh, stack, params, ns, widths, sel2, keys_pad, *,
                      n_levels: int):
    """Mesh leg of ``um_window_query_device``."""
    n_shards = mesh.shape["switch"]
    e_count, n_rows = params.shape[:2]
    want = shard_padded_rows(n_rows, n_shards, n_levels)
    if int(stack.shape[1]) < want:
        stack = _pad_rows(stack, want, 0.0, axis=1)
    r_pad = int(stack.shape[1])
    if r_pad % n_shards or r_pad < n_rows or r_pad % n_levels:
        raise ValueError(
            f"sharded um stack rows {r_pad} do not cover {n_rows} param "
            f"rows in level-aligned multiples of the switch axis "
            f"({n_shards} shards, {n_levels} levels)")
    col = _pad_rows(params[:, :, PARAM_COL_SEED].astype(np.uint32), r_pad, 0)
    sign = _pad_rows(params[:, :, PARAM_SIGN_SEED].astype(np.uint32), r_pad, 0)
    sub = _pad_rows(params[:, :, PARAM_SUB_SEED].astype(np.uint32), r_pad, 0)
    ns_p = _pad_rows(ns.astype(np.int32), r_pad, 1)
    w_p = _pad_rows(widths.astype(np.int32), r_pad, 4)
    n_frags = n_rows // n_levels
    sel_full = np.ascontiguousarray(
        np.broadcast_to(sel2, (e_count, n_frags)))
    row_sh = NamedSharding(mesh, P(None, "switch"))
    per_row_sh = NamedSharding(mesh, P("switch"))
    rep = NamedSharding(mesh, P())
    fn = _sharded_gather_merge_um(mesh, int(n_levels), n_rows)
    with sanitize.transfer_guard():
        out = fn(
            jax.device_put(jnp.asarray(stack),
                           NamedSharding(mesh, P(None, "switch", None, None))),
            jax.device_put(col, row_sh), jax.device_put(sign, row_sh),
            jax.device_put(sub, row_sh), jax.device_put(ns_p, per_row_sh),
            jax.device_put(w_p, per_row_sh), jax.device_put(sel_full, rep),
            jax.device_put(keys_pad, rep))
        return jax.device_get(out)


@functools.partial(jax.jit, static_argnames=("g", "k_heavy", "n_levels"))
def _um_gsum_jit(ests, lvl, *, g, k_heavy: int, n_levels: int):
    """Top-down UnivMon Y-recursion on device (mirrors
    ``core.query.um_gsum_combine``; the level loop is unrolled — L is
    small and static)."""
    sanitize.note_trace("sketch_query._um_gsum_jit")
    y = jnp.float32(0.0)
    for l in range(n_levels - 1, -1, -1):
        sel = lvl >= l
        est = jnp.where(sel, jnp.maximum(ests[l], 1.0), -jnp.inf)
        vals, idx = jax.lax.top_k(est, min(k_heavy, est.shape[0]))
        valid = vals > -jnp.inf
        gv = jnp.where(valid, g(jnp.where(valid, vals, 1.0)), 0.0)
        if l == n_levels - 1:
            y = gv.sum()
        else:
            in_next = ((lvl[idx] >= l + 1) & valid).astype(jnp.float32)
            y = 2.0 * y + jnp.sum((1.0 - 2.0 * in_next) * gv)
    return y


def um_gsum_device(ests: np.ndarray, lvl: np.ndarray, g,
                   k_heavy: int = 1024) -> float:
    """Device twin of ``core.query.um_gsum_combine``: the recursive
    G-sum estimator over precomputed (n_levels, K) per-level estimates.

    ``g`` must be a jnp-traceable callable (hashable — e.g. a module
    -level function, so the jit cache keys on it).  Accumulates in f32
    (jax's default; the host combine runs in f64), so expect ~1e-5
    relative agreement; additionally, with a *binding* top-k cutoff
    (``k_heavy < K``) the two may select different keys among exact
    ties (documented in docs/univmon.md).
    """
    ests = np.asarray(ests, np.float32)
    lvl = np.asarray(lvl, np.int32)
    n_levels, n_keys = ests.shape
    # Same O(log K) compile discipline as the query entry points: pad
    # the key axis to a pow2 bucket with lvl = -1 sentinels, which no
    # level ever selects (sel = lvl >= l with l >= 0).
    kb = key_bucket(n_keys)
    if kb != n_keys:
        ests = np.pad(ests, ((0, 0), (0, kb - n_keys)))
        lvl = np.pad(lvl, (0, kb - n_keys), constant_values=-1)
    with sanitize.transfer_guard():
        y = _um_gsum_jit(jnp.asarray(ests), jnp.asarray(lvl), g=g,
                         k_heavy=int(k_heavy), n_levels=int(n_levels))
        return float(jax.device_get(y))
