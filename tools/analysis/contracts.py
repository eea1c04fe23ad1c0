"""Layer 2: abstract-eval contract verification (no kernel execution).

Walks every shipped scenario geometry — the kernel-bench sweep shapes,
the heterogeneous fleet rows, the UnivMon fleet — through the kernel's
own cost model (``select_geometry``/``vmem_bytes``) and through
``jax.eval_shape`` on the ``pallas_call`` wrappers, asserting:

  * ``vmem-budget`` — every selected/shipped geometry fits
    ``VMEM_BUDGET_BYTES`` in every value mode it ships with;
  * ``pow2-width`` — ``pow2_width_cap`` yields 128-aligned powers of
    two and the selected blocks are MXU-aligned;
  * ``packing`` — the packed-ts field layout holds (level id in bits
    [24, 29), single-hop flag in bit 31) and shipped defaults satisfy
    ``log2_te <= 24`` / ``n_levels <= 32``;
  * ``eval-shape`` — the pallas wrappers abstract-eval to the factored
    ``(rows, W/LANE, LANE)`` f32 layout.  ``eval_shape`` traces the
    kernel body but never runs it, so this layer needs no TPU and
    finishes in seconds;
  * ``sharded-*`` — the device-mesh fleet's row padding is a
    level-aligned multiple of the shard count, and the per-shard
    ragged dispatch (global geometry at the shard's row count) keeps
    the vmem / pow2 / layout contracts for 1–8 shards;
  * ``peak-guard`` — AST check that every update path routes its output
    through the 2^24 exact-integer guard: each ``return`` of
    ``ops.sketch_update`` is a ``_guard_peak(...)`` call (this covers
    the ``backend="ref"`` branch, i.e. ``ref.py``'s oracle output), and
    the fleet runner's ``run_epoch``/``run_window`` call
    ``self._check_output_peak``.

jax (and ``repro``, via PYTHONPATH=src) are imported lazily inside
``run_contracts`` so the lint layer stays usable without them.
"""
from __future__ import annotations

import ast
import os
from typing import List

from .findings import Finding

_SRC = "tools/analysis/contracts.py"   # anchor for non-file findings

#: Shipped (width, n_sub) scenario geometries: the kernel-bench single-
#: fragment sweep, the heterogeneous fleet rows of _fleet_inputs /
#: run_fleet_ragged / run_query_plane, and the DiSketchSystem test
#: shapes (tests/test_query_device.py cov_list widths).
SCENARIOS = (
    (2048, 8), (16384, 8), (65536, 16),          # single-kernel sweep
    (512, 4), (2048, 8), (1024, 2), (4096, 16),  # fleet rows
    (256, 1), (128, 2), (1280, 32),              # narrow/ragged edges
)

#: Fleet-shaped eval_shape cases: (n_frags, n_sub_max, width_max,
#: n_levels).  Mirrors _fleet_inputs (16 frags), run_univmon_fleet
#: (8 frags x 8 levels) and the test fleets.
FLEET_CASES = (
    (16, 16, 4096, 1),
    (8, 8, 2048, 8),
)


def _check_geometry(findings: List[Finding]) -> None:
    from repro.kernels.sketch_update.kernel import (
        LANE, VALUE_MODES, VMEM_BUDGET_BYTES, pow2_width_cap,
        select_geometry, vmem_bytes)
    for width, n_sub in SCENARIOS:
        cap = pow2_width_cap(width)
        if cap & (cap - 1) or cap % LANE or cap < width:
            findings.append(Finding(
                "pow2-width", _SRC, 1,
                f"pow2_width_cap({width}) = {cap} is not a 128-aligned "
                "power-of-two ceiling"))
        for mode in VALUE_MODES:
            blk, w_blk = select_geometry(width, n_sub, mode)
            w_eff = min(w_blk, cap)
            if blk % 128 or w_eff % LANE or (w_eff & (w_eff - 1)):
                findings.append(Finding(
                    "pow2-width", _SRC, 1,
                    f"select_geometry({width}, {n_sub}, {mode}) -> "
                    f"({blk}, {w_blk}): blocks are not MXU-aligned"))
            used = vmem_bytes(blk, w_eff, n_sub, mode)
            if used > VMEM_BUDGET_BYTES:
                findings.append(Finding(
                    "vmem-budget", _SRC, 1,
                    f"geometry ({blk}, {w_eff}) for width={width} "
                    f"n_sub={n_sub} mode={mode} needs {used} B "
                    f"> budget {VMEM_BUDGET_BYTES} B"))


def _check_packing(findings: List[Finding]) -> None:
    import inspect

    from repro.core.disketch import DiSketchSystem
    from repro.kernels.sketch_update.kernel import (LVL_FIELD_MASK,
                                                   LVL_SHIFT, SH_SHIFT)
    from repro.net import traffic
    if LVL_SHIFT != 24 or LVL_FIELD_MASK != 0x1F or SH_SHIFT != 31:
        findings.append(Finding(
            "packing", _SRC, 1,
            f"packed-ts layout moved (LVL_SHIFT={LVL_SHIFT}, "
            f"mask={LVL_FIELD_MASK:#x}, SH_SHIFT={SH_SHIFT}); the "
            "log2_te<=24 / n_levels<=32 contracts below assume the "
            "documented layout — update them together"))
    max_levels = LVL_FIELD_MASK + 1
    n_levels_default = inspect.signature(
        DiSketchSystem.__init__).parameters["n_levels"].default
    if not isinstance(n_levels_default, int) or \
            n_levels_default > max_levels:
        findings.append(Finding(
            "packing", _SRC, 1,
            f"DiSketchSystem n_levels default {n_levels_default!r} "
            f"exceeds the {max_levels}-level packed-ts field"))
    for fn_name in ("linear_path_workload", "gen_workload"):
        fn = getattr(traffic, fn_name, None)
        if fn is None:
            continue
        p = inspect.signature(fn).parameters.get("log2_te")
        if p is None or not isinstance(p.default, int) or \
                p.default > LVL_SHIFT:
            findings.append(Finding(
                "packing", _SRC, 1,
                f"traffic.{fn_name} log2_te default "
                f"{getattr(p, 'default', None)!r} violates "
                f"log2_te <= {LVL_SHIFT} (level id needs ts bits "
                f"[{LVL_SHIFT}, {LVL_SHIFT + 5}))"))


def _check_eval_shapes(findings: List[Finding]) -> None:
    import functools

    import jax
    import numpy as np

    from repro.core.fleet import CSR_BLK as csr_blk
    from repro.kernels.sketch_update import fleet as FK
    from repro.kernels.sketch_update.kernel import (LANE, pow2_width_cap,
                                                    select_geometry,
                                                    sketch_update_pallas)

    def shapes(*specs):
        return [jax.ShapeDtypeStruct(s, d) for s, d in specs]

    # Single-fragment wrapper on the shipped sweep shapes.
    for width, n_sub in SCENARIOS[:3]:
        blk, w_blk = select_geometry(width, n_sub, "f32")
        w_blk = min(w_blk, pow2_width_cap(width))
        pad_w = (-width) % w_blk
        p = 4 * blk // LANE
        k, v, t = shapes(((p, LANE), np.uint32), ((p, LANE), np.float32),
                         ((p, LANE), np.uint32))
        fn = functools.partial(
            sketch_update_pallas, hash_width=width,
            padded_width=width + pad_w, n_sub=n_sub, log2_te=16,
            col_seed=1, sign_seed=2, sub_seed=3, signed=True, blk=blk,
            w_blk=w_blk, value_mode="f32", interpret=True)
        try:
            out = jax.eval_shape(fn, k, v, t)
        except Exception as e:          # analysis: ignore[silent-except]
            findings.append(Finding(
                "eval-shape", _SRC, 1,
                f"sketch_update_pallas(width={width}, n_sub={n_sub}) "
                f"failed abstract eval: {e!r}"))
            continue
        want = (n_sub, (width + pad_w) // LANE, LANE)
        if tuple(out.shape) != want or out.dtype != np.float32:
            findings.append(Finding(
                "eval-shape", _SRC, 1,
                f"sketch_update_pallas(width={width}) -> {out.shape} "
                f"{out.dtype}, expected {want} float32"))

    # Fleet wrappers (dense + ragged CSR) on the fleet-shaped cases.
    for n_frags, n_sub_max, width_max, n_levels in FLEET_CASES:
        blk, w_blk = select_geometry(width_max, n_sub_max, "f32")
        w_blk = min(w_blk, pow2_width_cap(width_max))
        pad_w = (-width_max) % w_blk
        padded = width_max + pad_w
        n_rows = n_frags * n_levels
        p = 2 * blk // LANE
        tile = (n_sub_max, padded // LANE, LANE)
        if n_levels == 1:
            k, v, t, prm = shapes(
                ((n_frags, p, LANE), np.uint32),
                ((n_frags, p, LANE), np.float32),
                ((n_frags, p, LANE), np.uint32),
                ((n_frags, FK.N_PARAMS), np.int32))
            fn = functools.partial(
                FK.fleet_update_pallas, n_sub_max=n_sub_max,
                padded_width=padded, log2_te=16, signed=True, blk=blk,
                w_blk=w_blk, value_mode="f32", interpret=True)
            try:
                out = jax.eval_shape(fn, k, v, t, prm)
            except Exception as e:      # analysis: ignore[silent-except]
                findings.append(Finding(
                    "eval-shape", _SRC, 1,
                    f"fleet_update_pallas({n_frags} frags) failed "
                    f"abstract eval: {e!r}"))
                continue
            want = (n_frags, *tile)
        else:
            nb = 2 * n_frags
            k, v, t, prm, bf = shapes(
                ((nb * csr_blk // LANE, LANE), np.uint32),
                ((nb * csr_blk // LANE, LANE), np.float32),
                ((nb * csr_blk // LANE, LANE), np.uint32),
                ((n_rows, FK.N_PARAMS), np.int32), ((nb,), np.int32))
            fn = functools.partial(
                FK.fleet_update_ragged_pallas, n_sub_max=n_sub_max,
                padded_width=padded, log2_te=16, signed=True, blk=csr_blk,
                w_blk=w_blk, value_mode="f32", n_levels=n_levels,
                interpret=True)
            try:
                out = jax.eval_shape(fn, k, v, t, prm, bf)
            except Exception as e:      # analysis: ignore[silent-except]
                findings.append(Finding(
                    "eval-shape", _SRC, 1,
                    f"fleet_update_ragged_pallas({n_rows} rows) failed "
                    f"abstract eval: {e!r}"))
                continue
            want = (n_rows, *tile)
        if tuple(out.shape) != want or out.dtype != np.float32:
            findings.append(Finding(
                "eval-shape", _SRC, 1,
                f"fleet wrapper -> {out.shape} {out.dtype}, "
                f"expected {want} float32"))


def _check_sharded(findings: List[Finding]) -> None:
    """Sharded-fleet contracts (docs/sharding.md), device-free.

    The device-mesh runner dispatches each shard through the ordinary
    ragged fleet wrapper over the shard's own rows, with the *global*
    ``(n_sub_max, width_max)`` geometry — so the single-device vmem /
    pow2 contracts must keep holding at every shard row count, and the
    row padding must stay shard-divisible and level-aligned (a level
    block split across shards would break the all_gather row order the
    bit-identity argument rests on).  All checks run via eval_shape /
    arithmetic only: no mesh, no devices, so the lint job covers them.
    """
    import functools

    import jax
    import numpy as np

    from repro.core.fleet import CSR_BLK as csr_blk
    from repro.kernels.sketch_query import shard_padded_rows
    from repro.kernels.sketch_update import fleet as FK
    from repro.kernels.sketch_update.kernel import (
        LANE, VMEM_BUDGET_BYTES, pow2_width_cap, select_geometry,
        vmem_bytes)

    def shapes(*specs):
        return [jax.ShapeDtypeStruct(s, d) for s, d in specs]

    for n_frags, n_sub_max, width_max, n_levels in FLEET_CASES:
        n_rows = n_frags * n_levels
        for n_shards in (1, 2, 4, 8):
            r_pad = shard_padded_rows(n_rows, n_shards, n_levels)
            if r_pad < n_rows or r_pad % n_shards or r_pad % n_levels \
                    or (r_pad // n_shards) % n_levels:
                findings.append(Finding(
                    "sharded-rows", _SRC, 1,
                    f"shard_padded_rows({n_rows}, {n_shards}, "
                    f"{n_levels}) = {r_pad} is not a level-aligned "
                    "multiple of the shard count covering every row"))
                continue
            # Per-shard dispatch geometry: global (n_sub_max,
            # width_max) at the shard's row count must still be
            # MXU-aligned and inside the vmem budget.
            blk, w_blk = select_geometry(width_max, n_sub_max, "f32")
            w_blk = min(w_blk, pow2_width_cap(width_max))
            if blk % 128 or w_blk % LANE or (w_blk & (w_blk - 1)):
                findings.append(Finding(
                    "sharded-pow2", _SRC, 1,
                    f"per-shard geometry ({blk}, {w_blk}) for "
                    f"width={width_max} n_sub={n_sub_max} is not "
                    "MXU-aligned"))
            used = vmem_bytes(blk, w_blk, n_sub_max, "f32")
            if used > VMEM_BUDGET_BYTES:
                findings.append(Finding(
                    "sharded-vmem", _SRC, 1,
                    f"per-shard geometry ({blk}, {w_blk}) for "
                    f"width={width_max} n_sub={n_sub_max} needs "
                    f"{used} B > budget {VMEM_BUDGET_BYTES} B"))
            rows_shard = r_pad // n_shards
            padded = width_max + (-width_max) % w_blk
            nb = 2 * max(rows_shard // n_levels, 1)
            k, v, t, prm, bf = shapes(
                ((nb * csr_blk // LANE, LANE), np.uint32),
                ((nb * csr_blk // LANE, LANE), np.float32),
                ((nb * csr_blk // LANE, LANE), np.uint32),
                ((rows_shard, FK.N_PARAMS), np.int32),
                ((nb,), np.int32))
            fn = functools.partial(
                FK.fleet_update_ragged_pallas, n_sub_max=n_sub_max,
                padded_width=padded, log2_te=16, signed=True,
                blk=csr_blk, w_blk=w_blk, value_mode="f32",
                n_levels=n_levels, interpret=True)
            try:
                out = jax.eval_shape(fn, k, v, t, prm, bf)
            except Exception as e:      # analysis: ignore[silent-except]
                findings.append(Finding(
                    "sharded-eval-shape", _SRC, 1,
                    f"per-shard ragged dispatch ({rows_shard} rows, "
                    f"{n_shards} shards) failed abstract eval: {e!r}"))
                continue
            want = (rows_shard, n_sub_max, padded // LANE, LANE)
            if tuple(out.shape) != want or out.dtype != np.float32:
                findings.append(Finding(
                    "sharded-eval-shape", _SRC, 1,
                    f"per-shard ragged dispatch -> {out.shape} "
                    f"{out.dtype}, expected {want} float32"))


def _returns_of(fn: ast.FunctionDef):
    """Return statements belonging to ``fn`` itself (not nested defs)."""
    out = []
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Return):
            out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def _check_peak_guard(root: str, findings: List[Finding]) -> None:
    ops_path = "src/repro/kernels/sketch_update/ops.py"
    fleet_path = "src/repro/core/fleet.py"

    def parse(rel):
        with open(os.path.join(root, rel), encoding="utf-8") as fh:
            return ast.parse(fh.read(), filename=rel)

    # Every return of ops.sketch_update must be _guard_peak(...) — the
    # ref branch included, which is how ref.py's oracle output is
    # guarded.  _guard_peak itself must call check_output_peak.
    tree = parse(ops_path)
    fns = {n.name: n for n in ast.walk(tree)
           if isinstance(n, ast.FunctionDef)}
    su = fns.get("sketch_update")
    if su is None:
        findings.append(Finding("peak-guard", ops_path, 1,
                                "sketch_update entry point not found"))
    else:
        for ret in _returns_of(su):
            ok = (isinstance(ret.value, ast.Call)
                  and isinstance(ret.value.func, ast.Name)
                  and ret.value.func.id == "_guard_peak")
            if not ok:
                findings.append(Finding(
                    "peak-guard", ops_path, ret.lineno,
                    "sketch_update return bypasses _guard_peak — the "
                    "2^24 exactness contract is unenforced on this path"))
    gp = fns.get("_guard_peak")
    if gp is None or not any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
            and n.func.id == "check_output_peak" for n in ast.walk(gp)):
        findings.append(Finding(
            "peak-guard", ops_path, getattr(gp, "lineno", 1),
            "_guard_peak no longer calls check_output_peak"))

    # The fleet runner's epoch/window dispatches must check the peak.
    tree = parse(fleet_path)
    runner = next((n for n in ast.walk(tree)
                   if isinstance(n, ast.ClassDef)
                   and n.name == "FleetEpochRunner"), None)
    if runner is None:
        findings.append(Finding("peak-guard", fleet_path, 1,
                                "FleetEpochRunner not found"))
        return
    methods = {n.name: n for n in runner.body
               if isinstance(n, ast.FunctionDef)}
    for name in ("run_epoch", "run_window"):
        fn = methods.get(name)
        calls_guard = fn is not None and any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr == "_check_output_peak"
            for n in ast.walk(fn))
        if not calls_guard:
            findings.append(Finding(
                "peak-guard", fleet_path,
                getattr(fn, "lineno", runner.lineno),
                f"FleetEpochRunner.{name} does not call "
                "self._check_output_peak"))


def run_contracts(root: str) -> List[Finding]:
    findings: List[Finding] = []
    _check_geometry(findings)
    _check_packing(findings)
    _check_eval_shapes(findings)
    _check_sharded(findings)
    _check_peak_guard(root, findings)
    return findings
