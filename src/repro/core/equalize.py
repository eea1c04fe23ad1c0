"""Error equalization (paper §4.2): PEB estimation + the n-control loop.

Each fragment estimates its probabilistic error bound (PEB) from its own
counters (Eq. 4), averages it over the epoch's subepochs (Eq. 5), and
doubles/halves its number of subepochs for the next epoch to approach the
network-wide target (Eq. 6).  Runs host-side at epoch transitions, exactly
mirroring the paper's ASIC/CPU split (Fig. 10).
"""
from __future__ import annotations

import functools

import numpy as np

from .fragment import EpochRecords

N_MAX = 1 << 10  # safety cap on subepochs (not in the paper; never hit in
#                  our experiments, present to bound record volume).


def peb_row(counters: np.ndarray, kind: str) -> float:
    """Eq. 4: estimated PEB of one subepoch record from its counters."""
    c = counters.astype(np.float64)
    w = c.shape[-1]
    if kind in ("cs", "um"):
        return float(np.sqrt((c * c).sum() / w))
    return float(np.abs(c).sum() / w)


def peb_epoch(rec: EpochRecords) -> float:
    """Eq. 5: mean estimated PEB over the epoch's subepochs."""
    counters = rec.counters
    if rec.kind == "um":
        counters = counters[0]  # level 0 sees the full stream (§4.2, UnivMon)
    return float(np.mean([peb_row(counters[s], rec.kind)
                          for s in range(rec.n)]))


def peb_fleet(stacked: np.ndarray, ns: np.ndarray, widths: np.ndarray,
              kind: str) -> np.ndarray:
    """Vectorized Eq. 4/5 over a fleet's stacked counters.

    ``stacked``: (n_frags, n_sub_max, width_max) with exact zeros outside
    each fragment's live ``[:ns[f], :widths[f]]`` block (the fleet-kernel
    output layout), so summing over the full padded axes is equivalent to
    summing the live block.  Returns per-fragment epoch PEBs identical to
    ``peb_epoch`` on the unpacked records.
    """
    c = stacked.astype(np.float64)
    n_sub_max = c.shape[1]
    w = np.asarray(widths, np.float64)[:, None]
    if kind in ("cs", "um"):
        row = np.sqrt((c * c).sum(axis=-1) / w)      # (n_frags, n_sub_max)
    else:
        row = np.abs(c).sum(axis=-1) / w
    live = np.arange(n_sub_max)[None, :] < np.asarray(ns)[:, None]
    return (row * live).sum(axis=1) / np.asarray(ns, np.float64)


@functools.lru_cache(maxsize=None)
def _peb_fleet_device_jit(kind: str):
    import jax
    import jax.numpy as jnp

    def peb(stacked, ns, widths):
        c = stacked.astype(jnp.float32)
        n_sub_max = c.shape[1]
        w = widths.astype(jnp.float32)[:, None]
        if kind in ("cs", "um"):
            row = jnp.sqrt((c * c).sum(axis=-1) / w)
        else:
            row = jnp.abs(c).sum(axis=-1) / w
        live = jnp.arange(n_sub_max)[None, :] < ns[:, None]
        return (row * live).sum(axis=1) / ns.astype(jnp.float32)

    return jax.jit(peb)


def peb_fleet_device(stacked, ns, widths, kind: str):
    """jnp twin of ``peb_fleet`` for device-resident (window) outputs.

    Same Eq. 4/5 math, but computed where the stacked f32 counters
    already live, so the epoch-window runner transfers only the
    ``(n_rows,)`` PEB vector instead of the whole counter stack.  f32
    accumulation differs from the float64 host path by ~1e-7 relative —
    irrelevant to the factor-of-two Eq. 6 control thresholds.
    """
    import jax.numpy as jnp

    return _peb_fleet_device_jit(kind)(stacked, jnp.asarray(ns),
                                       jnp.asarray(widths))


def next_n(n: int, peb: float, rho_target: float) -> int:
    """Eq. 6: moving adjustment of the subepoch count."""
    if peb > 2.0 * rho_target:
        return min(2 * n, N_MAX)
    if peb < rho_target / 2.0:
        return max(1, n // 2)
    return n


def next_n_observed(n: int, peb: float, n_observed: int,
                    rho_target: float) -> int:
    """Eq. 6 step from a PEB observed while the fragment ran at
    ``n_observed`` — the window-mode control, where every epoch of a
    window ran at the frozen n while the control walks on.  The
    observation is rescaled to the current ``n`` by the §4.2 error model
    (a record's Eq. 4 bound scales as 1/n), so a window of E epochs
    converges instead of doubling n E times on one stale reading;
    ``converge_n`` iterates it.  ``n == n_observed`` is plain
    ``next_n``."""
    return next_n(n, peb * n_observed / n, rho_target)


def converge_n(n: int, peb: float, rho_target: float) -> int:
    """Iterate the Eq. 6 control to its fixed point in one shot.

    ``peb`` is the PEB *measured at the current* ``n``; under the §4.2
    error model each doubling of the subepoch count halves a record's
    load and hence its Eq. 4 bound, so the predicted PEB at ``n'`` is
    ``peb * n / n'``.  The per-epoch loop walks ``next_n`` one factor-2
    step per epoch; after a churn event (fragment death or a
    resource-reclaim shrink) the controller instead jumps the survivors
    straight to the converged setting — the [rho/2, 2*rho] acceptance
    band spans a factor of 4 while steps move a factor of 2, so the
    iteration cannot oscillate and terminates within log2(N_MAX) steps.
    A fragment already inside the band is returned unchanged (re-running
    re-equalization is idempotent).
    """
    if peb <= 0.0 or not np.isfinite(peb):
        return n
    n0, peb0 = n, peb
    for _ in range(2 * N_MAX.bit_length()):
        nn = next_n_observed(n, peb0, n0, rho_target)
        if nn == n:
            return n
        n = nn
    return n


def reequalize(ns, pebs, rho_target: float):
    """§6 re-equalization after a churn event: converge every surviving
    fragment's subepoch count against its last observed PEB.

    ``ns``: {switch: current n}; ``pebs``: {switch: last observed PEB}
    (switches with no observation yet — e.g. a fleet that failed before
    its first epoch completed — are left untouched, preserving the
    bit-identity of the survivors with a never-failed fleet).  Returns
    the new {switch: n} for exactly the switches in ``ns``.
    """
    return {sw: converge_n(n, pebs[sw], rho_target) if sw in pebs else n
            for sw, n in ns.items()}
