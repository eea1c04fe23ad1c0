"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, window, reference, comparison) on the CPU at a size a test
can hold, with one fault planted in the system under test.  The faults
a cell can have: a step that leaves its state unchanged, half of the
batch left out, and an answer altered where it is produced.  The cells
run on one chip, so there is no exchange between chips to leave out.
"""
import time

import numpy as np
import pytest

from harness import cell

SMALL = {"traffic": dict(trace_seed=1, n_flows=2000, total_packets=20000,
                         alpha=1.1, max_flow_frac=0.02, n_epochs=16,
                         log2_te=16, burstiness=0.2, arrival="paced"),
         "memory": {"base_bytes": 8192, "gini": 0.4, "memory_seed": 101}}
SEED = 2**31 + 99


def _run(name):
    return cell.run(name, SEED, 0.5, False, t_start=time.perf_counter(),
                    require_chip=False, config_override=SMALL)


def _kernel_unchanged(monkeypatch):
    """The update returns the window's counters untouched (all zero)."""
    import jax.numpy as jnp
    from repro.kernels.sketch_update import fleet as FK

    def update(keys, vals, ts, params, block_frag, *, n_sub_max, width_max,
               **kw):
        return jnp.zeros((np.shape(params)[0], n_sub_max, width_max),
                         jnp.float32)

    monkeypatch.setattr(FK, "fleet_update_ragged", update)


def _half_batch(monkeypatch):
    """The second half of every packed packet stream is left out."""
    from repro.core import fleet

    pack = fleet.pack_csr

    def half(*a, **k):
        keys, vals, ts, bf = pack(*a, **k)
        vals = vals.copy()
        vals[len(vals) // 2:] = 0.0
        return keys, vals, ts, bf

    monkeypatch.setattr(fleet, "pack_csr", half)


def _counter_altered(monkeypatch):
    """One counter of every launch is off by one where it is produced."""
    from repro.kernels.sketch_update import fleet as FK

    update = FK.fleet_update_ragged

    def altered(*a, **k):
        return update(*a, **k).at[0, 0, 0].add(1.0)

    monkeypatch.setattr(FK, "fleet_update_ragged", altered)


def _estimate_altered(monkeypatch):
    """One estimate of every device window query is off by one."""
    from repro.core import query

    q = query.fleet_query_window_device

    def altered(*a, **k):
        out = q(*a, **k).copy()
        out[0] += 1.0
        return out

    monkeypatch.setattr(query, "fleet_query_window_device", altered)


def _estimates_halved(monkeypatch):
    """Half of every request's keys are left unanswered (estimate 0)."""
    from repro.core import query

    q = query.fleet_query_window_device

    def half(stack, params, keys, *a, **k):
        out = np.zeros(len(keys))
        n = (len(keys) + 1) // 2
        out[:n] = q(stack, params, keys[:n], *a, **k)
        return out

    monkeypatch.setattr(query, "fleet_query_window_device", half)


def test_sound_runs_are_correct():
    for name in ("ft4-cs.replay", "ft14-cms.replay", "ft4-cs.query"):
        out = _run(name)
        assert out["correct"], (name, out["checks"])
        assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_kernel_unchanged, _half_batch,
                                   _counter_altered])
@pytest.mark.parametrize("name", ["ft4-cs.replay", "ft14-cms.replay"])
def test_replay_fault_is_caught(monkeypatch, name, fault):
    fault(monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [_kernel_unchanged, _half_batch,
                                   _estimate_altered, _estimates_halved])
def test_query_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    out = _run("ft4-cs.query")
    assert not out["correct"], out["checks"]
