"""Hypothesis property-based tests for the system's invariants."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r "
           "requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import hashing as H
from repro.core import sketches as S
from repro.core import query as Q
from repro.core.equalize import next_n, peb_row
from repro.core.fragment import (FragmentConfig, packet_subepoch,
                                 process_epoch)

LOG2_TE = 10


@given(st.integers(0, 2**32 - 1), st.integers(0, 2**31 - 1),
       st.sampled_from([1, 2, 4, 8, 16, 64]))
def test_hash_pow2_in_range(key, seed, n):
    h = int(H.hash_pow2(np.array([key], np.uint32), seed, n)[0])
    assert 0 <= h < n


@given(st.integers(0, 2**31 - 1), st.integers(2, 100000))
def test_hash_mod_in_range(seed, mod):
    keys = np.arange(64, dtype=np.uint32) * np.uint32(2654435769)
    h = H.hash_mod(keys, seed, mod)
    assert (h >= 0).all() and (h < mod).all()


@settings(deadline=None, max_examples=25)
@given(st.lists(st.tuples(st.integers(1, 1000), st.integers(1, 500)),
                min_size=1, max_size=50, unique_by=lambda t: t[0]),
       st.integers(0, 1000))
def test_cms_point_query_overestimates(flows, seed):
    """CMS invariant: estimate >= true count, for ANY stream."""
    keys = np.array([k for k, _ in flows], np.uint32)
    vals = np.array([v for _, v in flows], np.int64)
    spec = S.SketchSpec("cms", depth=3, width=32, seed=seed)
    c = S.update(spec, S.make_counters(spec), keys, vals)
    est = S.query(spec, c, keys)
    assert (est >= vals - 1e-9).all()


@settings(deadline=None, max_examples=25)
@given(st.lists(st.tuples(st.integers(1, 1000), st.integers(1, 500)),
                min_size=1, max_size=50, unique_by=lambda t: t[0]),
       st.integers(0, 1000))
def test_sketch_linearity_property(flows, seed):
    """sketch(A) + sketch(B) == sketch(A + B) for any split."""
    keys = np.array([k for k, _ in flows], np.uint32)
    vals = np.array([v for _, v in flows], np.int64)
    spec = S.SketchSpec("cs", depth=3, width=16, seed=seed)
    cut = len(keys) // 2
    a = S.update(spec, S.make_counters(spec), keys[:cut], vals[:cut])
    b = S.update(spec, S.make_counters(spec), keys[cut:], vals[cut:])
    ab = S.update(spec, S.make_counters(spec), keys, vals)
    np.testing.assert_array_equal(a + b, ab)


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**20), st.sampled_from([1, 2, 4, 8, 16]))
def test_subepoch_bitslice_property(ts, n):
    """Method 2 bit-slice == arithmetic (t mod Te) // (Te/n), any t, n."""
    te = 1 << LOG2_TE
    got = int(packet_subepoch(np.array([ts], np.int64), 0, LOG2_TE, n)[0])
    assert got == (ts % te) // (te // n)


@settings(deadline=None, max_examples=20)
@given(st.integers(1, 512), st.floats(1e-3, 1e6), st.floats(1e-3, 1e6))
def test_next_n_moves_toward_target(n, peb, target):
    """Eq. 6 monotonicity: n grows iff error is too high, shrinks iff
    too low, and always stays a power of two in [1, N_MAX]."""
    n = 1 << (n.bit_length() - 1)  # snap to power of two
    n2 = next_n(n, peb, target)
    assert n2 & (n2 - 1) == 0
    if peb > 2 * target:
        assert n2 >= n
    elif peb < target / 2:
        assert n2 <= n
    else:
        assert n2 == n


@settings(deadline=None, max_examples=15)
@given(st.integers(0, 100), st.sampled_from([1, 2, 4, 8]),
       st.integers(1, 6))
def test_query_epoch_mass_conservation(seed, n, n_frag):
    """For a uniform-rate flow and CMS fragments with no collisions, the
    composite epoch estimate equals the true count regardless of the
    (n, fragment-count) combination."""
    true = 1 << LOG2_TE  # one packet per time unit
    keys = np.full(true, 12345, np.uint32)
    vals = np.ones(true, np.int64)
    ts = np.arange(true, dtype=np.int64)
    recs = []
    for f in range(n_frag):
        cfg = FragmentConfig(frag_id=f, kind="cms",
                             memory_bytes=4 * 1024)
        recs.append(process_epoch(cfg, 0, n, keys, vals, ts, 0, LOG2_TE))
    est = Q.query_epoch(recs, np.array([12345], np.uint32), "cms")
    assert est[0] == pytest.approx(true, rel=1e-9)


@settings(deadline=None, max_examples=25)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=64),
       st.sampled_from(["cs", "cms"]))
def test_peb_row_nonnegative_and_scale(counters, kind):
    c = np.array(counters, np.int64)
    rho = peb_row(c, kind)
    assert rho >= 0
    # doubling all counters doubles the PEB (both norms are 1-homogeneous)
    assert peb_row(2 * c, kind) == pytest.approx(2 * rho, rel=1e-9)


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 2**31 - 1))
def test_synthetic_data_in_vocab(seed):
    from repro.data.pipeline import SyntheticLM
    d = SyntheticLM(vocab=777, seq_len=8, batch_per_host=2, seed=seed)
    b = d.batch(0)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 777


@settings(deadline=None, max_examples=20)
@given(st.integers(4, 3000),
       st.sampled_from([1, 2, 4, 8, 16]),
       st.booleans(),
       st.sampled_from([1, 255, 4095, 65535]),
       st.integers(0, 2**31 - 1))
def test_bf16_value_modes_bit_identical(width, n_sub, signed, vmax, seed):
    """The limb-split/count bf16 contractions are *bit-identical* to the
    f32 kernel and the jnp scatter oracle for any integer workload
    within their bounds — 256 packets of |value| <= 65535 keeps every
    counter below the 2^24 exactness contract (256 * 65535 < 2^24)."""
    import jax.numpy as jnp

    from repro.kernels.sketch_update.ops import sketch_update

    rng = np.random.RandomState(seed % 2**31)
    p = 256
    keys = rng.randint(0, 500, p).astype(np.uint32)
    vals = rng.randint(1, vmax + 1, p).astype(np.float32)
    ts = rng.randint(0, 1 << LOG2_TE, p).astype(np.uint32)
    kw = dict(width=width, n_sub=n_sub, log2_te=LOG2_TE, col_seed=seed % 97,
              sign_seed=seed % 89, sub_seed=seed % 83, signed=signed)
    ref = np.asarray(sketch_update(jnp.asarray(keys), jnp.asarray(vals),
                                   jnp.asarray(ts), backend="ref", **kw))
    modes = ["f32", "limb"] + (["count"] if vmax <= 256 else [])
    for mode in modes:
        got = np.asarray(sketch_update(
            jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ts),
            backend="pallas", interpret=True, value_mode=mode, blk=128,
            **kw))
        np.testing.assert_array_equal(got, ref, err_msg=f"mode={mode}")


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**12 - 1),          # (E=2) x (F=6) liveness bitmask
       st.sampled_from(["cs", "cms"]),
       st.integers(0, 2**31 - 1))
def test_masked_merge_matches_numpy_oracle(mask_bits, kind, seed):
    """The device ``_masked_merge`` under ANY per-epoch fragment mask —
    odd or even survivor counts (cs masked median), any survivor subset
    (cms masked min) — matches the numpy oracle on the survivors; an
    epoch with no survivor fails loudly.  Shapes are fixed so the jit
    cache holds one compile per kind."""
    from repro.kernels.sketch_query import fleet_window_query_device
    from repro.kernels.sketch_update import fleet as FK

    e_count, n_frags, n_sub, width = 2, 6, 4, 256
    sel = np.array([(mask_bits >> i) & 1 for i in range(e_count * n_frags)],
                   bool).reshape(e_count, n_frags)
    rng = np.random.RandomState(seed % 2**31)
    stack = rng.randint(-200, 200,
                        (e_count, n_frags, n_sub, width)).astype(np.float32)
    if kind == "cms":
        stack = np.abs(stack)
    params = np.zeros((e_count, n_frags, FK.N_PARAMS), np.int32)
    for e in range(e_count):
        for f in range(n_frags):
            params[e, f, FK.PARAM_COL_SEED] = 11 + 17 * e + f
            params[e, f, FK.PARAM_SIGN_SEED] = 22 + 17 * e + f
            params[e, f, FK.PARAM_SUB_SEED] = 33 + 17 * e + f
            params[e, f, FK.PARAM_WIDTH] = width
            params[e, f, FK.PARAM_N_SUB] = n_sub
            params[e, f, FK.PARAM_LOG2_N_SUB] = 2
    keys = rng.randint(0, 1 << 20, 16).astype(np.uint32)
    if not sel.any(axis=1).all():
        with pytest.raises(ValueError, match="no on-path fragment"):
            fleet_window_query_device(stack, list(params), keys, kind,
                                      frag_sel=sel)
        return
    got = fleet_window_query_device(stack, list(params), keys, kind,
                                    frag_sel=sel)
    widths = np.full(n_frags, width, np.int64)
    ref = sum(Q.fleet_query_epoch(
        stack[e], params[e, :, FK.PARAM_COL_SEED],
        params[e, :, FK.PARAM_SIGN_SEED], params[e, :, FK.PARAM_SUB_SEED],
        params[e, :, FK.PARAM_N_SUB].astype(np.int64), widths, keys,
        kind, frag_sel=sel[e]) for e in range(e_count))
    np.testing.assert_allclose(got, ref, rtol=1e-6)


@settings(deadline=None, max_examples=15)
@given(mask_bits=st.integers(0, 2**12 - 1),  # (E=2) x (F=6) liveness bitmask
       kind=st.sampled_from(["cs", "cms"]),
       n_shards=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 2**31 - 1))
def test_sharded_merge_matches_host_oracle(mask_bits, kind, n_shards,
                                           seed, multidevice):
    """The cross-device fleet merge (PR 10), for ANY fragment->shard
    assignment (a random permutation of the fragment rows — contiguous
    shard blocks then hold a random fragment subset, including empty /
    pad-only shards since F=6 never divides the axis) and ANY on-path /
    liveness mask: bit-equal to the single-device device path and
    allclose to the host ``fleet_query_epoch`` oracle; all-masked epochs
    still raise.  Shapes are fixed so the jit cache holds one compile
    per (kind, n_shards)."""
    from repro.core.disketch import DiSketchSystem  # noqa: F401 (jax init)
    from repro.kernels.sketch_query import fleet_window_query_device
    from repro.kernels.sketch_update import fleet as FK
    from repro.launch.mesh import make_switch_mesh

    e_count, n_frags, n_sub, width = 2, 6, 4, 256
    rng = np.random.RandomState(seed % 2**31)
    perm = rng.permutation(n_frags)            # fragment -> row slot
    sel = np.array([(mask_bits >> i) & 1 for i in range(e_count * n_frags)],
                   bool).reshape(e_count, n_frags)[:, perm]
    stack = rng.randint(-200, 200,
                        (e_count, n_frags, n_sub, width)).astype(np.float32)
    if kind == "cms":
        stack = np.abs(stack)
    params = np.zeros((e_count, n_frags, FK.N_PARAMS), np.int32)
    for e in range(e_count):
        for f_slot, f in enumerate(perm):
            params[e, f_slot, FK.PARAM_COL_SEED] = 11 + 17 * e + f
            params[e, f_slot, FK.PARAM_SIGN_SEED] = 22 + 17 * e + f
            params[e, f_slot, FK.PARAM_SUB_SEED] = 33 + 17 * e + f
            params[e, f_slot, FK.PARAM_WIDTH] = width
            params[e, f_slot, FK.PARAM_N_SUB] = n_sub
            params[e, f_slot, FK.PARAM_LOG2_N_SUB] = 2
    keys = rng.randint(0, 1 << 20, 16).astype(np.uint32)
    mesh = make_switch_mesh(n_shards)
    if not sel.any(axis=1).all():
        with pytest.raises(ValueError, match="no on-path fragment"):
            fleet_window_query_device(stack, list(params), keys, kind,
                                      frag_sel=sel, mesh=mesh)
        return
    got = fleet_window_query_device(stack, list(params), keys, kind,
                                    frag_sel=sel, mesh=mesh)
    single = fleet_window_query_device(stack, list(params), keys, kind,
                                       frag_sel=sel)
    np.testing.assert_array_equal(got, single)
    widths = np.full(n_frags, width, np.int64)
    ref = sum(Q.fleet_query_epoch(
        stack[e], params[e, :, FK.PARAM_COL_SEED],
        params[e, :, FK.PARAM_SIGN_SEED], params[e, :, FK.PARAM_SUB_SEED],
        params[e, :, FK.PARAM_N_SUB].astype(np.int64), widths, keys,
        kind, frag_sel=sel[e]) for e in range(e_count))
    np.testing.assert_allclose(got, ref, rtol=1e-6)


@settings(deadline=None, max_examples=15)
@given(st.integers(100, 100000), st.sampled_from([1, 2, 4, 8, 16, 64]),
       st.sampled_from(["count", "limb", "f32"]))
def test_select_geometry_respects_budget(width, n_sub, mode):
    """Any auto-selected geometry fits the VMEM budget, is tile-aligned,
    and never exceeds the padded width (floored at one 1024 tile)."""
    from repro.kernels.sketch_update.kernel import (VMEM_BUDGET_BYTES,
                                                    select_geometry,
                                                    vmem_bytes)
    blk, w_blk = select_geometry(width, n_sub, mode)
    assert blk % 1024 == 0 and w_blk % 1024 == 0     # chip (8, 128) tiles
    assert w_blk <= max(1 << int(np.ceil(np.log2(max(width, 128)))), 1024)
    assert vmem_bytes(blk, w_blk, n_sub, mode) <= VMEM_BUDGET_BYTES


# -- durable export plane (PR 7) --------------------------------------------

_EXPORT_SW = 3
_EXPORT_EPOCHS = 2


def _export_streams(epoch, seed):
    from repro.core.disketch import SwitchStream
    r = np.random.default_rng(seed)
    return {sw: SwitchStream(
        r.integers(0, 30, 40).astype(np.uint32),
        np.ones(40, np.int64),
        ((epoch << LOG2_TE)
         + np.sort(r.integers(0, 1 << LOG2_TE, 40)).astype(np.int64)))
        for sw in range(_EXPORT_SW)}


def _export_system():
    from repro.core.disketch import DiSketchSystem
    return DiSketchSystem({sw: 128 for sw in range(_EXPORT_SW)}, "cms",
                          rho_target=5.0, log2_te=LOG2_TE, backend="loop")


@settings(deadline=None, max_examples=20)
@given(st.floats(0.0, 1.0), st.floats(0.0, 1.0), st.floats(0.0, 1.0),
       st.integers(0, 2**16), st.integers(0, 6))
def test_export_drain_invariants(p_drop, p_dup, p_reorder, seed,
                                 max_retries):
    """For ANY seeded drop/dup/reorder/delay pattern and ANY retry
    budget, a drained collector partitions the staged cells into
    applied | lost exactly; applied cells are bit-identical to a
    lossless oracle's; and a loss-free drain reproduces the oracle's
    queries bit for bit."""
    from repro.net.channel import LossyChannel
    from repro.runtime.export import DurableExportPlane

    oracle = _export_system()
    for e in range(_EXPORT_EPOCHS):
        oracle.run_epoch(e, _export_streams(e, 900 + e))
    plane = DurableExportPlane(
        _export_system(),
        LossyChannel(p_drop=p_drop, p_dup=p_dup, p_reorder=p_reorder,
                     delay=(0, 2), seed=seed),
        LossyChannel(p_drop=0.5 * p_drop, p_dup=p_dup, seed=seed + 1),
        max_retries=max_retries)
    for e in range(_EXPORT_EPOCHS):
        plane.run_epoch(e, _export_streams(e, 900 + e))
    plane.drain()

    staged = {(sw, e) for sw in range(_EXPORT_SW)
              for e in range(_EXPORT_EPOCHS)}
    applied = set(plane.collector.applied)
    lost = plane.lost_cells()
    assert applied | lost == staged
    assert not (applied & lost)
    assert plane.pending_cells() == set()
    # exactly the exhausted, never-delivered cells are reported lost
    assert lost == {(sw, e) for sw, exp in plane.exporters.items()
                    for e in exp.exhausted_epochs()
                    if (sw, e) not in applied}
    for sw, e in applied:
        assert np.array_equal(
            np.asarray(plane.system.records[e][sw].counters),
            np.asarray(oracle.records[e][sw].counters)), (sw, e)
    if not lost:
        keys = np.arange(30).astype(np.uint32)
        paths = [tuple(range(_EXPORT_SW))] * len(keys)
        epochs = list(range(_EXPORT_EPOCHS))
        assert np.array_equal(
            plane.query_flows(keys, paths, epochs, failures="mask"),
            oracle.query_flows(keys, paths, epochs, failures="mask"))


# -- lossy channel semantics (PR 8) ------------------------------------------


@settings(deadline=None, max_examples=40)
@given(st.floats(0.0, 1.0), st.floats(0.0, 1.0), st.floats(0.0, 1.0),
       st.integers(0, 2**16), st.integers(1, 24), st.integers(0, 4))
def test_channel_delay_beyond_drain_reported_not_dropped(
        p_drop, p_dup, p_reorder, seed, n_msgs, drain_round):
    """For ANY channel parameters, a drain loop that stops at round T
    must see every still-in-flight message in ``undelivered()`` —
    delayed-past-the-horizon is an explicit state, never a silent drop.
    Conservation holds at every round: sent - dropped + dup ==
    delivered + pending."""
    from repro.net.channel import LossyChannel
    from repro.runtime.export import AckMsg

    ch = LossyChannel(p_drop=p_drop, p_dup=p_dup, p_reorder=p_reorder,
                      delay=(0, 3), seed=seed)
    for i in range(n_msgs):
        ch.send(AckMsg(frag=i % 5, epoch=i // 5, seq=i), now=i % 3)
    delivered = []
    for r in range(drain_round + 1):
        delivered.extend(ch.deliver(r))
    assert (ch.n_sent - ch.n_dropped + ch.n_dup
            == ch.n_delivered + ch.pending())
    und = ch.undelivered()
    assert len(und) == ch.pending()
    rounds = [r for r, _ in und]
    assert rounds == sorted(rounds)            # soonest first
    assert all(r > drain_round for r in rounds)  # due ones were popped
    # extending the drain past the horizon delivers exactly them
    if und:
        late = ch.deliver(rounds[-1])
        assert len(late) == len(und)
        assert ch.pending() == 0 and ch.undelivered() == []


# -- §6 re-equalization (PR 8) -----------------------------------------------


@settings(deadline=None, max_examples=40)
@given(st.dictionaries(st.integers(0, 15),
                       st.tuples(st.sampled_from([1, 2, 4, 8, 16, 64]),
                                 st.floats(1e-3, 1e5)),
                       min_size=1, max_size=8),
       st.floats(1e-2, 1e4))
def test_reequalize_properties(fleet, rho):
    """§6 re-equalization, for ANY fleet state: (a) it touches subepoch
    counts only — the per-switch set and every fragment's memory are
    conserved; (b) each n_i is a power of two in [1, N_MAX] and is
    monotone in that switch's PEB; (c) on a converged fleet (PEBs
    updated under the peb * n/n' model) it is idempotent."""
    from repro.core.equalize import N_MAX, converge_n, reequalize

    ns = {sw: n for sw, (n, _) in fleet.items()}
    pebs = {sw: p for sw, (_, p) in fleet.items()}
    ns2 = reequalize(ns, pebs, rho)
    assert set(ns2) == set(ns)                        # switch set conserved
    for sw, n2 in ns2.items():
        assert 1 <= n2 <= N_MAX and n2 & (n2 - 1) == 0
        # monotone in PEB: a worse-bound fragment never subdivides less
        assert converge_n(ns[sw], 2.0 * pebs[sw], rho) >= n2
    # idempotent once the PEBs reflect the applied counts (Eq. 4 model:
    # peb scales as n/n')
    pebs2 = {sw: pebs[sw] * ns[sw] / ns2[sw] for sw in pebs}
    assert reequalize(ns2, pebs2, rho) == ns2


def test_reequalize_conserves_fleet_memory():
    """System-level: §6 re-equalization after a death re-tunes subepoch
    counts but never moves memory between switches — the survivors'
    fragment bytes (and widths) are exactly what they were."""
    from repro.core.disketch import DiSketchSystem
    from repro.net.simulator import FailureEvent

    s = DiSketchSystem({sw: 256 for sw in range(_EXPORT_SW)}, "cms",
                       rho_target=0.05, log2_te=LOG2_TE)
    for e in range(3):
        s.run_epoch(e, _export_streams(e, 70 + e))
    assert any(n > 1 for n in s.ns.values())  # Eq. 6 actually engaged
    before = {sw: (cfg.memory_bytes, cfg.width)
              for sw, cfg in s.fragments.items()}
    ns_before = dict(s.ns)
    s.apply_event(FailureEvent(2, 0, "fail"))
    assert {sw: (cfg.memory_bytes, cfg.width)
            for sw, cfg in s.fragments.items()} == before
    assert set(s.ns) == set(ns_before)
    changed = [sw for sw in s.ns if s.ns[sw] != ns_before[sw]]
    assert 0 not in changed                 # the dead switch is held out
