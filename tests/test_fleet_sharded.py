"""Sharded-fleet parity plane: the device-mesh fleet must be
BIT-IDENTICAL to the single-device fleet — counters and every query
path — under ``--xla_force_host_platform_device_count=8`` (tests/
conftest.py merges the flag; the ``multidevice`` fixture skips loudly
when it did not take effect).

The exactness argument (docs/sharding.md): per-shard dispatch reuses
the ordinary grouped ragged launch over the shard's own rows only, so
it differs from the single-device launch exclusively in which zero
rows/columns are materialized; the query plane all_gathers the gathered
counter slices in single-device row order before the unchanged masked
min/median merge.  Equality below is ``array_equal`` / ``==``, not
allclose.
"""
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.disketch import DiSketchSystem, SwitchStream
from repro.core.fleet import FleetEpochRunner
from repro.launch.mesh import make_switch_mesh, switch_axis_size

N_SW = 6
MEMS = {sw: 4096 if sw % 2 else 2048 for sw in range(N_SW)}
PATH = (0, 2, 4)
KEYS = np.arange(0, 500, 7, dtype=np.uint32)
EPOCHS = [0, 1, 2]


def _streams(e, n_sw=N_SW, skew=1):
    out = {}
    for sw in range(n_sw):
        n = 150 + skew * 40 * sw + 10 * e
        r = np.random.default_rng(100 * e + sw)
        out[sw] = SwitchStream(
            r.integers(0, 500, n).astype(np.uint32),
            r.integers(1, 5, n).astype(np.int64),
            r.integers(0, 1 << 12, n).astype(np.int64),
            single_hop=r.random(n) < 0.3)
    return out


def _system(kind, mesh, **kw):
    return DiSketchSystem(MEMS, kind, rho_target=2.0, log2_te=12,
                          backend="fleet", mesh=mesh, **kw)


def _pair(kind, n_dev, **kw):
    mesh = make_switch_mesh(n_dev)
    assert switch_axis_size(mesh) == n_dev
    return _system(kind, None, **kw), _system(kind, mesh, **kw)


def _assert_same_cells(ref, sh, epochs=EPOCHS):
    """Every (epoch, fragment) cell of the two fleets is bit-identical
    (their window buffers lay the cells out at other offsets)."""
    for e in epochs:
        for sw in ref.fleet.frag_order:
            assert np.array_equal(ref.fleet.cell_counters(e, sw),
                                  sh.fleet.cell_counters(e, sw))


def _run_both(ref, sh, e_count=3, **kw):
    for s in (ref, sh):
        s.run_window(0, [_streams(e) for e in range(e_count)], **kw)


@pytest.mark.parametrize("kind", ["cms", "cs"])
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_counters_and_queries_bit_identical(kind, n_dev, multidevice):
    ref, sh = _pair(kind, n_dev)
    _run_both(ref, sh)
    # heterogeneous widths (2048/4096 memories) and, after the window,
    # heterogeneous ns from the §4.2 control — both fleets saw the same
    # PEBs, so their control trajectories must agree too
    assert ref.ns == sh.ns
    paths = [PATH] * len(KEYS)
    a = ref.query_flows(KEYS, paths, EPOCHS, merge="fragment")
    b = sh.query_flows(KEYS, paths, EPOCHS, merge="fragment")
    assert np.array_equal(a, b)
    # single-hop path group exercises the §4.4 mitigation flag plumbing
    a1 = ref.query_flows(KEYS, [(3,)] * len(KEYS), EPOCHS, merge="fragment")
    b1 = sh.query_flows(KEYS, [(3,)] * len(KEYS), EPOCHS, merge="fragment")
    assert np.array_equal(a1, b1)
    _assert_same_cells(ref, sh)


@pytest.mark.parametrize("n_dev", [4, 8])
def test_um_levels_and_entropy_bit_identical(n_dev, multidevice):
    ref, sh = _pair("um", n_dev, n_levels=4)
    _run_both(ref, sh)
    paths = [PATH] * len(KEYS)
    a = ref.fleet.um_level_window_query(EPOCHS, KEYS, path=PATH)
    b = sh.fleet.um_level_window_query(EPOCHS, KEYS, path=PATH)
    assert np.array_equal(a, b)
    ea = ref.query_entropy(KEYS, paths, EPOCHS, total=1e4, n_levels=4,
                           merge="fragment")
    eb = sh.query_entropy(KEYS, paths, EPOCHS, total=1e4, n_levels=4,
                          merge="fragment")
    assert ea == eb
    fa = ref.query_flows(KEYS, paths, EPOCHS, merge="fragment")
    fb = sh.query_flows(KEYS, paths, EPOCHS, merge="fragment")
    assert np.array_equal(fa, fb)
    _assert_same_cells(ref, sh)


def test_churn_mask_parity_and_blind_raise(multidevice):
    # mid-window fail: epoch >= 1 dead + epoch 0 lost for switch 2, on
    # both fleets; masked queries must stay bit-identical, and a path
    # whose every fragment is out must raise on both.
    ev = [(), [SimpleNamespace(kind="fail", switch=2, factor=1.0)], ()]
    ref, sh = _pair("cms", 4)
    _run_both(ref, sh, events_by_epoch=ev)
    paths = [PATH] * len(KEYS)
    a = ref.query_flows(KEYS, paths, EPOCHS, merge="fragment",
                        failures="mask")
    b = sh.query_flows(KEYS, paths, EPOCHS, merge="fragment",
                       failures="mask")
    assert np.array_equal(a, b)
    assert ref.last_observability["scale"] == \
        sh.last_observability["scale"] == 1.0
    for s in (ref, sh):
        with pytest.raises(ValueError, match="unobservable"):
            s.fleet.window_query([1, 2], KEYS[:4], path=(2,),
                                 failures="mask")


def test_parity_recovery_shard_local(multidevice):
    # 6 frags over 2 shards -> shard-local chunked groups of 3; a lost
    # cell must reconstruct bit-identically on the sharded fleet.
    groups = [[0, 1, 2], [3, 4, 5]]
    ev = [(), (), [SimpleNamespace(kind="fail", switch=4, factor=1.0)]]
    ref, sh = _pair("cms", 2, fleet_kwargs={"parity_groups": groups})
    _run_both(ref, sh, events_by_epoch=ev)
    assert ref.fleet.recoverable() == sh.fleet.recoverable() \
        == {0: [4], 1: [4]}
    assert ref.fleet.recover() == sh.fleet.recover()
    a = ref.query_flows(KEYS, [PATH] * len(KEYS), EPOCHS, merge="fragment")
    b = sh.query_flows(KEYS, [PATH] * len(KEYS), EPOCHS, merge="fragment")
    assert np.array_equal(a, b)
    _assert_same_cells(ref, sh)


def test_parity_group_spanning_shards_rejected(multidevice):
    frags = _system("cms", None).fragments
    with pytest.raises(ValueError, match="shard-local"):
        FleetEpochRunner(frags, 12, mesh=make_switch_mesh(2),
                         parity_groups=[[2, 3]])  # spans shards 0 and 1


def test_run_epoch_mesh_matches(multidevice):
    ref, sh = _pair("cs", 4)
    for s in (ref, sh):
        s.run_epoch(0, _streams(0))
        s.run_epoch(1, _streams(1), events=[
            SimpleNamespace(kind="fail", switch=1, factor=1.0)])
    for e in (0, 1):
        for sw in set(ref.records[e]) | set(sh.records[e]):
            assert np.array_equal(ref.records[e][sw].counters,
                                  sh.records[e][sw].counters)
    assert set(sh.records[1]) == set(range(N_SW)) - {1}


def test_mesh_requires_fleet_backend_and_switch_axis(multidevice):
    import jax

    with pytest.raises(ValueError, match="backend='fleet'"):
        DiSketchSystem(MEMS, "cms", 2.0, 12, backend="loop",
                       mesh=make_switch_mesh(2))
    with pytest.raises(ValueError, match="switch"):
        FleetEpochRunner(_system("cms", None).fragments, 12,
                         mesh=jax.make_mesh((2,), ("data",)))


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_shard_launches_run_on_own_device(n_dev, multidevice, monkeypatch):
    """Each shard's grouped launches run on the device that holds its
    block of the window buffer, not on the default device."""
    import repro.core.fleet as fleet_mod
    from repro.kernels.sketch_update import fleet as FK

    launched = []
    launch = FK.fleet_update_ragged

    def recording(*a, **kw):
        out = launch(*a, **kw)
        launched.append(next(iter(out.devices())))
        return out

    monkeypatch.setattr(FK, "fleet_update_ragged", recording)
    sh = _system("cs", make_switch_mesh(n_dev))
    spans = []
    span = fleet_mod.obs.span

    def recording_span(name, **counts):
        if name == "fleet.shard":
            spans.append(counts)
        return span(name, **counts)

    monkeypatch.setattr(fleet_mod.obs, "span", recording_span)
    sh.run_window(0, [_streams(e) for e in range(3)])
    fleet = sh.fleet
    buf = fleet._window_bufs[0][0]
    by_block = {(idx[0].start or 0) // buf.shard_len: d for d, idx in
                buf.device().sharding.addressable_devices_indices_map(
                    buf.device().shape).items()}
    want = [by_block[s["shard"]] for s in spans]
    assert [s["device"] for s in spans] == [d.id for d in want]
    assert len(set(want)) == len(spans)      # one device per shard
    # every group launch of a shard ran on that shard's device
    assert set(launched) == set(want) and len(launched) >= len(spans)
    for shard in buf.device().addressable_shards:
        assert shard.data.shape == (buf.shard_len,)


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_run_window_reads_only_peaks_and_pebs(n_dev, multidevice,
                                              monkeypatch):
    """run_window on a mesh moves no counters to the host: the only
    device-to-host read is one ``jax.device_get`` of every shard's peak
    and PEBs, and no device array is converted implicitly."""
    import jax
    from jax._src.array import ArrayImpl

    sh = _system("cms", make_switch_mesh(n_dev))
    streams = [_streams(e) for e in range(3)]
    allowed, reads = [False], []
    to_np, buffer = ArrayImpl.__array__, ArrayImpl.__buffer__
    value = ArrayImpl._value
    device_get = jax.device_get

    def check():
        assert allowed[0], "implicit device-to-host transfer"

    def guarded_array(self, *a, **kw):
        check()
        return to_np(self, *a, **kw)

    def guarded_buffer(self, *a, **kw):
        check()
        return buffer(self, *a, **kw)

    def read(x):
        allowed[0] = True
        try:
            got = device_get(x)
        finally:
            allowed[0] = False
        reads.append(sum(np.size(a) for a in jax.tree.leaves(got)))
        return got

    monkeypatch.setattr(ArrayImpl, "__array__", guarded_array)
    monkeypatch.setattr(ArrayImpl, "__buffer__", guarded_buffer)
    monkeypatch.setattr(ArrayImpl, "_value", property(
        lambda self: check() or value.fget(self)))
    monkeypatch.setattr(jax, "device_get", read)
    with jax.transfer_guard_device_to_host("disallow"):
        sh.run_window(0, streams)
    monkeypatch.undo()
    live = sum(lo < hi for lo, hi in sh.fleet._shard_frag_bounds)
    # one read: a peak per non-empty shard and a PEB per (epoch, switch)
    assert reads == [live + 3 * N_SW]
    assert sh.fleet._window_bufs[0][0]._host is None


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_one_shard_span_per_nonempty_shard(n_dev, multidevice, monkeypatch):
    """``repro.fleet.shard`` wraps each non-empty shard's launches, once
    a window, with its rows and packets."""
    import repro.core.fleet as fleet_mod

    spans = []
    span = fleet_mod.obs.span

    def recording_span(name, **counts):
        if name == "fleet.shard":
            spans.append(counts)
        return span(name, **counts)

    monkeypatch.setattr(fleet_mod.obs, "span", recording_span)
    sh = _system("cs", make_switch_mesh(n_dev))
    for e0 in (0, 2):
        sh.run_window(e0, [_streams(e) for e in (e0, e0 + 1)])
    bounds = [(s, lo, hi) for s, (lo, hi)
              in enumerate(sh.fleet._shard_frag_bounds) if lo < hi]
    assert [(c["shard"], c["rows"]) for c in spans] == [
        (s, 2 * (hi - lo)) for s, lo, hi in bounds] * 2
