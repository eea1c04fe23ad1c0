"""Device-resident query plane parity suite (kernels/sketch_query).

Contract: a ``run_window`` -> ``window_query`` round trip on the fleet
backend serves queries straight from the still-resident window stack —
no full counter-stack host transfer, only the ``(K,)`` estimates — and
the on-device gather/merge (min for CMS, masked median for CS, with and
without §4.3 path restriction) matches the numpy oracles
(``query.fleet_query_window`` on the host stacks and
``query.query_window(merge="fragment")`` on the unpacked records) within
1e-6 relative on integer-exact counters.
"""
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import query as Q
from repro.core.disketch import DiSketchSystem, SwitchStream
from repro.kernels.sketch_query import (KEY_BUCKET_MIN, KEY_CHUNK,
                                        fleet_window_query_device,
                                        fleet_window_query_paths,
                                        key_bucket, key_chunk)
from repro.kernels.sketch_update import fleet as FK
from repro.net.simulator import Replayer
from repro.net.traffic import cov_list, linear_path_workload

LOG2_TE = 12
FLEET_KW = dict(blk=256, w_blk=512)
RTOL = 1e-6


def _small_workload(n_hops=5, seed=1, n_epochs=4):
    rng = np.random.RandomState(seed)
    widths = np.maximum(cov_list(n_hops, 1280, 1.2, rng).astype(int), 4)
    mems = {h: int(w) * 4 for h, w in enumerate(widths)}
    loads = np.maximum(cov_list(n_hops, 30_000, 0.9, rng).astype(int), 16)
    wl = linear_path_workload(n_hops, eval_flows=100, eval_packets=800,
                              bg_packets_per_hop=loads, n_epochs=n_epochs,
                              seed=seed)
    return wl, Replayer(wl, n_hops), mems


def _windowed_system(kind, wl, rep, mems, window=4, **kw):
    sysw = DiSketchSystem(mems, kind, rho_target=4.0, log2_te=wl.log2_te,
                          backend="fleet", fleet_kwargs=dict(FLEET_KW, **kw))
    rep.run(sysw, window=window)
    return sysw


@pytest.mark.parametrize("kind", ["cs", "cms"])
@pytest.mark.parametrize("path", [None, (2,), (1, 3)])
def test_device_matches_host_oracle(kind, path):
    """Device gather/merge == numpy fleet_query_window on the host copy
    of the same stacks — heterogeneous widths/n_sub (the control loop
    spreads ns), cms min vs cs masked median, frag_sel on/off."""
    wl, rep, mems = _small_workload()
    sysw = _windowed_system(kind, wl, rep, mems)
    keys = wl.keys[:65]                    # odd size: exercises padding
    epochs = list(range(wl.n_epochs))
    # ns actually heterogeneous: the equalization loop must have moved n
    assert len(set(sysw.ns.values())) > 1 or max(sysw.ns.values()) > 1
    got = sysw.fleet.window_query(epochs, keys, path=path)

    # no-host-transfer assertion: the window buffer never materialized
    buf = sysw.fleet._window_bufs[0][0]
    assert buf._host is None and buf.resident

    # numpy oracle on the *same* counters (forces the transfer now)
    frag_sel = None
    if path is not None:
        frag_sel = np.array([sw in set(path)
                             for sw in sysw.fleet.frag_order])
    ref = Q.fleet_query_window([buf.host_epoch(e) for e in epochs],
                               [sysw.fleet._params_log[e] for e in epochs],
                               sysw.fleet.widths, keys, kind,
                               frag_sel=frag_sel)
    np.testing.assert_allclose(got, ref, rtol=RTOL)


@pytest.mark.parametrize("kind", ["cs", "cms"])
def test_device_matches_record_plane(kind):
    """Device path == the per-record composite query
    query_window(merge="fragment") over the materialized WindowRecords
    (two identical deterministic systems; one stays resident)."""
    wl, rep, mems = _small_workload()
    a = _windowed_system(kind, wl, rep, mems, window=2)
    b = _windowed_system(kind, wl, rep, mems, window=2)
    keys = wl.keys[:64]
    epochs = list(range(wl.n_epochs))
    got = a.fleet.window_query(epochs, keys)
    assert a.fleet.has_device_window(epochs)
    recs = [[b.records[e][sw] for sw in sorted(mems)] for e in epochs]
    ref = Q.query_window(recs, keys, kind, merge="fragment")
    np.testing.assert_allclose(got, ref, rtol=RTOL)


def test_window_query_without_keep_stacked():
    """Regression (the PR's headline bugfix): window queries work after
    run_window with the default keep_stacked=False — the counters are
    alive in the window buffers; requiring keep_stacked both broke the
    query and forced the transfer window mode exists to avoid."""
    wl, rep, mems = _small_workload(n_epochs=2)
    sysw = _windowed_system("cms", wl, rep, mems, window=2)
    assert not sysw.fleet.keep_stacked and not sysw.fleet.stacked
    out = sysw.fleet.point_query(1, wl.keys[:16])
    assert out.shape == (16,)
    assert sysw.fleet._window_bufs[0][0]._host is None
    with pytest.raises(KeyError, match="not retained"):
        sysw.fleet.window_query([99], wl.keys[:4])


def test_mixed_device_and_host_epochs():
    """One window materialized (host path), one still resident (device
    path): window_query mixes both and matches the all-host answer."""
    wl, rep, mems = _small_workload()
    a = _windowed_system("cs", wl, rep, mems, window=2)
    b = _windowed_system("cs", wl, rep, mems, window=2)
    keys = wl.keys[:32]
    epochs = list(range(wl.n_epochs))
    a.records[0][0]                        # materialize first window only
    assert not a.fleet._window_bufs[0][0].resident
    assert a.fleet._window_bufs[2][0].resident
    got = a.fleet.window_query(epochs, keys)
    for e in epochs:                       # all-host reference
        b.records[e][0]
    ref = b.fleet.window_query(epochs, keys)
    np.testing.assert_allclose(got, ref, rtol=RTOL)


def test_empty_key_batch_and_buckets():
    wl, rep, mems = _small_workload(n_epochs=2)
    sysw = _windowed_system("cms", wl, rep, mems, window=2)
    out = sysw.fleet.window_query([0, 1], np.zeros(0, np.uint32))
    assert out.shape == (0,)
    assert sysw.fleet._window_bufs[0][0].resident  # not even touched
    # key-batch bucketing: pow2 padding, floored, slice back exactly
    assert key_bucket(0) == key_bucket(1) == KEY_BUCKET_MIN
    assert key_bucket(9) == 16 and key_bucket(16) == 16
    a = sysw.fleet.window_query([0, 1], wl.keys[:13])
    b = sysw.fleet.window_query([0, 1], wl.keys[:16])
    np.testing.assert_allclose(a, b[:13], rtol=RTOL)


def test_query_flows_routes_device():
    """System plane: query_flows(merge='fragment') answers from the
    device plane while windows are resident (no transfer), and falls
    back to the per-record path with identical results after
    materialization."""
    wl, rep, mems = _small_workload()
    sysw = _windowed_system("cms", wl, rep, mems, window=2)
    keys = wl.keys[:40]
    paths = [tuple(range(5))] * len(keys)
    epochs = list(range(wl.n_epochs))
    assert sysw.fleet.has_device_window(epochs)
    got = sysw.query_flows(keys, paths, epochs, merge="fragment")
    assert sysw.fleet._window_bufs[0][0]._host is None   # stayed on device
    sysw.records[0][0]                                   # materialize
    assert not sysw.fleet.has_device_window(epochs)
    ref = sysw.query_flows(keys, paths, epochs, merge="fragment")
    np.testing.assert_allclose(got, ref, rtol=RTOL)


def test_records_for_raises_on_missing_epochs():
    """Satellite bugfix: a window query over an unprocessed epoch raises
    (listing the epochs) instead of silently truncating the estimate."""
    wl, rep, mems = _small_workload(n_epochs=2)
    sysd = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te)
    rep.run(sysd)
    keys = wl.keys[:8]
    paths = [tuple(range(5))] * len(keys)
    with pytest.raises(KeyError, match=r"\[7\]"):
        sysd.query_flows(keys, paths, [0, 7])
    sysd.query_flows(keys, paths, [0, 1])  # processed epochs still fine


def test_reprocessed_epoch_invalidates_stale_retention():
    """Reprocessing an epoch (run_epoch after run_window, or vice versa)
    must not leave the query plane answering from the previous run's
    counters under the new run's seeds — stale retention is dropped and
    queries track the latest processing of each epoch."""
    from repro.core.disketch import DiscoSystem

    wl, rep, mems = _small_workload(n_epochs=2)
    # DISCO: n = 1 always, so per-epoch and window runs of the same
    # epochs are bit-identical — any estimate drift below would come
    # from stale-state routing, the thing under test.
    sysd = DiscoSystem(mems, "cms", rho_target=0, log2_te=wl.log2_te,
                       backend="fleet",
                       fleet_kwargs=dict(keep_stacked=True, **FLEET_KW))
    keys = wl.keys[:16]
    sysd.run_epoch(0, rep.epoch_stream(0))
    sysd.run_epoch(1, rep.epoch_stream(1))
    ref = sysd.fleet.window_query([0, 1], keys)
    sysd.run_window(0, [rep.epoch_stream(0), rep.epoch_stream(1)])
    assert 0 not in sysd.fleet.stacked          # stale host stack dropped
    assert sysd.fleet.has_device_window([0, 1])
    np.testing.assert_allclose(sysd.fleet.window_query([0, 1], keys),
                               ref, rtol=RTOL)
    # and the converse: run_epoch drops the window buffer registration
    sysd.run_epoch(0, rep.epoch_stream(0))
    assert 0 not in sysd.fleet._window_bufs
    assert not sysd.fleet.has_device_window([0, 1])
    np.testing.assert_allclose(sysd.fleet.window_query([0, 1], keys),
                               ref, rtol=RTOL)


def test_engine_rejects_unfrozen_windows():
    """The device engine's frozen-ns/width precondition is enforced."""
    params0 = np.zeros((2, FK.N_PARAMS), np.int32)
    params0[:, FK.PARAM_WIDTH] = 128
    params0[:, FK.PARAM_N_SUB] = 2
    params0[:, FK.PARAM_LOG2_N_SUB] = 1
    params1 = params0.copy()
    params1[0, FK.PARAM_N_SUB] = 4
    stack = np.zeros((2, 2, 4, 128), np.float32)
    with pytest.raises(AssertionError, match="frozen"):
        fleet_window_query_device(stack, [params0, params1],
                                  np.arange(4, dtype=np.uint32), "cms")


@pytest.mark.parametrize("kind", ["cs", "cms"])
def test_engine_masked_merge_matches_numpy(kind):
    """Unit-level: the engine's min / masked-median on a synthetic
    integer stack equals fleet_query_epoch summed over epochs, for odd
    and even on-path fragment counts (median midpoint averaging)."""
    rng = np.random.RandomState(7)
    e_count, n_frags, n_sub, width = 3, 6, 4, 96
    stack = rng.randint(-200, 200, (e_count, n_frags, n_sub, width)
                        ).astype(np.float32)
    if kind == "cms":
        stack = np.abs(stack)
    params = np.zeros((e_count, n_frags, FK.N_PARAMS), np.int32)
    for e in range(e_count):
        for f in range(n_frags):
            params[e, f, FK.PARAM_COL_SEED] = 11 + 31 * e + f
            params[e, f, FK.PARAM_SIGN_SEED] = 22 + 31 * e + f
            params[e, f, FK.PARAM_SUB_SEED] = 33 + 31 * e + f
            params[e, f, FK.PARAM_WIDTH] = width
            params[e, f, FK.PARAM_N_SUB] = n_sub
            params[e, f, FK.PARAM_LOG2_N_SUB] = 2
    keys = rng.randint(0, 1 << 20, 37).astype(np.uint32)
    widths = np.full(n_frags, width, np.int64)
    for sel in (None, np.array([1, 0, 1, 1, 0, 1], bool),   # even m
                np.array([0, 1, 1, 0, 1, 0], bool)):        # odd m
        got = fleet_window_query_device(stack, list(params), keys, kind,
                                        frag_sel=sel)
        ref = sum(Q.fleet_query_epoch(
            stack[e], params[e, :, FK.PARAM_COL_SEED],
            params[e, :, FK.PARAM_SIGN_SEED],
            params[e, :, FK.PARAM_SUB_SEED],
            params[e, :, FK.PARAM_N_SUB].astype(np.int64), widths, keys,
            kind, frag_sel=sel) for e in range(e_count))
        np.testing.assert_allclose(got, ref, rtol=RTOL)
    # no on-path fragments must fail loudly (an all-masked epoch is a
    # liveness bug upstream, not a zero estimate)
    with pytest.raises(ValueError, match="fragment"):
        fleet_window_query_device(stack, list(params), keys, kind,
                                  frag_sel=np.zeros(n_frags, bool))


# --- one batched launch per stack for every path of a request ----------

#: Paths of every length 1-5 over the 5-hop fleet, in no row order,
#: three of them single-hop (the §4.4 average on mitigation rows).
MIXED_PATHS = [(0, 1, 2, 3, 4), (1,), (2, 3), (0, 2, 4), (3,), (4, 1, 0),
               (1, 2, 3, 4), (4,)]


@pytest.fixture(scope="module", params=["cs", "cms", "um"])
def three_stacks(request):
    """A mitigating fleet whose 6 epochs sit in 3 resident stacks."""
    wl, rep, mems = _small_workload(n_epochs=6)
    sysw = DiSketchSystem(mems, request.param, rho_target=4.0,
                          log2_te=wl.log2_te, backend="fleet",
                          mitigation=True, fleet_kwargs=FLEET_KW)
    rep.run(sysw, window=2)
    params = np.stack([sysw.fleet._params_log[e] for e in range(6)])
    assert (params[..., FK.PARAM_MIT] != 0).all()
    assert (params[..., FK.PARAM_N_SUB] >= 2).any()
    return sysw


def _per_path(sysw, keys, paths, epochs):
    """The per-path loop: one window_query per distinct path."""
    out = np.zeros(len(keys))
    for p in set(paths):
        idx = np.array([i for i, q in enumerate(paths) if q == p])
        out[idx] = sysw.fleet.window_query(epochs, keys[idx], path=p,
                                           single_hop=len(p) == 1)
    return out


@pytest.mark.parametrize("n_keys", [0, 1, 37, KEY_CHUNK, KEY_CHUNK + 1])
def test_batched_paths_match_per_path_loop(three_stacks, n_keys):
    """query_flows answers every path of a request in one batched launch
    per resident stack and key chunk, bit-identical to the per-path
    loop: cs, cms and um, paths of 1-5 hops, single-hop keys on §4.4
    rows, 0 / 1 / a few keys, one chunk and a chunk plus one."""
    sysw = three_stacks
    epochs = list(range(6))
    keys = (np.arange(n_keys, dtype=np.uint32) * np.uint32(2654435761)
            ) ^ np.uint32(0x9E3779B9)
    rng = np.random.RandomState(n_keys)
    paths = [MIXED_PATHS[i]
             for i in rng.randint(0, len(MIXED_PATHS), n_keys)]
    calls = sysw.fleet.query_launches
    got = sysw.query_flows(keys, paths, epochs, merge="fragment")
    chunks = -(-n_keys // key_chunk(n_keys))
    assert sysw.fleet.query_launches - calls == 3 * chunks
    assert got.shape == (n_keys,)
    assert np.array_equal(got, _per_path(sysw, keys, paths, epochs))
    assert sysw.fleet._window_bufs[0][0]._host is None


def test_key_chunks():
    assert key_chunk(0) == key_chunk(1) == KEY_BUCKET_MIN
    assert key_chunk(KEY_CHUNK - 1) == key_chunk(KEY_CHUNK) == KEY_CHUNK
    assert key_chunk(10 * KEY_CHUNK + 1) == KEY_CHUNK


@pytest.mark.parametrize("kind", ["cs", "cms"])
def test_engine_paths_match_per_path_calls(kind):
    """Unit-level: fleet_window_query_paths over two synthetic stacks ==
    the sum of one fleet_window_query_device call per path and stack,
    bit for bit — unequal path lengths in unsorted slots, the §4.4
    average on the single-hop paths' mitigation rows only; a path with
    no row raises."""
    rng = np.random.RandomState(11)
    e_count, n_rows, n_sub, width = 3, 6, 4, 96
    stacks = []
    for g in range(2):
        stack = rng.randint(0, 300, (e_count, n_rows, n_sub, width)
                            ).astype(np.float32)
        params = np.zeros((e_count, n_rows, FK.N_PARAMS), np.int32)
        for e in range(e_count):
            params[e, :, FK.PARAM_COL_SEED] = 11 + 31 * (e + 3 * g) + np.arange(n_rows)
            params[e, :, FK.PARAM_SIGN_SEED] = -22 - 31 * e + np.arange(n_rows)
            params[e, :, FK.PARAM_SUB_SEED] = 33 + 31 * e + np.arange(n_rows)
        params[:, :, FK.PARAM_WIDTH] = width
        params[:, :, FK.PARAM_N_SUB] = [1, 2, 4, 4, 2, 4]
        params[:, :, FK.PARAM_MIT] = [1, 0, 1, 1, 0, 1]
        stacks.append((stack, list(params)))
    paths = [[5, 0, 3], [2], [1], [4, 1], [0, 1, 2, 3, 4, 5], [3, 2]]
    path_rows = np.full((len(paths), 6), -1, np.int32)
    for j, p in enumerate(paths):
        path_rows[j, :len(p)] = p
    hop = np.array([len(p) == 1 for p in paths])
    keys = rng.randint(0, 1 << 30, 61).astype(np.uint32)
    path_id = rng.randint(0, len(paths), len(keys))
    got = fleet_window_query_paths(stacks, keys, path_rows, path_id, kind,
                                   single_hop=hop)
    want = np.zeros(len(keys))
    for j, p in enumerate(paths):
        idx = np.flatnonzero(path_id == j)
        sel = np.isin(np.arange(n_rows), p)
        for stack, params in stacks:
            want[idx] += fleet_window_query_device(
                stack, params, keys[idx], kind, frag_sel=sel,
                single_hop=bool(hop[j]))
    assert np.array_equal(got, want)
    with pytest.raises(ValueError, match="path_id"):
        fleet_window_query_paths(stacks, keys, path_rows,
                                 path_id + len(paths), kind)
    path_rows[3] = -1
    with pytest.raises(ValueError, match="fragment"):
        fleet_window_query_paths(stacks, keys, path_rows, path_id, kind)


def test_churned_request_falls_back_per_path():
    """Churn masking that touches a queried epoch sends each path to its
    own window_query (liveness masks and blind-epoch scaling there);
    the answers still equal the per-path loop's, and an untouched
    window of the same fleet is batched again."""
    wl, rep, mems = _small_workload(n_epochs=4)
    sysw = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te,
                          backend="fleet", fleet_kwargs=FLEET_KW)
    for e0 in (0, 2):
        ev = [(), [SimpleNamespace(kind="fail", switch=2, factor=1.0)]] \
            if e0 == 2 else None
        sysw.run_window(e0, [rep.epoch_stream(e) for e in (e0, e0 + 1)],
                        events_by_epoch=ev)
    keys = wl.keys[:90]
    paths = [MIXED_PATHS[i % len(MIXED_PATHS)] for i in range(len(keys))]
    churned = [0, 1, 2, 3]
    assert not sysw.fleet.batches_paths(churned, "mask")
    assert sysw.fleet.batches_paths(churned, "oblivious")
    assert sysw.fleet.batches_paths([0, 1], "mask")
    # switch 2 is masked at epochs 2-3; (2, 3) keeps switch 3 there
    got = sysw.query_flows(keys, paths, churned, merge="fragment")
    assert np.array_equal(got, _per_path(sysw, keys, paths, churned))
    for eps in ([0, 1], churned):
        a = sysw.query_flows(keys, paths, eps, merge="fragment",
                             failures="oblivious")
        b = np.zeros(len(keys))
        for p in set(paths):
            idx = np.array([i for i, q in enumerate(paths) if q == p])
            b[idx] = sysw.fleet.window_query(eps, keys[idx], path=p,
                                             single_hop=len(p) == 1,
                                             failures="oblivious")
        assert np.array_equal(a, b)


# --- every query route over the group-extent window buffer ---------------

LAYOUT_WIDTHS = [6000, 64, 1500, 300, 3000, 700]
LAYOUT_NS = [[1, 2, 4, 8, 16, 1], [16, 8, 1, 2, 4, 2]]
LAYOUT_PATHS = [(0, 1, 2, 3, 4, 5), (1,), (2, 3), (0, 2, 4), (5,),
                (4, 1, 0), (1, 2, 3, 4)]


def _layout_streams(e):
    out = {}
    for sw in range(len(LAYOUT_WIDTHS)):
        r = np.random.default_rng(1000 * e + sw)
        n = 300 + 200 * sw
        out[sw] = SwitchStream(
            r.integers(0, 5000, n).astype(np.uint32),
            r.integers(1, 4, n).astype(np.int64),
            ((e << LOG2_TE) + r.integers(0, 1 << LOG2_TE, n)).astype(np.int64),
            single_hop=r.random(n) < 0.3)
    return out


def _layout_system(kind, churn=False):
    """Skewed widths, n forced over {1, 2, 4, 8, 16} in each of two
    2-epoch windows; ``churn``: switch 2 fails mid-window 0 (its epoch 0
    cell is lost, parity-recoverable in group [0, 1, 2])."""
    levels = 4 if kind == "um" else 1
    mems = {sw: 4 * w * levels for sw, w in enumerate(LAYOUT_WIDTHS)}
    kw = {"parity_groups": [[0, 1, 2], [3, 4, 5]]} if churn else {}
    s = DiSketchSystem(mems, kind, rho_target=4.0, log2_te=LOG2_TE,
                       backend="fleet", mitigation=True,
                       fleet_kwargs=dict(FLEET_KW, **kw),
                       **({"n_levels": levels} if kind == "um" else {}))
    for w, ns in enumerate(LAYOUT_NS):
        s.ns = dict(enumerate(ns))
        ev = None
        if churn and w == 0:
            ev = [(), [SimpleNamespace(kind="fail", switch=2, factor=1.0)]]
        s.run_window(2 * w, [_layout_streams(e) for e in (2 * w, 2 * w + 1)],
                     events_by_epoch=ev)
    return s


@pytest.mark.parametrize("route", ["batched", "per_path", "um", "churned"])
def test_layout_query_routes_match_host_oracle(route):
    """Over windows whose groups span n = 1..16 at skewed widths, every
    device query route equals its host oracle on the same counters bit
    for bit: the batched request (against the per-record merge), the
    per-path window query and the UnivMon levels (against the numpy
    fleet oracle on the transferred buffers), and a churned request with
    parity recovery (device-resident patch against host patch)."""
    kind = {"um": "um", "per_path": "cs"}.get(route, "cs")
    churn = route == "churned"
    dev, host = _layout_system(kind, churn), _layout_system(kind, churn)
    epochs = [0, 1, 2, 3]
    for e in epochs:                     # the oracle's counters on host
        host.records[e][0]
    assert not host.fleet.has_device_window(epochs)
    assert dev.fleet.has_device_window(epochs)
    keys = (np.arange(300, dtype=np.uint32) * np.uint32(2654435761)) \
        ^ np.uint32(0x5BD1E995)
    paths = [LAYOUT_PATHS[i % len(LAYOUT_PATHS)] for i in range(len(keys))]
    if churn:
        assert dev.fleet.recoverable() == host.fleet.recoverable() \
            == {0: [2]}
    if route in ("batched", "churned"):
        failures = "recover" if churn else "mask"
        got = dev.query_flows(keys, paths, epochs, merge="fragment",
                              failures=failures)
        want = host.query_flows(keys, paths, epochs, merge="fragment",
                                failures=failures)
        if churn:
            assert dev.fleet.recoverable() == host.fleet.recoverable() == {}
            for sw in range(len(LAYOUT_WIDTHS)):
                np.testing.assert_array_equal(
                    dev.fleet.cell_counters(0, sw),
                    host.fleet.cell_counters(0, sw))
    elif route == "per_path":
        got = np.concatenate([dev.fleet.window_query(
            epochs, keys, path=p, single_hop=len(p) == 1)
            for p in LAYOUT_PATHS])
        want = np.concatenate([host.fleet.window_query(
            epochs, keys, path=p, single_hop=len(p) == 1)
            for p in LAYOUT_PATHS])
    else:
        got = np.stack([dev.fleet.um_level_window_query(epochs, keys, path=p)
                        for p in LAYOUT_PATHS])
        want = np.stack([host.fleet.um_level_window_query(epochs, keys,
                                                          path=p)
                         for p in LAYOUT_PATHS])
    assert dev.fleet._window_bufs[0][0].resident
    assert np.array_equal(got, want)
