"""Epoch-window super-dispatch tests: E epochs x F fragments in one
kernel launch with device-resident counters.

Exactness contract: with no control action (DISCO, n = 1 always) window
mode is bit-identical to per-epoch dispatch.  With the §4.2 control loop
active, ``ns`` is frozen per window, so the trajectory may diverge — the
contract is then behavioural: query error within 2x of per-epoch control
(the paper's "within a factor of two" forgiveness), lazy record
materialization, and the window query path matching its per-epoch sum.
"""
import numpy as np
import pytest

from repro.core import equalize
from repro.core.disketch import DiSketchSystem, DiscoSystem, SwitchStream
from repro.core.fleet import WindowRecords
from repro.net.simulator import Replayer, rmse
from repro.net.traffic import cov_list, linear_path_workload

LOG2_TE = 12
FLEET_KW = dict(blk=256, w_blk=512)


def _small_workload(n_hops=5, seed=1, n_epochs=4):
    rng = np.random.RandomState(seed)
    widths = np.maximum(cov_list(n_hops, 1280, 1.2, rng).astype(int), 4)
    mems = {h: int(w) * 4 for h, w in enumerate(widths)}
    loads = np.maximum(cov_list(n_hops, 30_000, 0.9, rng).astype(int), 16)
    wl = linear_path_workload(n_hops, eval_flows=100, eval_packets=800,
                              bg_packets_per_hop=loads, n_epochs=n_epochs,
                              seed=seed)
    return wl, Replayer(wl, n_hops), mems


def test_window_bit_identical_without_control():
    """DISCO (n = 1 everywhere, no control): one 4-epoch super-dispatch
    must equal four per-epoch dispatches bit for bit."""
    wl, rep, mems = _small_workload()
    per_epoch = DiscoSystem(mems, "cs", rho_target=0, log2_te=wl.log2_te,
                            backend="fleet", fleet_kwargs=FLEET_KW)
    windowed = DiscoSystem(mems, "cs", rho_target=0, log2_te=wl.log2_te,
                           backend="fleet", fleet_kwargs=FLEET_KW)
    rep.run(per_epoch)
    rep.run(windowed, window=4)
    for e in range(wl.n_epochs):
        for sw in mems:
            np.testing.assert_array_equal(
                per_epoch.records[e][sw].counters,
                windowed.records[e][sw].counters)
    # device-side f32 PEBs agree with the float64 host path to f32 eps
    for e in range(wl.n_epochs):
        for sw in mems:
            assert windowed.peb_log[e][sw] == pytest.approx(
                per_epoch.peb_log[e][sw], rel=1e-5)


def test_window_partial_tail_and_epoch_numbering():
    """A replay whose epoch count is not a window multiple runs a short
    tail window; per-epoch seeds (epoch-dependent!) stay correct."""
    wl, rep, mems = _small_workload(n_epochs=5)
    a = DiscoSystem(mems, "cms", rho_target=0, log2_te=wl.log2_te,
                    backend="fleet", fleet_kwargs=FLEET_KW)
    b = DiscoSystem(mems, "cms", rho_target=0, log2_te=wl.log2_te,
                    backend="fleet", fleet_kwargs=FLEET_KW)
    rep.run(a)
    rep.run(b, window=2)          # windows: [0,1], [2,3], [4]
    assert sorted(b.records) == list(range(5))
    for e in range(5):
        for sw in mems:
            np.testing.assert_array_equal(a.records[e][sw].counters,
                                          b.records[e][sw].counters)


def test_window_control_error_within_2x():
    """With the Eq. 6 loop active, frozen-per-window ns may diverge from
    per-epoch control, but window-mode query error stays within the
    factor-of-two §4.2 budget."""
    wl, rep, mems = _small_workload()
    loop = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te)
    win = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te,
                         backend="fleet", fleet_kwargs=FLEET_KW)
    rep.run(loop)
    rep.run(win, window=2)
    keys = wl.keys[:100]
    paths = [tuple(range(5))] * len(keys)
    epochs = list(range(wl.n_epochs))
    truth = wl.sizes[:100]
    err_loop = rmse(loop.query_flows(keys, paths, epochs), truth)
    err_win = rmse(win.query_flows(keys, paths, epochs), truth)
    assert err_win <= 2.0 * err_loop
    # control still reacted (logs cover every epoch, ns moved)
    assert len(win.peb_log) == len(win.n_log) == wl.n_epochs
    assert max(win.ns.values()) > 1


def test_window_records_are_lazy():
    """run_window defers the host transfer: records materialize (one
    shared transfer per window) only when the query plane touches them."""
    wl, rep, mems = _small_workload(n_epochs=2)
    sysw = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te,
                          backend="fleet", fleet_kwargs=FLEET_KW)
    rep.run(sysw, window=2)
    recs0, recs1 = sysw.records[0], sysw.records[1]
    assert isinstance(recs0, WindowRecords)
    assert recs0._recs is None and recs0._buf._host is None  # untouched
    assert 0 in recs0 and 99 not in recs0                    # no transfer
    assert recs0._buf._host is None
    assert set(recs0) == set(mems) and len(recs0) == len(mems)
    rec = recs0[0]                                           # materialize
    assert recs0._buf._host is not None
    assert recs0._buf is recs1._buf                          # shared buffer
    assert rec.counters.dtype == np.int64
    # counters are views of the shared window stack, not copies
    assert rec.counters.base is not None


def test_window_query_matches_per_epoch_sum():
    """fleet.window_query == sum of per-epoch point queries, with and
    without path restriction."""
    wl, rep, mems = _small_workload()
    sysw = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te,
                          backend="fleet",
                          fleet_kwargs=dict(keep_stacked=True, **FLEET_KW))
    rep.run(sysw, window=2)
    keys = wl.keys[:64]
    epochs = [0, 1, 2, 3]
    for path in (None, (2,)):
        got = sysw.fleet.window_query(epochs, keys, path=path)
        ref = sum(sysw.fleet.point_query(e, keys, path=path)
                  for e in epochs)
        np.testing.assert_allclose(got, ref)
    with pytest.raises(KeyError, match="not retained"):
        sysw.fleet.window_query([99], keys)


def test_window_overflow_guards():
    """Both exactness guards fire in window mode too (cms output-peak,
    cs input-mass)."""
    k = np.full(8, 5, np.uint32)
    st = {0: SwitchStream(k, np.full(8, 1 << 23, np.int64),
                          np.zeros(8, np.int64))}
    for kind, match in (("cms", "2\\^24"), ("cs", "mass")):
        sysw = DiSketchSystem({0: 1024}, kind, rho_target=1e18,
                              log2_te=LOG2_TE, backend="fleet",
                              fleet_kwargs=FLEET_KW)
        with pytest.raises(OverflowError, match=match):
            sysw.run_window(0, [st, st])


def test_window_loop_backend_fallback():
    """run_window on a loop-backend system falls back to exact per-epoch
    processing (same trajectory as run_epoch)."""
    wl, rep, mems = _small_workload(n_epochs=2)
    a = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te)
    b = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te)
    rep.run(a)
    b.run_window(0, [rep.epoch_stream(0), rep.epoch_stream(1)])
    assert a.ns == b.ns and a.n_log == b.n_log
    for e in range(2):
        for sw in mems:
            np.testing.assert_array_equal(a.records[e][sw].counters,
                                          b.records[e][sw].counters)


def test_peb_fleet_device_matches_host():
    rng = np.random.RandomState(3)
    stacked = rng.randint(-50, 50, (6, 8, 32)).astype(np.int64)
    ns = np.array([1, 2, 8, 4, 1, 8], np.int64)
    widths = np.array([32, 16, 8, 32, 4, 16], np.int64)
    # zero out dead cells to honour the stacked-layout contract
    for f in range(6):
        stacked[f, ns[f]:, :] = 0
        stacked[f, :, widths[f]:] = 0
    # the window join reads them off each subepoch group's launch, at
    # the group's own (n, widest width) extent, in group order
    from repro.core.fleet import _join_groups

    groups = [np.flatnonzero(ns == n) for n in np.unique(ns)]
    outs = tuple(stacked[g, :ns[g[0]], :widths[g].max()].astype(np.float32)
                 for g in groups)
    for kind in ("cs", "cms"):
        host = equalize.peb_fleet(stacked, ns, widths, kind)
        flat, peak, dev = _join_groups(
            outs, tuple(widths[g].astype(np.int32) for g in groups),
            kind=kind, n_levels=1, size=sum(o.size for o in outs) + 7)
        np.testing.assert_allclose(np.asarray(dev), host[np.concatenate(groups)],
                                   rtol=1e-5)
        assert float(peak) == np.abs(stacked).max()
        assert flat.shape == (sum(o.size for o in outs) + 7,)


# --- the window buffer's layout: each n_sub group at its own extent -----

#: Skewed widths (64 to 6000 counters) and, per window, forced subepoch
#: counts that give groups of every n in {1, 2, 4, 8, 16}.
LAYOUT_WIDTHS = [6000, 64, 1500, 300, 3000, 700]
LAYOUT_NS = [[1, 2, 4, 8, 16, 1], [16, 8, 1, 2, 4, 2]]
LAYOUT_LEVELS = {"cs": 1, "cms": 1, "um": 4}


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


def _layout_system(kind):
    levels = LAYOUT_LEVELS[kind]
    mems = {sw: 4 * w * levels for sw, w in enumerate(LAYOUT_WIDTHS)}
    s = DiSketchSystem(mems, kind, rho_target=4.0, log2_te=LOG2_TE,
                       backend="fleet", mitigation=kind == "cs",
                       fleet_kwargs=FLEET_KW,
                       **({"n_levels": levels} if kind == "um" else {}))
    for w, ns in enumerate(LAYOUT_NS):
        s.ns = dict(enumerate(ns))
        s.run_window(2 * w, [_layout_streams(e) for e in (2 * w, 2 * w + 1)])
    return s


@pytest.mark.parametrize("kind", ["cs", "cms", "um"])
def test_layout_cells_match_loop(kind):
    """Every (epoch, fragment) cell of the group-extent window buffer is
    bit-identical to the per-switch loop's record at the same n."""
    from repro.core.fragment import process_epoch

    s = _layout_system(kind)
    for w, ns in enumerate(LAYOUT_NS):
        for e in (2 * w, 2 * w + 1):
            streams = _layout_streams(e)
            for sw, n in enumerate(ns):
                st = streams[sw]
                rec = process_epoch(s.fragments[sw], e, n, st.keys,
                                    st.values, st.ts, e << LOG2_TE, LOG2_TE,
                                    single_hop=st.single_hop)
                want = rec.counters if kind == "um" else rec.counters[None]
                got = s.fleet.cell_counters(e, sw)
                assert got.shape == want.shape
                np.testing.assert_array_equal(got, want)
                np.testing.assert_array_equal(s.records[e][sw].counters,
                                              rec.counters)


@pytest.mark.parametrize("kind", ["cs", "um"])
def test_layout_resident_bytes_are_group_extents(kind):
    """A window's buffer holds each n_sub group's (rows, n, width)
    rectangle and nothing more: strictly below E·F·n_max·w_max, and the
    runner's figures are the sums over its resident windows."""
    s = _layout_system(kind)
    levels = LAYOUT_LEVELS[kind]
    widths = np.array([s.fragments[sw].width
                       for sw in range(len(LAYOUT_WIDTHS))])
    stack = counters = 0
    for w, ns in enumerate(LAYOUT_NS):
        ns = np.array(ns)
        buf = s.fleet._window_bufs[2 * w][0]
        want = 4 * 2 * levels * sum(int((ns == n).sum()) * n
                                    * widths[ns == n].max()
                                    for n in np.unique(ns))
        assert buf.stack_bytes == want == 4 * buf.device().size
        assert want < 4 * 2 * len(ns) * levels * ns.max() * widths.max()
        assert buf.counter_bytes == 4 * 2 * levels * int((ns * widths).sum())
        stack += want
        counters += buf.counter_bytes
    assert (s.fleet.stack_bytes, s.fleet.counter_bytes) == (stack, counters)
    s.records[0][0]                     # the first window leaves the device
    assert s.fleet.stack_bytes == stack - s.fleet._window_bufs[0][0].stack_bytes
