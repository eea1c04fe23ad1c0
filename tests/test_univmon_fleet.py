"""UnivMon + §4.4 mitigation on the fleet and device-query planes.

Contract (PR 5 tentpole): ``DiSketchSystem(kind="um", mitigation=...,
backend="fleet")`` produces counters *bit-identical* to the per-switch
loop — every level, every subepoch, heterogeneous widths/n_sub — and the
device query plane answers UnivMon level queries (level-0 frequency,
all-levels G-sum inputs, entropy) from the still-resident window stacks
within 1e-6 relative of the host oracles, without transferring a counter
stack.
"""
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.disketch import DiSketchSystem, SwitchStream
from repro.core.fleet import (FleetPacket, build_params,
                              fold_packet_flags, pack_csr)
from repro.core.fragment import FragmentConfig, level_seed_mix, process_epoch
from repro.kernels.sketch_update import fleet as FK
from repro.kernels.sketch_update.kernel import LVL_SHIFT, SH_SHIFT
from repro.net.simulator import Replayer
from repro.net.traffic import cov_list, linear_path_workload

LOG2_TE = 12
FLEET_KW = dict(blk=256, w_blk=512)
RTOL = 1e-6
N_LEVELS = 4


def _small_workload(n_hops=5, seed=1, n_epochs=4, mem_scale=8):
    rng = np.random.RandomState(seed)
    # UnivMon divides the width by n_levels, so give fragments more
    # memory than the cs/cms suites to keep widths >= a few buckets.
    widths = np.maximum(cov_list(n_hops, 1280 * mem_scale, 1.2,
                                 rng).astype(int), 4)
    mems = {h: int(w) * 4 for h, w in enumerate(widths)}
    loads = np.maximum(cov_list(n_hops, 30_000, 0.9, rng).astype(int), 16)
    wl = linear_path_workload(n_hops, eval_flows=100, eval_packets=800,
                              bg_packets_per_hop=loads, n_epochs=n_epochs,
                              seed=seed)
    return wl, Replayer(wl, n_hops), mems


def _systems(mems, wl, mitigation=False, **fleet_kw):
    loop = DiSketchSystem(mems, "um", rho_target=4.0, log2_te=wl.log2_te,
                          n_levels=N_LEVELS, mitigation=mitigation)
    fleet = DiSketchSystem(mems, "um", rho_target=4.0, log2_te=wl.log2_te,
                           n_levels=N_LEVELS, mitigation=mitigation,
                           backend="fleet",
                           fleet_kwargs=dict(FLEET_KW, **fleet_kw))
    return loop, fleet


# ---------------------------------------------------------------------------
# Update plane: bit-identical counters
# ---------------------------------------------------------------------------


def _ragged_um_inputs(seed=0, n_frags=3, mitigation=False):
    """Heterogeneous um fleet: per-(fragment, level) virtual param rows
    + a folded CSR packet stream."""
    rng = np.random.RandomState(seed)
    widths = [64, 300, 128][:n_frags]
    nsubs = [2, 8, 1][:n_frags]
    lens = [700, 3, 257][:n_frags]
    level_seed = 7777
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    p = int(offsets[-1])
    pkt = FleetPacket(
        keys=rng.randint(0, 900, p).astype(np.uint32),
        values=np.ones(p, np.int64),
        ts=rng.randint(0, 1 << LOG2_TE, p).astype(np.int64),
        offsets=offsets, frag_order=tuple(range(n_frags)),
        single_hop=rng.rand(p) < 0.5 if mitigation else None)
    folded = fold_packet_flags(pkt, LOG2_TE, n_levels=N_LEVELS,
                               level_seed=level_seed, mitigation=mitigation)
    params = np.zeros((n_frags * N_LEVELS, FK.N_PARAMS), np.int32)
    for f in range(n_frags):
        for l in range(N_LEVELS):
            r = f * N_LEVELS + l
            params[r, FK.PARAM_COL_SEED] = level_seed_mix(11 + f, l)
            params[r, FK.PARAM_SIGN_SEED] = level_seed_mix(22 + f, l)
            params[r, FK.PARAM_SUB_SEED] = 33 + f
            params[r, FK.PARAM_WIDTH] = widths[f]
            params[r, FK.PARAM_N_SUB] = nsubs[f]
            params[r, FK.PARAM_LOG2_N_SUB] = nsubs[f].bit_length() - 1
            params[r, FK.PARAM_LEVEL] = l
            params[r, FK.PARAM_MIT] = int(mitigation)
    return pkt, folded, params, widths, nsubs


@pytest.mark.parametrize("mitigation", [False, True])
def test_ragged_um_kernel_matches_loop_oracle(mitigation):
    """Virtual level rows in one ragged dispatch == one sketch_update
    per (fragment, level), bit for bit, with the packet stream packed
    once per fragment."""
    pkt, folded, params, widths, nsubs = _ragged_um_inputs(
        mitigation=mitigation)
    blk = 128
    kw = dict(n_sub_max=8, width_max=300, log2_te=LOG2_TE, signed=True)
    fkeys, fvals, fts, block_frag = pack_csr([folded], blk)
    out = np.asarray(FK.fleet_update_ragged(
        fkeys, fvals, fts, params, block_frag, blk=blk, w_blk=512,
        n_levels=N_LEVELS, with_mitigation=mitigation, interpret=True,
        **kw))
    # per-row oracle re-reads the same folded packet rows
    dense_keys = np.zeros((3, 700), np.uint32)
    dense_vals = np.zeros((3, 700), np.float32)
    dense_ts = np.zeros((3, 700), np.uint32)
    for f in range(3):
        lo, hi = int(folded.offsets[f]), int(folded.offsets[f + 1])
        dense_keys[f, :hi - lo] = folded.keys[lo:hi]
        dense_vals[f, :hi - lo] = folded.values[lo:hi]
        dense_ts[f, :hi - lo] = folded.ts[lo:hi]
    out_loop = FK.fleet_update_loop(dense_keys, dense_vals, dense_ts,
                                    params, backend="ref", **kw)
    np.testing.assert_array_equal(out, out_loop)
    # stacked layout contract per virtual row
    for f in range(3):
        for l in range(N_LEVELS):
            r = f * N_LEVELS + l
            assert not out[r, nsubs[f]:, :].any()
            assert not out[r, :, widths[f]:].any()
    # levels actually thin out: higher levels see subsets of level 0
    mass = np.abs(out).reshape(3, N_LEVELS, 8, 300).sum(axis=(2, 3))
    assert (mass[:, 1:] <= mass[:, :-1] + 1e-9).all()


def test_fold_packet_flags_preserves_subepoch_bits():
    """Folding masks ts to log2_te bits and packs level/single-hop into
    the documented fields; cs/cms fleets (no levels, no mitigation) get
    the identical packet object back."""
    pkt, folded, _, _, _ = _ragged_um_inputs(mitigation=True)
    assert fold_packet_flags(pkt, LOG2_TE) is pkt
    te_mask = (1 << LOG2_TE) - 1
    np.testing.assert_array_equal(np.asarray(folded.ts) & te_mask,
                                  np.asarray(pkt.ts) & te_mask)
    lvl = (np.asarray(folded.ts) >> LVL_SHIFT) & 0x1F
    assert lvl.max() < N_LEVELS
    sh = (np.asarray(folded.ts) >> SH_SHIFT) & 1
    np.testing.assert_array_equal(sh.astype(bool), pkt.single_hop)


@pytest.mark.parametrize("mitigation", [False, True])
def test_um_fleet_system_identical_to_loop(mitigation):
    """Acceptance: DiSketchSystem(kind='um', mitigation=..., backend=
    'fleet') — counters bit-identical to the loop backend per level,
    identical PEBs/ns trajectory, identical queries on both merges."""
    wl, rep, mems = _small_workload()
    loop, fleet = _systems(mems, wl, mitigation=mitigation)
    rep.run(loop)
    rep.run(fleet)
    assert loop.ns == fleet.ns and loop.n_log == fleet.n_log
    for e in range(wl.n_epochs):
        for sw in mems:
            a, b = loop.records[e][sw], fleet.records[e][sw]
            assert b.counters.shape == (N_LEVELS, a.n, a.width)
            np.testing.assert_array_equal(a.counters, b.counters)
            assert loop.peb_log[e][sw] == pytest.approx(
                fleet.peb_log[e][sw], rel=1e-12)
    keys = wl.keys[:50]
    paths = [tuple(range(5))] * len(keys)
    epochs = list(range(wl.n_epochs))
    for merge in ("subepoch", "fragment"):
        np.testing.assert_allclose(
            loop.query_flows(keys, paths, epochs, merge=merge),
            fleet.query_flows(keys, paths, epochs, merge=merge))


def test_mitigation_changes_single_hop_counters():
    """Sanity: the §4.4 mask actually fires on the fleet — a single-hop
    stream under n>=2 produces different counters with mitigation on."""
    rng = np.random.RandomState(3)
    k = rng.randint(0, 50, 400).astype(np.uint32)
    st = {0: SwitchStream(k, np.ones(400, np.int64),
                          rng.randint(0, 1 << LOG2_TE, 400).astype(np.int64),
                          single_hop=np.ones(400, bool))}
    outs = {}
    for mit in (False, True):
        sysf = DiSketchSystem({0: 64 * 1024}, "cs", rho_target=1e-9,
                              log2_te=LOG2_TE, mitigation=mit,
                              backend="fleet", fleet_kwargs=FLEET_KW)
        sysf.run_epoch(0, st)       # n=1: identical (no second subepoch)
        sysf.run_epoch(1, st)       # control doubled n: mask differs
        assert sysf.ns[0] >= 2
        outs[mit] = sysf.records[1][0].counters
    assert not np.array_equal(outs[False], outs[True])


def test_cs_mitigation_fleet_identical_to_loop():
    """Mitigation is kind-agnostic: plain Count-Sketch fragments with
    §4.4 enabled also match the loop bit for bit."""
    wl, rep, mems = _small_workload(mem_scale=1)
    loop = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te,
                          mitigation=True)
    fleet = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te,
                           mitigation=True, backend="fleet",
                           fleet_kwargs=FLEET_KW)
    rep.run(loop)
    rep.run(fleet)
    assert loop.ns == fleet.ns
    for e in range(wl.n_epochs):
        for sw in mems:
            np.testing.assert_array_equal(loop.records[e][sw].counters,
                                          fleet.records[e][sw].counters)
    # queries agree too, including the single-hop second-record average
    keys = wl.keys[:40]
    sh_paths = [(2,)] * len(keys)
    epochs = list(range(wl.n_epochs))
    for merge in ("subepoch", "fragment"):
        np.testing.assert_allclose(
            loop.query_flows(keys, sh_paths, epochs, merge=merge),
            fleet.query_flows(keys, sh_paths, epochs, merge=merge))


def test_um_window_identical_to_per_epoch_at_fixed_ns():
    """Window super-dispatch with um virtual rows: frozen ns (rho=inf
    keeps n=1 everywhere) makes the 4-epoch window bit-identical to
    four per-epoch dispatches."""
    wl, rep, mems = _small_workload()
    a = DiSketchSystem(mems, "um", rho_target=float("inf"),
                       log2_te=wl.log2_te, n_levels=N_LEVELS,
                       backend="fleet", fleet_kwargs=FLEET_KW)
    b = DiSketchSystem(mems, "um", rho_target=float("inf"),
                       log2_te=wl.log2_te, n_levels=N_LEVELS,
                       backend="fleet", fleet_kwargs=FLEET_KW)
    rep.run(a)
    rep.run(b, window=4)
    for e in range(wl.n_epochs):
        for sw in mems:
            np.testing.assert_array_equal(a.records[e][sw].counters,
                                          b.records[e][sw].counters)


# ---------------------------------------------------------------------------
# Query plane: device UnivMon level queries
# ---------------------------------------------------------------------------


def _windowed_um(wl, rep, mems, window=4):
    sysw = DiSketchSystem(mems, "um", rho_target=4.0, log2_te=wl.log2_te,
                          n_levels=N_LEVELS, backend="fleet",
                          fleet_kwargs=FLEET_KW)
    rep.run(sysw, window=window)
    return sysw


@pytest.mark.parametrize("path", [None, (2,), (1, 3)])
def test_um_device_level_query_matches_host_oracle(path):
    """Device all-levels gather/merge == per-level numpy oracle on the
    host copy of the same stacks, heterogeneous widths/n_sub, path
    restriction on/off — and the stack never transfers."""
    wl, rep, mems = _small_workload()
    sysw = _windowed_um(wl, rep, mems)
    keys = wl.keys[:65]
    epochs = list(range(wl.n_epochs))
    got = sysw.fleet.um_level_window_query(epochs, keys, path=path)
    assert got.shape == (N_LEVELS, len(keys))

    buf = sysw.fleet._window_bufs[0][0]
    assert buf._host is None and buf.resident   # no bulk transfer

    ref = np.zeros_like(got)                    # the oracle forces it
    for level in range(N_LEVELS):
        ref[level] = Q.fleet_query_window(
            [buf.host_epoch(e) for e in epochs],
            [sysw.fleet._params_log[e] for e in epochs],
            sysw.fleet.row_widths, keys, "um",
            frag_sel=sysw.fleet._row_sel(path, level))
    np.testing.assert_allclose(got, ref, rtol=RTOL)


def test_um_query_flows_routes_device():
    """Acceptance: query_flows(merge='fragment') on a um window answers
    from the device plane (level-0 rows) with no counter-stack transfer,
    matching the per-record fallback after materialization."""
    wl, rep, mems = _small_workload()
    sysw = _windowed_um(wl, rep, mems)
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


def test_um_entropy_device_matches_host_fragment_merge():
    """query_entropy(merge='fragment'): the device path (batched
    all-levels query + jitted top-down G-sum combine) matches the
    per-record host estimator, and never transfers the stack."""
    wl, rep, mems = _small_workload()
    a = _windowed_um(wl, rep, mems)
    b = _windowed_um(wl, rep, mems)
    epochs = list(range(wl.n_epochs))
    total = float(wl.sizes.sum())
    ent_dev = a.query_entropy(wl.keys, wl.paths, epochs, total,
                              n_levels=N_LEVELS, merge="fragment")
    assert a.fleet._window_bufs[0][0]._host is None
    for e in epochs:
        b.records[e][0]                     # force the host/record path
    assert not b.fleet.has_device_window(epochs)
    ent_host = b.query_entropy(wl.keys, wl.paths, epochs, total,
                               n_levels=N_LEVELS, merge="fragment")
    assert ent_dev == pytest.approx(ent_host, rel=1e-4)


def test_um_gsum_device_matches_host_combine():
    """Unit: the jitted top-down Y-recursion == the numpy combine on a
    synthetic estimate matrix (k_heavy >= K, so top-k ties cannot pick
    different key subsets)."""
    from repro.kernels.sketch_query import um_gsum_device

    rng = np.random.RandomState(11)
    n_levels, n_keys = 6, 200
    lvl = rng.randint(0, n_levels, n_keys)
    ests = np.zeros((n_levels, n_keys))
    for l in range(n_levels):
        m = lvl >= l
        ests[l, m] = rng.randint(1, 5000, int(m.sum()))

    def g(x):
        import jax.numpy as jnp
        return x * jnp.log2(jnp.maximum(x, 1.0))

    got = um_gsum_device(ests, lvl, g, k_heavy=1024)
    ref = Q.um_gsum_combine(ests, lvl,
                            lambda x: x * np.log2(np.maximum(x, 1.0)),
                            k_heavy=1024)
    assert got == pytest.approx(ref, rel=1e-5)


def test_mitigated_window_query_matches_records():
    """Device window query with single_hop=True applies the §4.4 average
    exactly like the per-record fragment merge."""
    wl, rep, mems = _small_workload(mem_scale=1)
    a = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te,
                       mitigation=True, backend="fleet",
                       fleet_kwargs=FLEET_KW)
    rep.run(a, window=4)
    keys = wl.keys[:32]
    epochs = list(range(wl.n_epochs))
    path = (2,)                             # single-hop path group
    got = a.fleet.window_query(epochs, keys, path=path, single_hop=True)
    assert a.fleet._window_bufs[0][0]._host is None
    recs = [[a.records[e][2]] for e in epochs]
    ref = Q.query_window(recs, keys, "cms",
                         single_hop=np.ones(len(keys), bool),
                         merge="fragment")
    np.testing.assert_allclose(got, ref, rtol=RTOL)


def test_um_build_params_level_rows():
    """Param-table contract: n_levels virtual rows per fragment with
    level-mixed col/sign seeds, shared sub seed, PARAM_LEVEL/PARAM_MIT
    filled."""
    frags = {7: FragmentConfig(frag_id=7, kind="um", memory_bytes=4096,
                               n_levels=N_LEVELS, mitigation=True)}
    params = build_params(frags, epoch=2, ns={7: 4}, frag_order=(7,))
    assert params.shape == (N_LEVELS, FK.N_PARAMS)
    rec = process_epoch(frags[7], 2, 4, np.zeros(0, np.uint32),
                        np.zeros(0, np.int64), np.zeros(0, np.int64),
                        2 << LOG2_TE, LOG2_TE)
    col, sgn, sub = rec.seeds()
    for l in range(N_LEVELS):
        assert params[l, FK.PARAM_COL_SEED] == level_seed_mix(col, l)
        assert params[l, FK.PARAM_SIGN_SEED] == level_seed_mix(sgn, l)
        assert params[l, FK.PARAM_SUB_SEED] == sub
        assert params[l, FK.PARAM_LEVEL] == l
        assert params[l, FK.PARAM_MIT] == 1
