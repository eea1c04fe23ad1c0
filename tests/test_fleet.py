"""Fleet engine tests: the batched (one-dispatch-per-epoch) path must be
bit-identical to the per-switch loop — kernel level, system level, PEB
control loop, and the batched query-side op.  The ragged CSR layout (the
default) must additionally be bit-identical to the PR-1 dense rectangle
on heterogeneous widths/n_sub and ragged segment lengths."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import equalize, query as Q
from repro.core.disketch import DiSketchSystem, DiscoSystem, SwitchStream
from repro.core.fleet import (FleetEpochRunner, FleetPacket, pack_csr)
from repro.core.fragment import FragmentConfig
from repro.kernels.sketch_update import fleet as FK
from repro.net.simulator import Replayer
from repro.net.traffic import cov_list, linear_path_workload

LOG2_TE = 12


def _fleet_inputs(n_frags, p, seed=0, widths=None, nsubs=None):
    rng = np.random.RandomState(seed)
    keys = rng.randint(0, 900, (n_frags, p)).astype(np.uint32)
    vals = np.ones((n_frags, p), np.float32)
    for f in range(n_frags):          # ragged streams: zero-value padding
        vals[f, rng.randint(p // 2, p):] = 0.0
    ts = rng.randint(0, 1 << LOG2_TE, (n_frags, p)).astype(np.uint32)
    widths = widths or [128, 300, 512, 64, 1000][:n_frags]
    nsubs = nsubs or [1, 2, 8, 4, 16][:n_frags]
    params = np.zeros((n_frags, FK.N_PARAMS), np.int32)
    for f in range(n_frags):
        params[f, FK.PARAM_COL_SEED] = 11 + f
        params[f, FK.PARAM_SIGN_SEED] = 22 + f
        params[f, FK.PARAM_SUB_SEED] = 33 + f
        params[f, FK.PARAM_WIDTH] = widths[f]
        params[f, FK.PARAM_N_SUB] = nsubs[f]
        params[f, FK.PARAM_LOG2_N_SUB] = nsubs[f].bit_length() - 1
    return keys, vals, ts, params, widths, nsubs


@pytest.mark.parametrize("signed", [True, False])
def test_fleet_kernel_matches_loop_oracle(signed):
    """Heterogeneous widths/subepoch counts in one dispatch == one
    sketch_update per fragment."""
    keys, vals, ts, params, widths, nsubs = _fleet_inputs(5, 700)
    kw = dict(n_sub_max=16, width_max=1000, log2_te=LOG2_TE, signed=signed)
    out_fleet = np.asarray(FK.fleet_update(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ts),
        jnp.asarray(params), blk=256, w_blk=512, interpret=True, **kw))
    out_loop = FK.fleet_update_loop(keys, vals, ts, params,
                                    backend="ref", **kw)
    np.testing.assert_array_equal(out_fleet, out_loop)
    # stacked layout contract: exact zeros outside each live block
    for f in range(5):
        assert not out_fleet[f, nsubs[f]:, :].any()
        assert not out_fleet[f, :, widths[f]:].any()


def _small_workload(n_hops=5, seed=1, n_epochs=4):
    rng = np.random.RandomState(seed)
    widths = np.maximum(cov_list(n_hops, 1280, 1.2, rng).astype(int), 4)
    mems = {h: int(w) * 4 for h, w in enumerate(widths)}
    loads = np.maximum(cov_list(n_hops, 30_000, 0.9, rng).astype(int), 16)
    wl = linear_path_workload(n_hops, eval_flows=100, eval_packets=800,
                              bg_packets_per_hop=loads, n_epochs=n_epochs,
                              seed=seed)
    return wl, Replayer(wl, n_hops), mems


FLEET_KW = dict(blk=256, w_blk=512)


@pytest.mark.parametrize("kind", ["cs", "cms"])
def test_fleet_backend_identical_to_loop(kind):
    """Full system on a multi-switch workload: counters, PEBs, the
    equalization trajectory, and window queries all match exactly."""
    wl, rep, mems = _small_workload()
    loop = DiSketchSystem(mems, kind, rho_target=4.0, log2_te=wl.log2_te)
    fleet = DiSketchSystem(mems, kind, rho_target=4.0, log2_te=wl.log2_te,
                           backend="fleet", fleet_kwargs=FLEET_KW)
    rep.run(loop)
    rep.run(fleet)
    assert loop.ns == fleet.ns
    assert loop.n_log == fleet.n_log
    for e in range(wl.n_epochs):
        for sw in mems:
            np.testing.assert_array_equal(loop.records[e][sw].counters,
                                          fleet.records[e][sw].counters)
        for sw in mems:
            assert loop.peb_log[e][sw] == pytest.approx(
                fleet.peb_log[e][sw], rel=1e-12)
    keys = wl.keys[:50]
    paths = [tuple(range(5))] * len(keys)
    epochs = list(range(wl.n_epochs))
    np.testing.assert_allclose(loop.query_flows(keys, paths, epochs),
                               fleet.query_flows(keys, paths, epochs))


def test_fleet_backend_disco():
    """DISCO (no subepoching) also runs on the fleet engine: n stays 1."""
    wl, rep, mems = _small_workload(n_epochs=2)
    loop = DiscoSystem(mems, "cs", rho_target=0, log2_te=wl.log2_te)
    fleet = DiscoSystem(mems, "cs", rho_target=0, log2_te=wl.log2_te,
                        backend="fleet", fleet_kwargs=FLEET_KW)
    rep.run(loop)
    rep.run(fleet)
    assert all(n == 1 for n in fleet.ns.values())
    for sw in mems:
        np.testing.assert_array_equal(loop.records[1][sw].counters,
                                      fleet.records[1][sw].counters)


def test_fleet_point_query_matches_fragment_merge():
    """The batched query-side op over stacked counters == the per-record
    merge='fragment' composite query (min for CMS, median for CS)."""
    wl, rep, mems = _small_workload()
    for kind in ("cs", "cms"):
        sysf = DiSketchSystem(mems, kind, rho_target=4.0,
                              log2_te=wl.log2_te, backend="fleet",
                              fleet_kwargs=dict(keep_stacked=True,
                                                **FLEET_KW))
        rep.run(sysf)
        keys = wl.keys[:64]
        recs = [sysf.records[1][sw] for sw in sorted(mems)]
        ref = Q.query_epoch(recs, keys, kind, merge="fragment")
        np.testing.assert_allclose(sysf.fleet.point_query(1, keys), ref)


def test_fleet_point_query_path_restriction():
    """frag_sel / path= merges only on-path fragments: off-path fragments
    would bias the min/median toward their near-zero collision values."""
    wl, rep, mems = _small_workload()
    sysf = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te,
                          backend="fleet",
                          fleet_kwargs=dict(keep_stacked=True, **FLEET_KW))
    rep.run(sysf)
    # background flows cross only switch 2; query them on their true path
    keys = wl.keys[:32]
    path = (2,)
    got = sysf.fleet.point_query(1, keys, path=path)
    ref = Q.query_epoch([sysf.records[1][2]], keys, "cms",
                        merge="fragment")
    np.testing.assert_allclose(got, ref)
    # unrestricted merge over all 5 fragments must differ (off-path min)
    allfrag = sysf.fleet.point_query(1, keys)
    assert (allfrag <= got + 1e-9).all()


def test_fleet_overflow_guard():
    """f32 counters are exact only below 2^24; the fleet must refuse to
    return silently-corrupt counters instead of diverging from the loop."""
    from repro.core.disketch import SwitchStream

    k = np.full(8, 5, np.uint32)
    st = SwitchStream(k, np.full(8, 1 << 23, np.int64),
                      np.zeros(8, np.int64))
    # cms: output-side check (counters are monotone non-negative)
    sysf = DiSketchSystem({0: 1024}, "cms", rho_target=1e18,
                          log2_te=LOG2_TE, backend="fleet",
                          fleet_kwargs=FLEET_KW)
    with pytest.raises(OverflowError, match="2\\^24"):
        sysf.run_epoch(0, {0: st})
    # cs: input-side |value|-mass bound (sign cancellation could hide an
    # inexact intermediate peak from the output check)
    syss = DiSketchSystem({0: 1024}, "cs", rho_target=1e18,
                          log2_te=LOG2_TE, backend="fleet",
                          fleet_kwargs=FLEET_KW)
    with pytest.raises(OverflowError, match="mass"):
        syss.run_epoch(0, {0: st})


def test_peb_fleet_matches_peb_epoch():
    keys, vals, ts, params, widths, nsubs = _fleet_inputs(5, 700, seed=3)
    stacked = FK.fleet_update_loop(keys, vals, ts, params, n_sub_max=16,
                                   width_max=1000, log2_te=LOG2_TE,
                                   signed=True).astype(np.int64)
    ns = params[:, FK.PARAM_N_SUB].astype(np.int64)
    got = equalize.peb_fleet(stacked, ns, np.asarray(widths, np.int64),
                             "cs")
    from repro.core.fragment import EpochRecords
    for f in range(5):
        rec = EpochRecords(f, 0, int(ns[f]),
                           stacked[f, :nsubs[f], :widths[f]], "cs", False)
        assert got[f] == pytest.approx(equalize.peb_epoch(rec), rel=1e-12)


def test_pack_streams_roundtrip():
    wl, rep, _ = _small_workload(n_epochs=2)
    streams = rep.epoch_stream(0)
    pkt = rep.epoch_packet(0)
    assert pkt is rep.epoch_packet(0)  # cached
    assert pkt.offsets[0] == 0 and pkt.offsets[-1] == len(pkt.keys)
    for i, sw in enumerate(pkt.frag_order):
        lo, hi = int(pkt.offsets[i]), int(pkt.offsets[i + 1])
        st = streams.get(sw)
        if st is None:
            assert lo == hi
        else:
            np.testing.assert_array_equal(pkt.keys[lo:hi], st.keys)
            np.testing.assert_array_equal(pkt.ts[lo:hi], st.ts)
    keys2d, vals2d, ts2d = pkt.densify(blk=256)
    assert keys2d.shape[1] % 256 == 0
    lens = pkt.seg_lengths()
    for i in range(len(pkt.frag_order)):
        assert not vals2d[i, int(lens[i]):].any()  # zero-value padding


def _ragged_packet(lens, seed=0, max_key=900):
    """A FleetPacket with the given heterogeneous segment lengths."""
    rng = np.random.RandomState(seed)
    p = int(sum(lens))
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    return FleetPacket(
        keys=rng.randint(0, max_key, p).astype(np.uint32),
        values=np.ones(p, np.int64),
        ts=rng.randint(0, 1 << LOG2_TE, p).astype(np.int64),
        offsets=offsets, frag_order=tuple(range(len(lens))))


@pytest.mark.parametrize("signed", [True, False])
def test_ragged_kernel_matches_dense_and_loop(signed):
    """CSR layout == dense rectangle == per-fragment oracle, bit for bit,
    on heterogeneous widths/n_sub and ragged segments (including a
    zero-length one and a hot fragment spanning many blocks)."""
    _, _, _, params, widths, nsubs = _fleet_inputs(5, 700)
    pkt = _ragged_packet([700, 3, 0, 130, 257], seed=4)
    blk = 128
    kw = dict(n_sub_max=16, width_max=1000, log2_te=LOG2_TE, signed=signed)
    fkeys, fvals, fts, block_frag = pack_csr([pkt], blk)
    out_ragged = np.asarray(FK.fleet_update_ragged(
        jnp.asarray(fkeys), jnp.asarray(fvals), jnp.asarray(fts),
        jnp.asarray(params), jnp.asarray(block_frag), blk=blk, w_blk=512,
        interpret=True, **kw))
    dkeys, dvals, dts = pkt.densify(blk)
    out_dense = np.asarray(FK.fleet_update(
        jnp.asarray(dkeys), jnp.asarray(dvals), jnp.asarray(dts),
        jnp.asarray(params), blk=blk, w_blk=512, interpret=True, **kw))
    out_loop = FK.fleet_update_loop(dkeys, dvals, dts, params,
                                    backend="ref", **kw)
    np.testing.assert_array_equal(out_ragged, out_dense)
    np.testing.assert_array_equal(out_ragged, out_loop)
    # stacked layout contract survives the ragged path
    for f in range(5):
        assert not out_ragged[f, nsubs[f]:, :].any()
        assert not out_ragged[f, :, widths[f]:].any()


@pytest.mark.parametrize("signed", [True, False])
def test_ragged_default_geometry_matches_dense(signed):
    """Auto-selected geometry (w_blk=None -> kernel.select_geometry) and
    every value mode stay bit-identical to the dense rectangle and the
    loop oracle on heterogeneous widths/n_sub."""
    _, _, _, params, widths, nsubs = _fleet_inputs(5, 700)
    pkt = _ragged_packet([700, 3, 0, 130, 257], seed=4)
    blk = 128
    kw = dict(n_sub_max=16, width_max=1000, log2_te=LOG2_TE, signed=signed)
    fkeys, fvals, fts, block_frag = pack_csr([pkt], blk)
    dkeys, dvals, dts = pkt.densify(blk)
    out_loop = FK.fleet_update_loop(dkeys, dvals, dts, params,
                                    backend="ref", **kw)
    for mode in ("f32", "count", "limb"):
        out_ragged = np.asarray(FK.fleet_update_ragged(
            jnp.asarray(fkeys), jnp.asarray(fvals), jnp.asarray(fts),
            jnp.asarray(params), jnp.asarray(block_frag), blk=blk,
            value_mode=mode, interpret=True, **kw))
        np.testing.assert_array_equal(out_ragged, out_loop,
                                      err_msg=f"mode={mode}")
        out_dense = np.asarray(FK.fleet_update(
            jnp.asarray(dkeys), jnp.asarray(dvals), jnp.asarray(dts),
            jnp.asarray(params), blk=blk, value_mode=mode, interpret=True,
            **kw))
        np.testing.assert_array_equal(out_dense, out_loop,
                                      err_msg=f"mode={mode}")


def test_grouped_dispatch_matches_single_launch():
    """dispatch_ragged_grouped (the production path: one launch per
    distinct n_sub, zero subepoch-row padding, each group at its own
    extent in the window buffer's layout) is bit-identical to the
    single-launch ragged rectangle — per epoch and across a frozen-ns
    window — and drives the same system trajectory as the loop."""
    from repro.core.fleet import dispatch_ragged_grouped

    _, _, _, params, widths, nsubs = _fleet_inputs(5, 700)
    blk = 128
    kw = dict(log2_te=LOG2_TE, signed=True, interpret=True)
    pkts = [_ragged_packet([700, 3, 0, 130, 257], seed=4),
            _ragged_packet([31, 257, 700, 0, 65], seed=7)]
    # window: rows are (epoch, fragment) pairs with per-epoch seeds
    params_w = np.concatenate([params, params + np.array(
        [[7, 7, 7, 0, 0, 0, 0, 0]], np.int32)])
    fkeys, fvals, fts, block_frag = pack_csr(pkts, blk)
    single = np.asarray(FK.fleet_update_ragged(
        jnp.asarray(fkeys), jnp.asarray(fvals), jnp.asarray(fts),
        jnp.asarray(params_w), jnp.asarray(block_frag), blk=blk,
        n_sub_max=16, width_max=1000, **kw))
    outs, layout, _, _ = dispatch_ragged_grouped(params_w, pkts, blk=blk,
                                                 **kw)
    assert [o.shape for o in outs] == [
        (2 * len(g.frags), g.n, g.width) for g in layout.groups]
    flat = np.concatenate([np.asarray(o).ravel() for o in outs])
    assert len(flat) == layout.size
    for e in range(2):
        for f in range(5):
            n, w = nsubs[f], widths[f]
            start, shape = layout.cell(e, f, f + 1)
            cell = flat[start:start + np.prod(shape)].reshape(shape)[0]
            np.testing.assert_array_equal(cell[:, :w],
                                          single[e * 5 + f, :n, :w])
            assert not single[e * 5 + f, n:].any()
            assert not cell[:, w:].any()
    # runner-level: the grouped fleet drives the loop's trajectory
    wl, rep, mems = _small_workload(n_epochs=2)
    a = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te,
                       backend="fleet", fleet_kwargs=FLEET_KW)
    b = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te)
    rep.run(a)
    rep.run(b)
    assert a.ns == b.ns
    for e in range(wl.n_epochs):
        for sw in mems:
            np.testing.assert_array_equal(a.records[e][sw].counters,
                                          b.records[e][sw].counters)


def test_pack_csr_layout():
    """CSR contract: blk-aligned segments, >= 1 block per row (empty rows
    included), a non-decreasing block->row map covering every row, and
    value-0 padding only."""
    blk = 64
    lens = [700, 3, 0, 130, 257]
    pkt = _ragged_packet(lens, seed=5)
    keys, vals, ts, block_frag = pack_csr([pkt], blk)
    assert keys.shape == vals.shape == ts.shape
    assert keys.size == block_frag.size * blk
    assert (np.diff(block_frag) >= 0).all()
    counts = np.bincount(block_frag, minlength=len(lens))
    assert (counts >= 1).all()                       # empty row owns a block
    nblk = np.maximum(1, -(-np.asarray(lens) // blk))
    # per-row waste <= blk (modulo the trailing shape bucket on the last row)
    np.testing.assert_array_equal(counts[:-1], nblk[:-1])
    # every live packet lands in its row's span, padding carries value 0
    row_off = np.concatenate([[0], np.cumsum(counts)]) * blk
    for f, n in enumerate(lens):
        seg = vals[row_off[f]:row_off[f + 1]]
        assert seg[:n].sum() == n and not seg[n:].any()
    # window packing: rows are epoch-major (e * n_frags + f)
    _, _, _, bf2 = pack_csr([pkt, pkt], blk)
    assert bf2.max() == 2 * len(lens) - 1
    np.testing.assert_array_equal(
        np.bincount(bf2, minlength=2 * len(lens))[len(lens):-1],
        nblk[:-1])


def test_fleet_all_empty_epoch():
    """An epoch with no packets anywhere still produces (zero) records,
    PEBs, and a control step identical to the loop backend."""
    mems = {0: 512, 1: 1024, 2: 2048}
    loop = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=LOG2_TE)
    fleet = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=LOG2_TE,
                           backend="fleet", fleet_kwargs=FLEET_KW)
    loop.run_epoch(0, {})
    fleet.run_epoch(0, {})
    assert loop.ns == fleet.ns
    for sw in mems:
        np.testing.assert_array_equal(loop.records[0][sw].counters,
                                      fleet.records[0][sw].counters)
        assert not fleet.records[0][sw].counters.any()
        assert fleet.peb_log[0][sw] == loop.peb_log[0][sw] == 0.0


def test_fleet_zero_length_segment():
    """A switch with no packets this epoch (zero-length CSR segment)
    matches the loop backend exactly alongside busy neighbours."""
    rng = np.random.RandomState(9)
    mems = {0: 512, 1: 1024, 2: 768}
    st = SwitchStream(rng.randint(0, 500, 300).astype(np.uint32),
                      np.ones(300, np.int64),
                      rng.randint(0, 1 << LOG2_TE, 300).astype(np.int64))
    streams = {0: st, 2: SwitchStream(st.keys[:7], st.values[:7],
                                      st.ts[:7])}  # switch 1 idle
    loop = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=LOG2_TE)
    fleet = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=LOG2_TE,
                           backend="fleet", fleet_kwargs=FLEET_KW)
    loop.run_epoch(0, streams)
    fleet.run_epoch(0, streams)
    for sw in mems:
        np.testing.assert_array_equal(loop.records[0][sw].counters,
                                      fleet.records[0][sw].counters)
    assert not fleet.records[0][1].counters.any()


def test_fleet_prepacked_equals_streams():
    """run_epoch(packet=prepacked) is identical to run_epoch(streams)."""
    wl, rep, mems = _small_workload(n_epochs=2)
    a = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te,
                       backend="fleet", fleet_kwargs=FLEET_KW)
    b = DiSketchSystem(mems, "cms", rho_target=4.0, log2_te=wl.log2_te,
                       backend="fleet", fleet_kwargs=FLEET_KW)
    a.run_epoch(0, rep.epoch_stream(0))
    b.run_epoch(0, {}, packet=rep.epoch_packet(0, b.fleet.frag_order))
    assert a.ns == b.ns
    for sw in mems:
        np.testing.assert_array_equal(a.records[0][sw].counters,
                                      b.records[0][sw].counters)


def test_dense_layout_identical_to_ragged():
    """layout='dense' (the PR-1 rectangle, kept as oracle) and the
    default ragged CSR layout drive the same system trajectory."""
    wl, rep, mems = _small_workload(n_epochs=3)
    ragged = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te,
                            backend="fleet", fleet_kwargs=FLEET_KW)
    dense = DiSketchSystem(mems, "cs", rho_target=4.0, log2_te=wl.log2_te,
                           backend="fleet",
                           fleet_kwargs=dict(layout="dense", **FLEET_KW))
    rep.run(ragged)
    rep.run(dense)
    assert ragged.n_log == dense.n_log
    for e in range(wl.n_epochs):
        for sw in mems:
            np.testing.assert_array_equal(ragged.records[e][sw].counters,
                                          dense.records[e][sw].counters)


def test_replayer_packet_cache_lru():
    """The packed-epoch cache is a bounded LRU: recent epochs are reused,
    old ones are evicted, long replays don't accumulate every epoch."""
    wl, _, mems = _small_workload(n_epochs=4)
    rep = Replayer(wl, 5, packet_cache=2)
    p0 = rep.epoch_packet(0)
    assert rep.epoch_packet(0) is p0          # hit
    rep.epoch_packet(1)
    assert rep.epoch_packet(0) is p0          # still resident, now MRU
    rep.epoch_packet(2)                       # evicts epoch 1
    rep.epoch_packet(3)                       # evicts epoch 0
    assert len(rep._packets) == 2
    assert rep.epoch_packet(0) is not p0      # rebuilt after eviction


def test_fleet_rejects_unsupported_configs():
    # um and §4.4 mitigation are fleet-supported since PR 5: both
    # construct cleanly (parity suite: tests/test_univmon_fleet.py)
    frags = {0: FragmentConfig(frag_id=0, kind="um", memory_bytes=1024,
                               mitigation=True)}
    assert FleetEpochRunner(frags, log2_te=LOG2_TE).n_levels == 16
    mixed = {0: FragmentConfig(frag_id=0, kind="cs", memory_bytes=1024),
             1: FragmentConfig(frag_id=1, kind="cms", memory_bytes=1024)}
    with pytest.raises(ValueError, match="homogeneous"):
        FleetEpochRunner(mixed, log2_te=LOG2_TE)
    hetero = {0: FragmentConfig(frag_id=0, kind="um", memory_bytes=1024,
                                n_levels=8),
              1: FragmentConfig(frag_id=1, kind="um", memory_bytes=1024,
                                n_levels=16)}
    with pytest.raises(ValueError, match="n_levels"):
        FleetEpochRunner(hetero, log2_te=LOG2_TE)
    frags = {0: FragmentConfig(frag_id=0, kind="um", memory_bytes=1024)}
    with pytest.raises(ValueError, match="log2_te"):
        FleetEpochRunner(frags, log2_te=25)   # level id rides bits 24+
    with pytest.raises(ValueError, match="dense"):
        FleetEpochRunner(frags, log2_te=LOG2_TE, layout="dense")
    with pytest.raises(ValueError, match="backend"):
        DiSketchSystem({0: 1024}, "cs", rho_target=1.0, log2_te=LOG2_TE,
                       backend="warp")
    frags = {0: FragmentConfig(frag_id=0, kind="cs", memory_bytes=1024)}
    with pytest.raises(ValueError, match="layout"):
        FleetEpochRunner(frags, log2_te=LOG2_TE, layout="brick")
