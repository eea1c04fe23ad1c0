"""Program spans (``repro.obs``) as a profiler records them: a small fleet
window replay and one device window query traced on the CPU, read back
from the ``.xplane.pb`` the profiler wrote."""
import glob
import warnings

import numpy as np
import pytest

from repro.core.disketch import DiSketchSystem
from repro.kernels.sketch_update.fleet import PARAM_N_SUB
from repro.net.simulator import Replayer
from repro.net.traffic import cov_list, linear_path_workload

N_HOPS, N_EPOCHS, WINDOW = 5, 4, 2
FLEET_KW = dict(blk=256, w_blk=512)

#: span -> the span it runs under (None: outside every program span)
PARENT = {
    "repro.system.init": None,
    "repro.replay.epoch_packet": None,
    "repro.system.run_window": None,
    "repro.fleet.run_window": "repro.system.run_window",
    "repro.fleet.prepare": "repro.fleet.run_window",
    "repro.fleet.shard": "repro.fleet.run_window",
    "repro.fleet.select": "repro.fleet.shard",
    "repro.fleet.pack_csr": "repro.fleet.shard",
    "repro.fleet.launch": "repro.fleet.shard",
    "repro.fleet.sync": "repro.fleet.run_window",
    "repro.fleet.records": "repro.fleet.run_window",
    "repro.query.flows": None,
    "repro.query.prep": "repro.query.flows",
    "repro.query.launch": "repro.query.flows",
    "repro.query.sync": "repro.query.flows",
}


def _spans(logdir):
    """(start_ns, end_ns, name, stats) of every ``repro.*`` host event,
    with the innermost ``repro.*`` span around it on its thread."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            with warnings.catch_warnings():
                # jaxlib's stats type warns that it has no __module__; a
                # warning raised inside the binding aborts the process
                warnings.simplefilter("ignore", DeprecationWarning)
                evs = sorted((ev.start_ns, -(ev.start_ns + ev.duration_ns),
                              ev.name, dict(ev.stats))
                             for ev in line.events
                             if ev.name.startswith("repro."))
            stack = []
            for s, neg_e, name, stats in evs:
                while stack and stack[-1][1] <= s:
                    stack.pop()
                parent = stack[-1][2] if stack else None
                out.append(dict(start=s, end=-neg_e, name=name,
                                stats=stats, parent=parent))
                stack.append((s, -neg_e, name))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    rng = np.random.RandomState(3)
    widths = np.maximum(cov_list(N_HOPS, 1280, 1.2, rng).astype(int), 4)
    mems = {h: int(w) * 4 for h, w in enumerate(widths)}
    loads = np.maximum(cov_list(N_HOPS, 30_000, 0.9, rng).astype(int), 16)
    wl = linear_path_workload(N_HOPS, eval_flows=100, eval_packets=800,
                              bg_packets_per_hop=loads, n_epochs=N_EPOCHS,
                              seed=3)
    rep = Replayer(wl, N_HOPS)
    keys = wl.keys
    paths = [tuple(int(x) for x in r if x >= 0) for r in wl.path_mat]
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        system = DiSketchSystem(mems, "cs", rho_target=4.0,
                                log2_te=wl.log2_te, mitigation=True,
                                backend="fleet", fleet_kwargs=FLEET_KW)
        rep.run(system, window=WINDOW)
        est = system.query_flows(keys, paths, list(range(N_EPOCHS)),
                                 merge="fragment")
    order = system.fleet.frag_order
    obs_per_window = [
        sum(len(rep.epoch_packet(e, order).keys)
            for e in range(w0, w0 + WINDOW))
        for w0 in range(0, N_EPOCHS, WINDOW)]
    # each window's frozen n per fragment, and the fragments' widths
    window_ns = [system.fleet._params_log[w0][:, PARAM_N_SUB]
                 for w0 in range(0, N_EPOCHS, WINDOW)]
    return dict(spans=_spans(logdir), est=est, paths=len(set(paths)),
                n_keys=len(keys), obs_per_window=obs_per_window,
                window_ns=window_ns, widths=np.array(widths),
                fleet_bytes=(system.fleet.stack_bytes,
                             system.fleet.counter_bytes))


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(spans, outer):
    return [s for s in spans
            if outer["start"] <= s["start"] and s["end"] <= outer["end"]
            and s is not outer]


def test_every_span_present_and_nested(traced):
    spans = traced["spans"]
    assert {s["name"] for s in spans} == set(PARENT)
    for s in spans:
        assert s["parent"] == PARENT[s["name"]], s


def test_window_spans(traced):
    spans = traced["spans"]
    windows = _named(spans, "repro.system.run_window")
    assert [w["stats"] for w in windows] == [
        {"epoch0": e0, "epochs": WINDOW} for e0 in range(0, N_EPOCHS, WINDOW)]
    fleet = _named(spans, "repro.fleet.run_window")
    assert len(fleet) == len(windows)
    widths = traced["widths"]
    totals = np.zeros(2, np.int64)
    for f, want, ns in zip(fleet, traced["obs_per_window"],
                           traced["window_ns"]):
        # the buffer holds each n_sub group at its own (rows, n, width)
        # extent: under the fleet-wide rectangle, over the counters
        stack = 4 * WINDOW * sum(int((ns == n).sum()) * n
                                 * widths[ns == n].max()
                                 for n in np.unique(ns))
        counters = 4 * WINDOW * int((ns * widths).sum())
        assert counters <= stack <= 4 * WINDOW * N_HOPS * ns.max() \
            * widths.max()
        assert f["stats"] == {"epochs": WINDOW, "rows": WINDOW * N_HOPS,
                              "stack_bytes": stack,
                              "counter_bytes": counters}
        totals += (stack, counters)
        inner = _inside(spans, f)
        # one read a window: the peak and the PEBs together
        assert len(_named(inner, "repro.fleet.sync")) == 1
        assert len(_named(inner, "repro.fleet.records")) == 1
        shard, = _named(inner, "repro.fleet.shard")
        assert shard["stats"]["shard"] == 0
        assert shard["stats"]["rows"] == WINDOW * N_HOPS
        assert shard["stats"]["packets"] == want
        assert shard["stats"]["h2d_bytes"] == sum(
            s["stats"]["h2d_bytes"]
            for s in _named(inner, "repro.fleet.launch"))
        packs = _named(inner, "repro.fleet.pack_csr")
        assert packs
        for p in packs:
            st = p["stats"]
            assert st["slots"] >= st["slots_live"] >= st["packets"]
            assert st["slots"] % FLEET_KW["blk"] == 0
        # every observation of the window's epochs is packed once
        assert sum(p["stats"]["packets"] for p in packs) == want
        launches = _named(inner, "repro.fleet.launch")
        assert len(launches) >= len(packs)
        assert all(s["stats"]["h2d_bytes"] > 0 for s in launches)
    # the runner's figures: every window is still resident
    assert tuple(totals) == traced["fleet_bytes"]


def test_epoch_packet_spans(traced):
    packs = _named(traced["spans"], "repro.replay.epoch_packet")
    assert len(packs) == N_EPOCHS
    # the replay packs every epoch afresh; the test's own reads after it
    # ran outside the trace
    assert all(p["stats"]["hit"] == 0 for p in packs)
    assert sum(p["stats"]["packets"] for p in packs) == sum(
        traced["obs_per_window"])


def test_system_init_span(traced):
    init, = _named(traced["spans"], "repro.system.init")
    assert init["stats"] == {"fragments": N_HOPS}


def test_query_spans(traced):
    spans = traced["spans"]
    stacks = N_EPOCHS // WINDOW
    n_keys, n_paths = traced["n_keys"], traced["paths"]
    flows, = _named(spans, "repro.query.flows")
    # one batched launch per resident window stack (one key chunk)
    assert flows["stats"] == {"request": 1, "keys": n_keys,
                              "paths": n_paths, "device_calls": stacks,
                              "batched_keys": n_keys, "fallback_paths": 0}
    launches = _named(spans, "repro.query.launch")
    assert len(launches) == stacks
    # the estimates of every launch come back in one read
    assert len(_named(spans, "repro.query.sync")) == 1
    # window_query_paths' routing, then the engine's preparation
    assert len(_named(spans, "repro.query.prep")) == 2
    # every key is asked once of every stack
    assert [s["stats"]["keys"] for s in launches] == [n_keys] * stacks
    assert all(s["stats"]["paths"] == n_paths for s in launches)
    # resident stacks cross nothing: the first launch sends the padded
    # path table, keys and key paths; each stack its (R, 4E + 4) rows
    kb = max(8, 1 << (n_keys - 1).bit_length())
    pb = max(8, 1 << (n_paths - 1).bit_length())
    n_slots = N_HOPS                      # the longest path: every hop
    row_tab = 4 * N_HOPS * (4 * WINDOW + 4)
    assert [s["stats"]["h2d_bytes"] for s in launches] == (
        [pb * (4 * n_slots + 1) + row_tab + 2 * 4 * kb]
        + [row_tab] * (stacks - 1))
    assert np.isfinite(traced["est"]).all()


def test_request_route_counters(tmp_path):
    """A 192-path request over 4 resident stacks: one batched launch per
    stack and key chunk, every key batched; the same request on a
    churned window sends each path to its own calls."""
    import itertools
    from types import SimpleNamespace

    import jax

    from repro.core.disketch import SwitchStream
    from repro.kernels.sketch_query import KEY_CHUNK

    n_sw, n_epochs, window = 6, 8, 2

    def streams(e):
        out = {}
        for sw in range(n_sw):
            r = np.random.default_rng(100 * e + sw)
            n = 150 + 40 * sw
            out[sw] = SwitchStream(r.integers(0, 500, n).astype(np.uint32),
                                   r.integers(1, 5, n).astype(np.int64),
                                   r.integers(0, 1 << 12, n).astype(np.int64))
        return out

    def system(fail_at=None):
        s = DiSketchSystem({sw: 2048 for sw in range(n_sw)}, "cs",
                           rho_target=2.0, log2_te=12, backend="fleet",
                           fleet_kwargs=FLEET_KW)
        for e0 in range(0, n_epochs, window):
            ev = None
            if e0 == fail_at:
                ev = [(), [SimpleNamespace(kind="fail", switch=2,
                                           factor=1.0)]]
            s.run_window(e0, [streams(e) for e in range(e0, e0 + window)],
                         events_by_epoch=ev)
        return s

    all_paths = [p for n in (3, 4) for p in
                 itertools.permutations(range(n_sw), n)][:192]
    n_keys = KEY_CHUNK + 1
    keys = np.arange(n_keys, dtype=np.uint32) * np.uint32(40503)
    paths = [all_paths[i % 192] for i in range(n_keys)]
    epochs = list(range(n_epochs))
    clean, churned = system(), system(fail_at=4)
    with jax.profiler.trace(str(tmp_path)):
        clean.query_flows(keys, paths, epochs, merge="fragment")
        churned.query_flows(keys[:192], paths[:192], epochs,
                            merge="fragment")
    flows = [s["stats"] for s in _named(_spans(str(tmp_path)),
                                        "repro.query.flows")]
    assert flows == [
        {"request": 1, "keys": n_keys, "paths": 192, "device_calls": 4 * 2,
         "batched_keys": n_keys, "fallback_paths": 0},
        {"request": 1, "keys": 192, "paths": 192, "device_calls": 192 * 4,
         "batched_keys": 0, "fallback_paths": 192}]


def test_span_outside_profiler_is_a_no_op():
    from repro import obs

    with obs.span("test.idle", blocks=3) as sp:
        sp.set_metadata(more=1)
