"""The plain reference agrees with the system's per-switch numpy backend
(per-epoch control) and with its fleet backend (window control), counter
for counter, and its window estimates with the host query plane."""
import numpy as np
import pytest

from harness import gen, reference

T = dict(n_flows=3000, total_packets=40000, alpha=1.1, max_flow_frac=0.02,
         n_epochs=8, log2_te=16, burstiness=0.2, arrival="paced")


def _deployment(kind, mitigation, base, window, rho, seed=5, t=T):
    from repro.net.traffic import Workload

    topo = gen.FatTree(4)
    tr = gen.gen_trace(topo, t, seed)
    mems = gen.memories(topo.n_switches, dict(base_bytes=base, gini=0.4,
                                              memory_seed=101))
    d = reference.Deployment(
        kind=kind, mitigation=mitigation, base_seed=0,
        widths=np.array([max(mems[s] // 4, 4) for s in range(20)]),
        rho_target=rho, window=window, log2_te=16, n_epochs=T["n_epochs"],
        keys=tr.keys, path_mat=tr.path_mat, pkt_flow=tr.pkt_flow,
        pkt_ts=tr.pkt_ts)
    wl = Workload(tr.keys, tr.sizes, tr.path_mat, tr.pkt_flow, tr.pkt_ts,
                  16, T["n_epochs"])
    return d, mems, wl, tr


def _system(mems, kind, mitigation, rho, backend):
    from repro.core.disketch import DiSketchSystem

    return DiSketchSystem(mems, kind, rho_target=rho, log2_te=16,
                          mitigation=mitigation, backend=backend)


def _assert_same(system, res, d):
    for e in range(d.n_epochs):
        for s in range(len(d.widths)):
            np.testing.assert_array_equal(system.records[e][s].counters,
                                          res.counters[(e, s)])
        assert [system.n_log[e][s] for s in range(len(d.widths))] \
            == res.n_log[e].tolist()


# 512 KiB fragments have widths above 2^16, where the column hash's
# 16-bit limb products wrap; rho 2 walks n up to 8 and back.
@pytest.mark.parametrize("kind,mit,base,rho", [
    ("cs", True, 8 * 1024, 2.0), ("cs", False, 512 * 1024, 0.05),
    ("cms", False, 2 * 1024, 2.0)])
def test_reference_matches_loop_backend(kind, mit, base, rho):
    from repro.net.simulator import Replayer

    d, mems, wl, tr = _deployment(kind, mit, base, 1, rho)
    system = _system(mems, kind, mit, rho, "loop")
    Replayer(wl, 20).run(system)
    res = reference.replay(d)
    assert max(int(n.max()) for n in res.n_used) > 1
    _assert_same(system, res, d)
    flows = np.arange(len(tr.keys))
    paths = [tuple(int(x) for x in r if x >= 0) for r in tr.path_mat]
    for epochs in ([0], [2, 3, 4, 5]):
        want = system.query_flows(tr.keys, paths, epochs, merge="fragment")
        got = reference.window_estimates(d, res, flows, epochs)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-9)


@pytest.mark.parametrize("kind,mit", [("cs", True), ("cms", False)])
def test_reference_matches_fleet_windows(kind, mit):
    from repro.net.simulator import Replayer

    d, mems, wl, _ = _deployment(kind, mit, 4 * 1024, 4, 2.0)
    system = _system(mems, kind, mit, 2.0, "fleet")
    Replayer(wl, 20).run(system, window=4)
    _assert_same(system, reference.replay(d), d)


def test_bf16_control_rounds_counters():
    # 128-byte fragments (32 counters) over 100K packets: counters
    # above 256, where bfloat16 holds no odd integer
    d, *_ = _deployment("cms", False, 128, 4, 1e9,
                        t=dict(T, total_packets=100_000))
    exact, low = reference.replay(d), reference.replay(d, "bf16")
    big = [k for k, c in exact.counters.items() if np.abs(c).max() > 256]
    assert big and any((low.counters[k] != exact.counters[k]).any()
                       for k in big)
    with pytest.raises(ValueError):
        reference.replay(d, "fp8")
