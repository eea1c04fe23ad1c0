"""``bench/phases.py`` on the CPU, at a size a test can hold: the
program's spans of a replay window and of a query request, reduced."""
import pytest

import phases

SMALL = {"traffic": dict(trace_seed=1, n_flows=2000, total_packets=20000,
                         alpha=1.1, max_flow_frac=0.02, n_epochs=16,
                         log2_te=16, burstiness=0.2, arrival="paced"),
         "memory": {"base_bytes": 8192, "gini": 0.4, "memory_seed": 101}}
SEED = 2**31 + 99


def _measure(name):
    return phases.measure(name, SEED, 0.3, require_chip=False,
                          config_override=SMALL)


def test_replay_phases():
    out = _measure("ft4-cs.replay")
    assert [e["traced"] for e in out["e2e"]] == [False, True, False]
    assert all(e["obs_per_s"] > 0 for e in out["e2e"])
    assert set(out["readings"]) == {"replay.csr_pack_ms_per_window",
                                    "replay.sync_wait_ms_per_window",
                                    "replay.csr_pad_share"}
    assert 0 <= out["readings"]["replay.csr_pad_share"] < 100
    rows = {r[0]: r for r in out["phases"]}
    windows = out["e2e"][1]["windows"]
    # two blocking reads a window, and the window's spans under it
    assert rows["repro.fleet.sync"][5] == 2 * windows
    assert rows["repro.fleet.run_window"][5] == windows
    c = out["counters"]["repro.fleet.pack_csr"]
    assert c["slots"] >= c["slots_live"] >= c["packets"]
    assert c["packets"] == out["counters"]["repro.replay.epoch_packet"][
        "packets"]
    assert out["span_cost_us"] > 0


def test_query_phases():
    out = _measure("ft4-cs.query")
    requests = out["e2e"][1]["requests"]
    assert requests >= 1
    flows = out["counters"]["repro.query.flows"]
    # 16 epochs in windows of 8: two resident stacks for every path
    assert out["readings"]["query.device_calls_per_request"] == \
        pytest.approx(2 * flows["paths"] / requests)
    assert out["readings"]["query.h2d_bytes_per_request"] > 0
    assert out["readings"]["query.host_prep_ms_per_request"] > 0
    assert out["program_spans_per"][1] == "requests"
