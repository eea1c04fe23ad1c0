"""The control (the plain reference in bfloat16, put in the program's
place) fails each cell's limits, and the program passes them, at a size
a test run can hold.  On the chip, at the cells' own sizes, the same
readings come from ``bench/readings.py``."""
import json
from pathlib import Path

import pytest

import readings

# 512-byte fragments (128 counters) under 50K packets an epoch: the
# heaviest flows put counters above 256, where bfloat16 rounds.
SMALL = {"traffic": dict(trace_seed=1, n_flows=4000,
                         total_packets=400_000, alpha=1.1,
                         max_flow_frac=0.02, n_epochs=8, log2_te=16,
                         burstiness=0.2, arrival="paced"),
         "memory": {"base_bytes": 512, "gini": 0.4, "memory_seed": 101}}
TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("name", ["ft4-cs.replay", "ft14-cms.replay",
                                  "ft4-cs.query"])
def test_control_fails_and_program_passes(name):
    traffic = name.split(".")[1]
    limits = json.loads((TRAFFIC / f"{traffic}.json").read_text())["limits"]
    seeds = [2**31 + 5, 7]
    rows = list(readings.readings(name, seeds, set(seeds), 1.0,
                                  config_override=SMALL))
    for row in rows:
        assert all(row["program"][k] <= v for k, v in limits.items()), row
        assert any(row["control"][k] > v for k, v in limits.items()), row


def test_ties_follow_an_admissible_branch():
    """At rho 1.0, Count-Min bounds count / (width n) meet the Eq. 6
    thresholds 2.0 and 0.5 exactly; float32 (the system) and float64
    (the reference) may decide such ties differently, and the reference
    follows the system's admissible branch."""
    from harness import deploy, find, modes

    spec = find.cell_spec("ft14-cms.replay")
    cfg = {**spec["config"], **SMALL,
           "traffic": dict(SMALL["traffic"], total_packets=100_000,
                           n_epochs=16),
           "control": {"window": 8, "rho_target": 1.0}}
    mode = modes.load("replay")(deploy.build(cfg, 2**31 + 5),
                                spec["traffic"], 0)
    mode.setup()
    mode.window(0.1)
    res = mode.reference()
    assert res.ties
    assert mode.check(res) == {"counters_wrong": 0, "n_sub_wrong": 0}
