#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 5

For every seed, in one process: the cell's set-up, a short window at the
cell's own load, and the numbers its run compares (the program against
the plain reference).  For the control seeds also the same numbers with
the reference computed in bfloat16 put in the program's place (the
control, which the limits must reject).  One JSON line per seed.
The benchmark's own runs never run this.
"""
import gc
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]
os.environ["TPU_LOG_DIR"] = "disabled"

from harness import cell, deploy, find, modes  # noqa: E402


def readings(name: str, seeds, control_seeds, seconds: float,
             config_override=None):
    """Yield one dict of numbers per seed."""
    import jax

    spec = find.cell_spec(name)
    cfg = spec["config"]
    if config_override:
        cfg = {**cfg, **config_override}
    cell.enable_cache(jax.devices())
    for seed in seeds:
        t0 = time.perf_counter()
        built = deploy.build(cfg, seed % (2**32 - 1))
        mode = modes.load(spec["traffic"]["mode"])(built, spec["traffic"],
                                                   seed)
        mode.setup()
        mode.window(seconds)
        res = mode.reference()
        row = {"workload": name, "seed": seed,
               "attempted": mode.attempted, "program": mode.check(res)}
        if seed in control_seeds:
            row["control"] = mode.control(
                res, built.kind.replay(built.ref, "bf16"))
        row["seconds"] = time.perf_counter() - t0
        del mode, built, res
        gc.collect()
        yield row


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    ctl = {int(s) for s in a.control_seeds.split(",") if s}
    for row in readings(a.workload, seeds, ctl, a.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
