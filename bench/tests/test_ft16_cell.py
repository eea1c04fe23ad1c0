"""The four-chip FatTree(16) cell: a run of it at a cut traffic comes out
correct on four virtual CPU devices (in a child process: the device
count is fixed when JAX starts), and its four ``mesh.*`` readers give
the figures of a synthetic run."""
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from harness import cell, find, peaks, trace

BENCH = Path(__file__).resolve().parents[1]
CELL = "ft16-cs.replay.4chip"

CHILD = r"""
import sys, time
sys.path[:0] = [%(src)r, %(bench)r]
import jax
assert jax.device_count() == 4, jax.device_count()
from harness import cell
small = {"traffic": dict(trace_seed=1, n_flows=2000, total_packets=20000,
                         alpha=1.1, max_flow_frac=0.02, n_epochs=16,
                         log2_te=16, burstiness=0.2, arrival="paced")}
out = cell.run(%(cell)r, 2**31 + 11, 0.5, False,
               t_start=time.perf_counter(), require_chip=False,
               config_override=small)
assert out["correct"], out["checks"]
assert set(out["metrics"]) == {"setup_s", "obs_per_s", "hbm_peak_bytes"}
print("ft16 ok", out["checks"])
"""


def test_ft16_cell_runs_correct_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD % {"src": str(BENCH.parent / "src"), "bench": str(BENCH),
                    "cell": CELL}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0 and "ft16 ok" in r.stdout, r.stderr[-3000:]


def _run(n_devices=4, kernel_s=0.5, fleet=True):
    """A synthetic traced run of the cell: 2 passes of 2 epochs over 3
    switches, a 10 s window with 2 s busy on each of ``n_devices`` chips
    and ``kernel_s`` of the update kernel on each."""
    widths = np.array([1000, 3000, 500])
    b = SimpleNamespace(cfg={"system": {"shards": 4}}, widths=widths,
                        trace=SimpleNamespace(observations=10_000))
    last = SimpleNamespace(fleet=SimpleNamespace(
        stack_bytes=1000, counter_bytes=250)) if fleet else None
    mode = SimpleNamespace(b=b, passes=2, windows=2, last=last)
    red = trace.Reduced(window_s=10.0, busy_s=2.0, n_devices=n_devices,
                        op_s={"_fleet_update_ragged_jit.3": kernel_s})
    ref = SimpleNamespace(n_used=[np.array([1, 2, 4]), np.array([2, 2, 1])])
    return cell.Run(mode, red, 0, "TPU v5 lite", ref)


def _read(name, run):
    return find.module("metrics", name).read(run)


def test_mesh_readers_on_a_synthetic_run():
    run = _run()
    assert _read("mesh.chips_busy", run) == 4.0
    # 4 chips busy 2 s of 10 each: 80% of the cell's chip time idle
    assert _read("mesh.device_idle_share", run) == pytest.approx(80.0)
    # two of the four chips ran: the other two count as idle
    assert _read("mesh.device_idle_share", _run(n_devices=2)) \
        == pytest.approx(90.0)
    # by hand: 12 B an observation, 4 B a counter of every cell
    counters = (1000 + 6000 + 2000) + (2000 + 6000 + 500)
    per_pass = 12 * 10_000 + 4 * counters
    assert per_pass == peaks.update_bytes(10_000, run.ref.n_used,
                                          run.mode.b.widths)
    least_s = 2 * per_pass / 819e9
    assert _read("mesh.update_kernel_roofline", run) == pytest.approx(
        100.0 * least_s / (0.5 * 4))
    assert _read("mesh.stack_pad_share", run) == pytest.approx(75.0)


def test_mesh_readers_find_nothing_where_nothing_ran():
    run = _run(n_devices=0, kernel_s=0.0, fleet=False)
    for name in ("mesh.chips_busy", "mesh.device_idle_share",
                 "mesh.update_kernel_roofline", "mesh.stack_pad_share"):
        assert _read(name, run) is None
    # a fleet without the window-buffer figures (an older program)
    run = _run()
    run.mode.last.fleet = SimpleNamespace()
    assert _read("mesh.stack_pad_share", run) is None
