"""A configuration that asks for ``system.shards`` gets its fragments
sharded over that many devices, and a run of it comes out correct.  Run
in a child process on four virtual CPU devices (the device count is
fixed when JAX starts)."""
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CHILD = r"""
import sys, time
sys.path[:0] = [%(src)r, %(bench)r]
import jax
assert jax.device_count() == 4, jax.device_count()
from harness import cell
small = {"traffic": dict(trace_seed=1, n_flows=2000, total_packets=20000,
                         alpha=1.1, max_flow_frac=0.02, n_epochs=16,
                         log2_te=16, burstiness=0.2, arrival="paced"),
         "system": {"shards": 4}}
out = cell.run("ft4-cs.replay", 2**31 + 7, 0.5, False,
               t_start=time.perf_counter(), require_chip=False,
               config_override=small)
assert out["correct"], out["checks"]
print("sharded ok")
"""


def test_sharded_configuration_runs_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD % {"src": str(BENCH.parent / "src"), "bench": str(BENCH)}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "sharded ok" in r.stdout, r.stderr[-3000:]
