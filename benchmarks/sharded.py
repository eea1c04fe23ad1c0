"""Benchmark: sharded fragment fleet on a forced 8-device CPU mesh.

A 200+-switch fat-tree (FatTree(14) -> 245 switches, ~10x the paper's
testbed) replayed through ``DiSketchSystem(backend="fleet")`` twice —
single-device and sharded over an 8-way ``switch`` mesh — inside a
subprocess.  The subprocess is load-bearing: the forced host device
count only takes effect via ``XLA_FLAGS`` *before* jax initialises, and
the main bench process must keep its 1-device view so the committed
gated headlines (``ragged_pkts_per_s`` etc.) are measured under the
same runtime as their baselines.

The child is pinned to the CPU (``JAX_PLATFORMS=cpu``, Pallas in
interpret mode): on a TPU host the parent already holds the chip, and a
child that asked for it would fail or hang.  This row is a CPU parity
check of the mesh plumbing; the sharded fleet's run on real chips is
``python chip_smoke.py --chips 4``.

``sharded_ok`` is a correctness gate (kernel_bench._MATCH_COLS): the
sharded run must reproduce the single-device counters and fragment-
merged query estimates bit for bit.  Throughput numbers are recorded
as ungated headline fields — on a 1-core CPU host, 8 forced devices
share one core, so the honest scaling factor is ~1x (the row exists to
pin the parity + plumbing cost, not to demonstrate speedup).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# Child process: builds the fat-tree scenario, replays it at 1 and 8
# devices, checks bit-identity, prints one JSON line on stdout.
_CHILD = r"""
import json
import sys
import time

sys.path.insert(0, %(src)r)
sys.path.insert(0, %(root)r)
import numpy as np
import jax

assert jax.device_count() >= 8, (
    "forced host device count did not take: %%d" %% jax.device_count())

from benchmarks.common import memories_for
from repro.core.disketch import DiSketchSystem
from repro.launch.mesh import make_switch_mesh
from repro.net.simulator import Replayer
from repro.net.topology import FatTree
from repro.net.traffic import gen_workload

quick = %(quick)r
topo = FatTree(14)                       # 2*14*7 + 7*7 = 245 switches
n_epochs = 2 if quick else 4
wl = gen_workload(topo, n_flows=1_200 if quick else 8_000,
                  total_packets=10_000 if quick else 80_000,
                  n_epochs=n_epochs, burstiness=0.2, seed=17)
rng = np.random.RandomState(5)
mems = memories_for(topo, 2 * 1024, 0.5, rng)   # heterogeneous widths


def build(mesh):
    return DiSketchSystem(mems, "cms", rho_target=2.0,
                          log2_te=wl.log2_te, backend="fleet", mesh=mesh)


def replay(mesh):
    # Warm run populates the process-wide jit/dispatch caches (shapes
    # are identical across runs), then a fresh system is timed.
    Replayer(wl, topo.n_switches).run(build(mesh), window=n_epochs)
    system = build(mesh)
    t0 = time.perf_counter()
    Replayer(wl, topo.n_switches).run(system, window=n_epochs)
    return system, time.perf_counter() - t0


ref, t_1dev = replay(None)
sh, t_8dev = replay(make_switch_mesh(8))

keys = wl.keys[:64]
paths = wl.paths[:64]
epochs = list(range(n_epochs))
est_ref = np.asarray(ref.query_flows(keys, paths, epochs,
                                     merge="fragment"))
est_sh = np.asarray(sh.query_flows(keys, paths, epochs,
                                   merge="fragment"))
ok = (ref.ns == sh.ns and np.array_equal(est_ref, est_sh)
      and all(np.array_equal(ref.fleet._host_stack(e),
                             sh.fleet._host_stack(e)) for e in epochs))

# packet observations = one counter update per on-path switch hop
obs = int(wl.path_len[wl.pkt_flow].sum())
print(json.dumps({
    "sharded_ok": bool(ok),
    "n_switches": int(topo.n_switches),
    "n_devices": int(jax.device_count()),
    "n_epochs": n_epochs,
    "total_pkts": int(len(wl.pkt_flow)),
    "total_obs": obs,
    "t_1dev_s": round(t_1dev, 4),
    "t_8dev_s": round(t_8dev, 4),
    "pkts_per_s_1dev": round(obs / t_1dev, 1),
    "pkts_per_s_8dev": round(obs / t_8dev, 1),
    "scaling_x": round(t_1dev / t_8dev, 3),
}))
"""


def run(quick: bool = True):
    env = dict(os.environ)
    flag = "--xla_force_host_platform_device_count=8"
    if "xla_force_host_platform_device_count" not in \
            env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    env["JAX_PLATFORMS"] = "cpu"
    code = _CHILD % {"src": os.path.join(_ROOT, "src"), "root": _ROOT,
                     "quick": quick}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1800, env=env)
    if r.returncode != 0:
        # keep the bench JSON writable and let the _MATCH_COLS gate
        # report the failure instead of crashing the whole bench run
        tail = (r.stderr or r.stdout).strip().splitlines()[-8:]
        rows = [{"bench": "fleet_sharded", "sharded_ok": False,
                 "error": " | ".join(tail)}]
    else:
        payload = json.loads(r.stdout.strip().splitlines()[-1])
        rows = [{"bench": "fleet_sharded", **payload}]
    emit("fleet_sharded", rows)
    return rows
