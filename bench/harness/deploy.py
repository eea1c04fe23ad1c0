"""A configuration file and a seed made into the system under test."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np

from . import find, gen


@dataclass
class Built:
    cfg: Dict
    topo: gen.FatTree
    trace: gen.Trace
    memories: Dict[int, int]
    widths: np.ndarray
    replayer: object            # repro.net.simulator.Replayer
    kind: object                # the module bench/references/<kind>.py
    ref: object                 # its Deployment

    @property
    def window(self) -> int:
        return int(self.cfg["control"]["window"])

    @property
    def n_epochs(self) -> int:
        return self.trace.n_epochs

    def new_system(self):
        """A fresh fleet-backed system, every fragment at n = 1, its
        fragments sharded over the first ``system.shards`` devices where
        the configuration asks for more than one."""
        from repro.core.disketch import DiSketchSystem

        sk = self.cfg["sketch"]
        shards = int(self.cfg.get("system", {}).get("shards", 1))
        mesh = None
        if shards > 1:
            from repro.launch.mesh import make_switch_mesh

            mesh = make_switch_mesh(shards)
        return DiSketchSystem(
            self.memories, sk["kind"],
            rho_target=float(self.cfg["control"]["rho_target"]),
            log2_te=self.trace.log2_te, counter_bytes=sk["counter_bytes"],
            mitigation=sk["mitigation"], seed=sk["base_seed"],
            backend="fleet", mesh=mesh)


def build(cfg: Dict, seed: int) -> Built:
    """Trace and memories from the bench's generators; the system gets
    them as a ``repro.net.traffic.Workload`` and a memory map, and the
    reference of the sketch's kind (``bench/references/<kind>.py``) gets
    them as its own ``Deployment``.

    The run seed draws the flow keys; flow sizes, hosts, ECMP routes and
    arrivals come from the configuration's ``trace_seed``.  Every seed so
    replays the same packets at the same switches under other flow
    identities (and so other hash columns, signs and subepochs): every
    fragment's load, and with it the subepoch control's trajectory, stays
    the same from seed to seed, which keeps the runs' spread that of the
    system.
    """
    from repro.net.simulator import Replayer
    from repro.net.traffic import Workload

    topo = gen.FatTree(int(cfg["topology"]["fat_tree_k"]))
    tr = gen.gen_trace(topo, cfg["traffic"],
                       int(cfg["traffic"]["trace_seed"]), key_seed=seed)
    mems = gen.memories(topo.n_switches, cfg["memory"])
    sk = cfg["sketch"]
    kind = find.module("references", sk["kind"])
    widths = np.array([max(mems[s] // sk["counter_bytes"], 4)
                       for s in range(topo.n_switches)], np.int64)
    wl = Workload(tr.keys, tr.sizes, tr.path_mat, tr.pkt_flow, tr.pkt_ts,
                  tr.log2_te, tr.n_epochs)
    return Built(cfg, topo, tr, mems, widths,
                 Replayer(wl, topo.n_switches), kind,
                 kind.deployment(cfg, tr, widths))
