"""Continuous window replay.

One step is ``Replayer.run(system, window=W)`` over the whole trace on a
fresh fleet-backed system.  Before a pass the previous pass's system is
dropped and collected (so the HBM peak is one pass's), and the
Replayer's packed-epoch cache is emptied (every epoch is packed afresh,
as a deployment packs each new epoch).  Set-up makes ``WARM_PASSES``
passes: the first loads every program, and on a TPU v5e host the second
still ran some percent slower than the passes after it.

Correct: the counters of every (epoch, switch) of the window's last pass
and the subepoch trajectory of every pass equal the reference's.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

from harness.modes import n_arrays, span

WARM_PASSES = 3


class Mode:
    def __init__(self, b, params: Dict, seed: int):
        self.b = b
        self.rep = b.replayer
        pack = self.rep.epoch_packet

        def epoch_packet(*a, **k):
            with span("bench.pack"):
                return pack(*a, **k)

        self.rep.epoch_packet = epoch_packet

    def _pass(self):
        import jax

        system = self.b.new_system()
        run_window = system.run_window

        def spanned(*a, **k):
            with span("bench.run_window"):
                return run_window(*a, **k)

        system.run_window = spanned
        self.rep.invalidate_packets(range(self.b.n_epochs))
        self.rep.run(system, window=self.b.window)
        buf = system.fleet._window_bufs[self.b.n_epochs - 1][0]
        jax.block_until_ready(buf._dev)
        return system

    def setup(self) -> None:
        for _ in range(WARM_PASSES):
            self._pass()
            gc.collect()

    def window(self, seconds: float) -> None:
        self.n_logs: List[List[Dict[int, int]]] = []
        system = None
        t0 = time.perf_counter()
        while True:
            with span("bench.pass_boundary"):
                if system is not None:
                    self.n_logs.append(system.n_log)
                system = None
                gc.collect()
            with span("bench.pass"):
                system = self._pass()
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0
        self.n_logs.append(system.n_log)
        self.last = system
        self.passes = len(self.n_logs)

    @property
    def attempted(self) -> int:
        return self.passes

    def describe(self) -> str:
        return (f"window: {self.passes} passes in {self.elapsed:.3f} s, "
                f"{self.b.trace.observations} observations each")

    @property
    def windows(self) -> int:
        return self.passes * -(-self.b.n_epochs // self.b.window)

    def e2e(self) -> Dict[str, float]:
        return {"obs_per_s": self.passes * self.b.trace.observations
                / self.elapsed}

    def reference(self):
        return self.b.kind.replay_matching(self.b.ref,
                                           n_arrays(self.n_logs[0]))

    def check(self, res) -> Dict[str, int]:
        """Counters of the last pass and the n trajectory of every pass,
        against the reference's."""
        return _numbers(lambda e, s: self.last.records[e][s].counters,
                        [n_arrays(n_log) for n_log in self.n_logs], self.b,
                        res)

    def control(self, res, low) -> Dict[str, int]:
        """The same numbers with a lower-precision reference in the
        program's place."""
        return _numbers(lambda e, s: low.counters[(e, s)], [low.n_log],
                        self.b, res)


def _numbers(counters, n_logs, b, res) -> Dict[str, int]:
    n_wrong = sum(int((ns != res.n_log[e]).sum())
                  for n_log in n_logs for e, ns in enumerate(n_log))
    c_wrong = 0
    for e in range(b.n_epochs):
        for s in range(len(b.widths)):
            want, got = res.counters[(e, s)], counters(e, s)
            if got.shape != want.shape:
                n_wrong += 1
                c_wrong += want.size
            else:
                c_wrong += int((got != want).sum())
    return {"counters_wrong": c_wrong, "n_sub_wrong": n_wrong}
