"""A closed loop of one controller's window queries.

Set-up replays the trace once and leaves its window stacks resident; one
step is one ``query_flows(keys, paths, epochs, merge="fragment")``.
Parameters of the traffic file:

* ``path_len``: the flows queried are those whose path has this many
  switches (5: the full-path flows that the paper's section 6.1 scores);
* ``keys_per_request``: ``"all"`` of them, or that many drawn from the
  seed for each request;
* ``epochs_per_request``: ``"all"`` epochs of the trace, or that many
  consecutive ones from a start drawn from the seed.

Set-up makes one request from every start epoch, with the window's key
count, so that the window compiles nothing new.

Correct: every estimate the window answered equals the reference's
fragment merge over the reference's own replay, and every request was
answered.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from harness.modes import n_arrays, span


class Mode:
    def __init__(self, b, params: Dict, seed: int):
        self.b = b
        self.seed = seed
        self.pool = np.flatnonzero(b.trace.path_len == int(params["path_len"]))
        span_e = params["epochs_per_request"]
        self.span_e = b.n_epochs if span_e == "all" else int(span_e)
        self.last_start = b.n_epochs - self.span_e
        k = params["keys_per_request"]
        self.k = len(self.pool) if k == "all" else int(k)

    def _draw(self, rng) -> np.ndarray:
        if self.k == len(self.pool):
            return self.pool
        return rng.choice(self.pool, self.k, replace=False)

    def _request(self, flows: np.ndarray, start: int) -> np.ndarray:
        rows = self.b.trace.path_mat[flows]
        paths = [tuple(int(x) for x in r if x >= 0) for r in rows]
        return np.asarray(self.system.query_flows(
            self.b.trace.keys[flows], paths,
            list(range(start, start + self.span_e)), merge="fragment"))

    def setup(self) -> None:
        import jax

        self.system = self.b.new_system()
        self.b.replayer.run(self.system, window=self.b.window)
        buf = self.system.fleet._window_bufs[self.b.n_epochs - 1][0]
        jax.block_until_ready(buf._dev)
        rng = np.random.default_rng([self.seed, 1])
        for start in range(self.last_start + 1):
            self._request(self._draw(rng), start)

    def window(self, seconds: float) -> None:
        rng = np.random.default_rng([self.seed, 2])
        self.log = []           # (flows, start, estimates)
        self.lat: List[float] = []
        self.failed = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            flows = self._draw(rng)
            start = int(rng.integers(0, self.last_start + 1))
            t = time.perf_counter()
            with span("bench.request"):
                try:
                    est = self._request(flows, start)
                except Exception:   # counted against correctness below
                    self.failed += 1
                    est = None
            self.lat.append(time.perf_counter() - t)
            self.log.append((flows, start, est))
        self.elapsed = time.perf_counter() - t0

    @property
    def attempted(self) -> int:
        return len(self.log)

    @property
    def requests(self) -> int:
        return len(self.log)

    def describe(self) -> str:
        ms = np.percentile(self.lat, [0, 50, 95, 100]) * 1e3
        return (f"window: {len(self.log)} requests of {self.k} keys over "
                f"{self.span_e} epochs in {self.elapsed:.3f} s; latency ms "
                f"min {ms[0]:.2f} median {ms[1]:.2f} p95 {ms[2]:.2f} "
                f"max {ms[3]:.2f}")

    def e2e(self) -> Dict[str, float]:
        answered = sum(len(f) for f, _, est in self.log if est is not None)
        return {"query_keys_per_s": answered / self.elapsed,
                "query_p95_ms": float(np.percentile(self.lat, 95)) * 1e3}

    def reference(self):
        return self.b.kind.replay_matching(self.b.ref,
                                           n_arrays(self.system.n_log))

    def check(self, res) -> Dict[str, int]:
        """Every answered request's estimates against the reference's
        fragment merge over the reference's own replay."""
        bad, answered = self.failed, []
        for flows, start, est in self.log:
            if est is None:
                continue
            if len(est) != len(flows) or not np.isfinite(est).all():
                bad += 1
                continue
            answered.append((flows, start, est))
        return {"estimates_wrong": self._wrong(answered, res),
                "answers_failed": bad}

    def control(self, res, low) -> Dict[str, int]:
        """The same numbers with the window's requests answered from a
        lower-precision reference in the program's place."""
        ans = _Answers(self.b, low, [f for f, _, _ in self.log])
        answered = [(flows, start, ans.window(flows, start, self.span_e))
                    for flows, start, _ in self.log]
        return {"estimates_wrong": self._wrong(answered, res),
                "answers_failed": 0}

    def _wrong(self, answered, res) -> int:
        """Estimates that differ from the reference's.  The comparison is
        exact: a full path has an odd number of fragments, so the median
        is one of the counters, each an integer times a power of two, and
        a window's sum of them stays an integer below 2^24."""
        ans = _Answers(self.b, res, [f for f, _, _ in answered])
        return sum(int((est != ans.window(flows, start, self.span_e)).sum())
                   for flows, start, est in answered)


class _Answers:
    """The reference's per-epoch fragment-merge estimates of every flow
    the requests asked for, summed per request in epoch order."""

    def __init__(self, b, res, flow_sets):
        self.flows = (np.unique(np.concatenate(flow_sets)) if flow_sets
                      else np.zeros(0, np.int64))
        self.per_epoch = np.stack([
            b.kind.window_estimates(b.ref, res, self.flows, [e])
            for e in range(b.n_epochs)]) if len(self.flows) else None

    def window(self, flows, start: int, span_e: int) -> np.ndarray:
        idx = np.searchsorted(self.flows, flows)
        total = np.zeros(len(flows))
        for e in range(start, start + span_e):
            total += self.per_epoch[e, idx]
        return total
