"""One run of one cell: set-up, the measured window, the readings, the
comparison with the reference, and the result line."""
from __future__ import annotations

import gc
import json
import sys
import tempfile
import time
from typing import Dict, List, Optional

from . import deploy, find, modes, peaks, trace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class MissingReading(RuntimeError):
    """A per-layer metric that BENCHMARK.json assigns to the cell found
    nothing to read in its trace."""


def e2e_metrics(bench: Dict, cell: Dict) -> List[Dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def layer_metrics(bench: Dict, cell: Dict) -> List[Dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those that list no cells and move an end-to-end metric it reports."""
    e2e = {m["name"] for m in e2e_metrics(bench, cell)}
    return [m for m in bench["per_layer"] if m["moves"] in e2e
            and cell["name"] in m.get("workloads", [cell["name"]])]


def reader(name: str):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    return find.module("metrics", name).read


def enable_cache(devices) -> None:
    """JAX's persistent compilation cache, where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else a fixed directory of the
    checkout), so that only a checkout's first run compiles.  CPU test
    runs keep none."""
    if devices[0].platform != "cpu":
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()


class CompileLog:
    """Host-clock times of the backend compiles JAX reports."""

    def __init__(self):
        import jax

        self.at: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.at.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.at)


class Run:
    """What the per-layer readers read."""

    def __init__(self, mode, red: trace.Reduced, compiles: int,
                 device_kind: str, ref):
        self.mode = mode
        self.trace = red
        self.compiles_in_window = compiles
        self.device_kind = device_kind
        self.ref = ref

    def peaks(self) -> Dict[str, float]:
        return peaks.peaks(self.device_kind)


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def run(name: str, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_chip: bool = True,
        config_override: Optional[Dict] = None) -> Dict:
    """Run a cell and return its result line (a dict).

    ``config_override`` replaces keys of the configuration (the CPU
    tests run a cell at a size a test can hold); the chip runs never
    pass it.
    """
    spec = find.cell_spec(name)
    bench, cell, cfg, tr = (spec["bench"], spec["cell"], spec["config"],
                            spec["traffic"])
    if config_override:
        cfg = {**cfg, **config_override}
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform == "cpu"
                         or len(devices) < cell["chips"]):
        raise NoChip(f"{name} needs {cell['chips']} accelerator chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform} "
                     "device(s)")
    enable_cache(devices)
    used = devices[:cell["chips"]]
    compiles = CompileLog()
    built = deploy.build(cfg, seed % (2**32 - 1))
    mode = modes.load(tr["mode"])(built, tr, seed)
    mode.setup()
    # What set-up made lives through the window: frozen, it is left out of
    # every collection there, so a collection scans only what the window
    # makes and takes some tenths of a millisecond, not tens.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    tmp = tempfile.TemporaryDirectory() if traced else None
    if traced:
        jax.profiler.start_trace(tmp.name, profiler_options=_profiler_options())
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        mode.window(seconds)
    w1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    print(f"bench: set-up {setup_s:.3f} s; {mode.describe()}",
          file=sys.stderr, flush=True)
    stats = [d.memory_stats() or {} for d in used]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": mem_peak}
    res = mode.reference()
    out: Dict = {"correct": False, "attempted": mode.attempted,
                 "failed": getattr(mode, "failed", 0), "metrics": {}}
    if traced:
        red = trace.reduce(trace.load(tmp.name))
        tmp.cleanup()
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        r = Run(mode, red, compiles.between(w0, w1), used[0].device_kind,
                res)
        for m in layer_metrics(bench, cell):
            v = reader(m["name"])(r)
            if v is None and "workloads" in m:
                raise MissingReading(f"{m['name']}: nothing to read in the "
                                     f"trace of {name}")
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = red.breakdown()
    else:
        e2e = dict(mode.e2e(), setup_s=setup_s, hbm_peak_bytes=mem_peak)
        for m in e2e_metrics(bench, cell):
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    out["device"] = device
    numbers = mode.check(res)
    limits = tr["limits"]
    out["correct"] = all(numbers[k] <= limits[k] for k in limits)
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    return out


def print_result(out: Dict) -> None:
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json and print its result "
                    "as one JSON line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=time.perf_counter() if t_start is None
                  else t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except MissingReading as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print_result(out)
    return 0

