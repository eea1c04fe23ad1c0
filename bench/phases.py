#!/usr/bin/env python3
"""Where a cell's window spends its time, by the program's own spans.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> [--out F]

Sets the cell up as ``bench/run.py`` does, then measures three windows of
``--seconds`` each: untraced, traced, untraced.  Prints one JSON object
(and writes it to ``--out``): each window's end-to-end numbers, so that
the cost of tracing shows against the untraced windows on either side;
the traced window's device busy time, its idle time put down to the
innermost span (``harness.spans``), each span's self time, count and
summed counters, and the per-layer readings the spans give; and what a
span costs with no profiler running.  Needs the accelerator the cell
names, like ``bench/run.py``; it checks no result against the reference.
"""
import gc
import json
import sys
import tempfile
import time
import timeit
from typing import Dict, Optional

# As for a benchmark run: importing run.py fixes the allocator's
# thresholds and puts the program and the harness on the import path.
import run  # noqa: F401

from harness import cell, deploy, find, modes, spans, trace  # noqa: E402

#: harness spans around the harness's own calls into the program: the
#: idle time left to them (or to no span), by gap midpoints and by
#: overlap, is what the program's spans do not name
HARNESS = ("bench.run_window", "bench.request", "bench.pass", spans.NO_SPAN)


def span_cost_us(n: int = 200_000) -> float:
    """Microseconds of one ``repro.obs.span`` with two counters, with no
    profiler running."""
    from repro import obs

    def one():
        with obs.span("cost", a=1, b=2):
            pass
    return 1e6 * min(timeit.repeat(one, number=n, repeat=3)) / n


def measure(name: str, seed: int, seconds: float, *,
            require_chip: bool = True,
            config_override: Optional[Dict] = None) -> dict:
    """The phases of one cell; ``require_chip`` and ``config_override``
    as in ``cell.run`` (the CPU tests run a small configuration)."""
    import jax

    t_start = time.perf_counter()
    spec = find.cell_spec(name)
    devices = jax.devices()
    if require_chip and (devices[0].platform == "cpu"
                         or len(devices) < spec["cell"]["chips"]):
        raise cell.NoChip(f"{name} needs {spec['cell']['chips']} "
                          "accelerator chip(s)")
    cell.enable_cache(devices)
    cfg = {**spec["config"], **(config_override or {})}
    built = deploy.build(cfg, seed % (2**32 - 1))
    mode = modes.load(spec["traffic"]["mode"])(built, spec["traffic"], seed)
    mode.setup()
    gc.collect()
    gc.freeze()
    out = {"workload": name, "seed": seed, "seconds": seconds,
           "device": devices[0].device_kind,
           "setup_s": time.perf_counter() - t_start, "e2e": []}
    with tempfile.TemporaryDirectory() as tmp:
        for traced in (False, True, False):
            if traced:
                jax.profiler.start_trace(
                    tmp, profiler_options=cell._profiler_options())
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                mode.window(seconds)
            counts = {"windows": getattr(mode, "windows", 0),
                      "requests": getattr(mode, "requests", 0)}
            out["e2e"].append(dict(mode.e2e(), traced=traced, **counts))
            if traced:
                jax.profiler.stop_trace()
                per = counts
                profile = trace.load(tmp)
                red = spans.reduce(profile)
                old = trace.reduce(profile)
    idle = max(red.window_s - red.busy_s, 0.0)
    left = sum(red.idle_by_span.get(n, 0.0) for n in HARNESS)
    left_self = sum(red.idle_self_s.get(n, 0.0) for n in HARNESS)
    n_program = sum(n for s, n in red.span_n.items()
                    if s.startswith("repro."))
    unit = "windows" if per["windows"] else "requests"
    out.update(
        window_s=red.window_s, busy_s=red.busy_s,
        idle_share=100.0 * idle / red.window_s if red.window_s else None,
        idle_left_to_harness_share=100.0 * left / idle if idle else None,
        idle_self_left_to_harness_share=(100.0 * left_self / idle
                                         if idle else None),
        program_spans_per=(n_program / per[unit] if per[unit] else None,
                           unit),
        readings=spans.readings(red, **per),
        phases=red.breakdown(),
        counters=red.counters,
        device_ops=old.breakdown()["device_ops"],
        harness_idle_gaps=old.breakdown()["idle_gaps"],
        span_cost_us=span_cost_us())
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    try:
        out = measure(args.workload, args.seed, args.seconds)
    except cell.NoChip as e:
        print(f"phases: {e}", file=sys.stderr)
        return 2
    line = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
