"""Reduction of a JAX profiler trace to device and host-span figures.

A traced run wraps its measured window in the host span ``bench.window``
and its own calls into the system in further ``bench.*`` spans
(``jax.profiler.TraceAnnotation``).  The reduction reads the ``.xplane.pb``
the profiler wrote:

* device planes are those named ``/device:<accelerator>:<n>``; their
  operations are the events of the ``XLA Ops`` line, and the ``XLA
  Modules`` line names the compiled program each operation belongs to;
* busy time is the union of the operation intervals that fall inside the
  window, averaged over the devices that ran anything;
* an idle gap (a stretch of the window with no operation on a device) is
  attributed to the innermost ``bench.*`` host span that covers its
  midpoint;
* operation and program times are summed by name.  A TPU names an
  operation by its HLO instruction (``%name = f32[...] custom-call(...)``);
  the name is the part before `` = ``, without the ``%``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NOT_OPS = {MODULES_LINE, "Steps", "XLA TraceMe"}

Interval = Tuple[float, float]


@dataclass
class Reduced:
    """Figures of one traced window (seconds unless named otherwise)."""

    window_s: float = 0.0
    busy_s: float = 0.0
    n_devices: int = 0
    op_s: Dict[str, float] = field(default_factory=dict)
    module_s: Dict[str, float] = field(default_factory=dict)
    span_s: Dict[str, float] = field(default_factory=dict)
    span_n: Dict[str, int] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    idle_gaps_n: Dict[str, int] = field(default_factory=dict)

    @property
    def has_device(self) -> bool:
        return self.n_devices > 0 and self.busy_s > 0

    def module_time(self, *needles: str) -> float:
        """Device seconds of the programs whose name holds any needle."""
        return sum(s for name, s in self.module_s.items()
                   if any(n in name for n in needles))

    def breakdown(self, top: int = 10) -> Dict[str, List[list]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[f"{n} ({self.idle_gaps_n[n]} gaps)", s]
                              for n, s in gaps]}


def load(path: str):
    """ProfileData of an ``.xplane.pb`` file (optionally gzipped), or of
    the newest one under a profiler log directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _op_name(name: str) -> str:
    """An operation's name without the HLO text a TPU trace appends."""
    return name.split(" = ", 1)[0].lstrip("%")


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def reduce(profile) -> Reduced:
    """Reduce a ProfileData to the figures of its ``bench.window``."""
    spans: List[Tuple[float, float, str]] = []
    devices: List[Dict[str, list]] = []
    for plane in profile.planes:
        if _is_device_plane(plane.name):
            lines = {ln.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                _op_name(ev.name)) for ev in ln.events]
                     for ln in plane.lines}
            if OPS_LINE not in lines:
                # a profiler that names the operations' line otherwise:
                # every line but the step and program summaries
                lines[OPS_LINE] = [ev for name, evs in lines.items()
                                   if name not in NOT_OPS for ev in evs]
            if lines[OPS_LINE]:
                devices.append(lines)
        elif not plane.name.startswith("/device:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    red = Reduced()
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        return red
    w0, w1 = windows[0]
    red.window_s = (w1 - w0) * 1e-9
    inner = sorted((s, e, n) for s, e, n in spans
                   if n != WINDOW and e > w0 and s < w1)
    for s, e, n in inner:
        red.span_s[n] = red.span_s.get(n, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        red.span_n[n] = red.span_n.get(n, 0) + 1
    busy_total = 0.0
    for dev in devices:
        ops = [(s, e, n) for s, e, n in dev[OPS_LINE] if e > w0 and s < w1]
        if not ops:
            continue
        red.n_devices += 1
        for s, e, n in ops:
            red.op_s[n] = red.op_s.get(n, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        for s, e, n in dev.get(MODULES_LINE, []):
            if e > w0 and s < w1:
                red.module_s[n] = (red.module_s.get(n, 0.0)
                                   + (min(e, w1) - max(s, w0)) * 1e-9)
        busy = _union(_clip([(s, e) for s, e, _ in ops], w0, w1))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                name = _owner(inner, 0.5 * (g0 + g1))
                red.idle_by_span[name] = (red.idle_by_span.get(name, 0.0)
                                          + (g1 - g0) * 1e-9)
                red.idle_gaps_n[name] = red.idle_gaps_n.get(name, 0) + 1
    if red.n_devices:
        red.busy_s = busy_total / red.n_devices
        for d in (red.op_s, red.module_s, red.idle_by_span):
            for k in d:
                d[k] /= red.n_devices
    return red


def _owner(spans: List[Tuple[float, float, str]], t: float,
           reach: int = 64) -> str:
    """The innermost (shortest) span covering time ``t``, among the
    ``reach`` spans that started last before it (harness spans nest at
    most two deep, with a few dozen children per parent)."""
    best: Optional[Tuple[float, str]] = None
    hi = bisect.bisect_right(spans, (t, float("inf"), ""))
    for s, e, n in spans[max(0, hi - reach):hi]:
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "bench.window (no span)"
