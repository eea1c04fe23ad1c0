"""The sharded update kernel's share of its roofline, in percent: the
least time the window's update work needs on one chip, over the
kernel's device seconds summed over the chips that ran it.

The work is counted as ``replay.update_kernel_roofline`` counts it
(``harness.peaks.update_bytes``: every observation read once, every
(epoch, fragment)'s own n x width counters written once, against one
chip's HBM bandwidth); the kernel's time is found by
``replay.update_kernel_ms_per_window``, whose figure is the mean over
chips, so it is multiplied back by the chips that ran."""
from harness import find, peaks


def read(run):
    s = find.module("metrics", "replay.update_kernel_ms_per_window"
                    ).kernel_s(run.trace) * run.trace.n_devices
    if s <= 0:
        return None
    b = run.mode.b
    per_pass = peaks.update_bytes(b.trace.observations, run.ref.n_used,
                                  b.widths)
    least_s = run.mode.passes * per_pass / run.peaks()["hbm_bytes_s"]
    return 100.0 * least_s / s
