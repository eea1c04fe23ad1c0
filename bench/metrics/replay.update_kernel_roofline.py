"""Update kernel's share of its roofline, in percent: the least time the
window's update work needs on this chip, over the kernel's device time.

The work is counted whatever implements it (``harness.peaks.
update_bytes``): every observation's key, value and timestamp read once
and every (epoch, fragment)'s own n x width counters written once.  The
update moves bytes and does no arithmetic a scatter could not avoid, so
the bound is HBM bandwidth; the one-hot contraction's MXU work is not
counted."""
from harness import find, peaks


def read(run):
    s = find.module("metrics", "replay.update_kernel_ms_per_window"
                    ).kernel_s(run.trace)
    if s <= 0:
        return None
    b = run.mode.b
    per_pass = peaks.update_bytes(b.trace.observations, run.ref.n_used,
                                  b.widths)
    least_s = run.mode.passes * per_pass / run.peaks()["hbm_bytes_s"]
    return 100.0 * least_s / s
