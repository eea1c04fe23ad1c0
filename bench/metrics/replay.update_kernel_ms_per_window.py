"""Device milliseconds of the ragged update kernel per control window
(8 epochs), summed over the kernel's device operations in the trace."""

#: The kernel's device operation is the Pallas custom call, which the
#: compiled program names after its jitted wrapper
#: (``_fleet_update_ragged_jit.<n>``), or after the kernel function
#: itself; the wrapper's program (which adds the slice undoing the
#: factored output layout) is the fallback.
OP_NAMES = ("_fleet_update_ragged_jit", "fleet_ragged_kernel")
PROGRAM = "_fleet_update_ragged_jit"


def kernel_s(trace) -> float:
    s = sum(v for k, v in trace.op_s.items()
            if any(k.startswith(n) for n in OP_NAMES))
    return s if s > 0 else trace.module_time(PROGRAM)


def read(run):
    s = kernel_s(run.trace)
    if s <= 0 or not run.mode.windows:
        return None
    return 1e3 * s / run.mode.windows
