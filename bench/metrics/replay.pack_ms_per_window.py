"""Host milliseconds in ``Replayer.epoch_packet`` (per-epoch packing of
the switch streams) per control window, from the harness's ``bench.pack``
spans in the trace."""


def read(run):
    s = run.trace.span_s.get("bench.pack", 0.0)
    if s <= 0 or not run.mode.windows:
        return None
    return 1e3 * s / run.mode.windows
