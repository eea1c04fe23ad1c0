"""Share of the resident window buffers' bytes that hold no counter of
the window, in percent, for the fleet of the window's last pass:
100 · (1 - counter_bytes / stack_bytes), where ``counter_bytes`` is
4 · Σ n · width over the resident windows' (epoch, fragment) cells and
``stack_bytes`` the bytes of their buffers (each subepoch group's
rectangle, each shard padded to the longest).  A program whose fleet
keeps neither figure reads nothing."""


def read(run):
    fleet = getattr(getattr(run.mode, "last", None), "fleet", None)
    stack = getattr(fleet, "stack_bytes", None)
    counters = getattr(fleet, "counter_bytes", None)
    if not stack or counters is None:
        return None
    return 100.0 * (1.0 - counters / stack)
