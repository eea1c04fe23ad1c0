"""Chips that ran device operations inside the traced window: the shards
whose updates ran on their own chip (a mesh whose updates all ran on one
chip reads 1)."""


def read(run):
    t = run.trace
    if not t.has_device:
        return None
    return float(t.n_devices)
