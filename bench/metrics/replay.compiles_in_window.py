"""Backend compiles (JAX monitoring events) inside the replay window."""


def read(run):
    return float(run.compiles_in_window)
