"""Backend compiles (JAX monitoring events) inside the query window; a
compile shows up as a tail stall."""


def read(run):
    return float(run.compiles_in_window)
