"""Device milliseconds of the query plane's fused gather/merge programs
(``kernels/sketch_query/engine.py`` ``_gather_merge``) per request."""

PROGRAM = "_gather_merge"


def read(run):
    s = run.trace.module_time(PROGRAM)
    if s <= 0 or not run.mode.requests:
        return None
    return 1e3 * s / run.mode.requests
