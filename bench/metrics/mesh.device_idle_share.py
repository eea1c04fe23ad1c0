"""Share of the cell's chip time inside the traced window in which no
operation ran, in percent, over every chip the configuration shards its
fleet over (``system.shards``), busy or not:
100 · (1 - busy_s · n_devices / (shards · window_s)), where ``busy_s``
is the union of a chip's operation intervals averaged over the chips
that ran any."""


def read(run):
    t = run.trace
    if not t.has_device or t.window_s <= 0:
        return None
    shards = int(run.mode.b.cfg.get("system", {}).get("shards", 1))
    return 100.0 * (1.0 - t.busy_s * t.n_devices / (shards * t.window_s))
