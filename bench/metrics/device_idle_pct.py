"""device_idle_pct (device trace): 100 x (1 - union of the ops' intervals
over the traced window), averaged over the cell's chips."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())
