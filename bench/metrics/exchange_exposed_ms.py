"""exchange_exposed_ms (device trace): per step, the time the replicas'
collectives (the param and momentum all-reduce of ``core/param_avg.py``)
run on a chip while no other op runs there, averaged over the chips."""
import xplane


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["steps"] or not tr.count(xplane.is_collective):
        return None
    return 1e3 * tr.exposed_s(xplane.is_collective) / ctx["steps"]
