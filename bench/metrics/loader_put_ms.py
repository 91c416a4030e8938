"""loader_put_ms (program span): mean milliseconds per batch of the
loader's ``repro.data.device_put`` spans (``data/pipeline.py``: the host
part of the put) that start in the traced window."""
import phases


def read(ctx):
    w = phases.window(ctx)
    return None if w is None else w.span_ms("repro.data.device_put")
