"""loader_mean_subtract_ms (program span): mean milliseconds per batch of
the loader's ``repro.data.mean_subtract`` spans (``data/preprocess.py``:
the uint8-to-float32 convert and the mean image's subtraction) that start
in the traced window."""
import phases


def read(ctx):
    w = phases.window(ctx)
    return None if w is None else w.span_ms("repro.data.mean_subtract")
