"""loader_crop_flip_ms (program span): mean milliseconds per batch of the
loader's ``repro.data.crop_flip`` spans (``data/preprocess.py``: the
random crop and mirror) that start in the traced window."""
import phases


def read(ctx):
    w = phases.window(ctx)
    return None if w is None else w.span_ms("repro.data.crop_flip")
