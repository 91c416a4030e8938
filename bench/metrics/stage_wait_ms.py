"""stage_wait_ms (program counter): mean milliseconds per step the
trainer waited in ``next()`` on the loader (``wait_ms_total`` of
``repro.data.pipeline``), over the traced window's steps."""


def read(ctx):
    total = ctx["counters"].get("stage_wait_ms_total")
    if total is None or not ctx["steps"]:
        return None
    return total / ctx["steps"]
