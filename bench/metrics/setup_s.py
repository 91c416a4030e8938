"""setup_s (host clock): process start to the first timed step — imports
and device init, weights, trace and autotune, compile, feed, and the
three checked steps."""


def read(ctx):
    return ctx["setup_s"]
