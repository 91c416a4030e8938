"""images_per_s (host clock): the images of every step completed in the
window over the window, which ends when the last step's loss is ready."""


def read(ctx):
    return ctx["steps"] * ctx["batch"] / ctx["window_s"]
