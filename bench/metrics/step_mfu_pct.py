"""step_mfu_pct (host clock): model FLOPs per step (``counts``: convs and
matrix products, forward and backward, no conv1 input gradient) times the
steps completed, over the window times chips times the chip's bf16 peak."""
import counts


def read(ctx):
    peak = ctx.get("peaks")
    if peak is None or not ctx["steps"]:
        return None
    flops = counts.model_flops_per_step(ctx["cfg"], ctx["batch"])
    return 100.0 * flops * ctx["steps"] / (
        ctx["window_s"] * ctx["chips"] * peak["flops_per_s"]["bfloat16"])
