"""conv2d_bwd_roofline (device trace): the conv backward's XLA
input- and weight-gradient convolutions (from the fused conv's
``custom_vjp``): their least possible time (``counts.conv_calls`` "bwd")
for each step of the window over their device time in the trace."""
import counts
import xplane


def read(ctx):
    tr, peak = ctx["trace"], ctx.get("peaks")
    if tr is None or peak is None:
        return None
    busy = tr.op_s(xplane.is_conv_grad)
    if busy <= 0:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    roof = sum(counts.roofline_s(c, peak)
               for c in counts.conv_calls(ctx["cfg"], per_chip)["bwd"])
    return 100.0 * roof * ctx["steps"] / busy
