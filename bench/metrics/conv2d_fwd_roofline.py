"""conv2d_fwd_roofline (device trace): the fused Pallas conv forward
kernels' least possible time (``counts.conv_calls`` "fwd", per call the
larger of FLOPs over the dtype's peak and bytes over HBM bandwidth, for
each step of the window) over their device time in the trace."""
import counts
import xplane


def read(ctx):
    tr, peak = ctx["trace"], ctx.get("peaks")
    if tr is None or peak is None:
        return None
    busy = tr.op_s(xplane.is_conv_fwd_kernel)
    if busy <= 0:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    roof = sum(counts.roofline_s(c, peak)
               for c in counts.conv_calls(ctx["cfg"], per_chip)["fwd"])
    return 100.0 * roof * ctx["steps"] / busy
