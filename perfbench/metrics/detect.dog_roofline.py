"""The DoG's share of its roofline: the least time its FLOPs and bytes
(computed from the tile's shape) take at the chip's peaks, over its mean
device time per call in the trace. The bytes bound it: the float32 tile
read once and the response written once."""
from perfbench.lib import roofline


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    if trace is None or peak is None:
        return None
    secs, calls = trace.module_seconds("difference_of_gaussians")
    if not calls:
        return None
    v = ctx["config"]["vision"]
    cost = roofline.dog_cost(ctx["traffic"]["tile"], v["sigma1"], v["sigma2"],
                             v["radius"])
    return roofline.roofline_pct(cost, secs / calls, peak)
