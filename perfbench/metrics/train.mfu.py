"""Model FLOP utilisation of the traced training window: forward and
backward FLOPs per token, from the cell's architecture (for a dense model,
6 per matrix-product parameter plus the causal attention products;
recomputation not counted), times the window's tokens per second, over the
chip's bf16 peak."""
from perfbench.lib import roofline


def read(ctx):
    drv, peak = ctx["driver"], ctx["peak"]
    if peak is None or not drv.window_steps:
        return None
    tok_s = drv.window_steps * drv.batch * drv.seq / drv.window_s
    flops = drv.arch.train_flops_per_token(ctx["config"], drv.seq,
                                           drv.counters())
    return roofline.mfu_pct(flops, tok_s, peak)
