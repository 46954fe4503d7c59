"""Device milliseconds a training step spends in the forward pass of
attention: the projections, rotary embedding and blockwise attention
(``hlo_scopes``' layer ``attention``, pass ``fwd``)."""
from perfbench.lib.hlo_scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("attention",), ("fwd",))
