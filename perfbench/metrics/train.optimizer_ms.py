"""Device milliseconds a training step spends in the optimizer: AdamW's
clipping, moments and update (``hlo_scopes``' layer ``optimizer``)."""
from perfbench.lib.hlo_scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("optimizer",))
