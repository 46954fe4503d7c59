"""Device milliseconds a training step spends in the forward pass of the
MLP (``hlo_scopes``' layer ``mlp``, pass ``fwd``)."""
from perfbench.lib.hlo_scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("mlp",), ("fwd",))
