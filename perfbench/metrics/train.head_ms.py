"""Device milliseconds a training step spends in the model's head: the
embedding lookup, the final norm, the logits and the loss, forward and
backward (``hlo_scopes``' layer ``head``)."""
from perfbench.lib.hlo_scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("head",))
