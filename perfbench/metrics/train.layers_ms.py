"""Device milliseconds a training step spends in the decoder layers:
attention, MLP, norms, residuals and the layer scan, forward, recomputed
and backward (``hlo_scopes``' layers ``attention``, ``mlp`` and
``layers``, every pass)."""
from perfbench.lib.hlo_scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("attention", "mlp", "layers"))
