"""Sweeps of ``connected_components``' propagation loop per tile: the
``sweeps`` that ``vision/synapse_detector.connected_components`` records on
the ``detect.device`` span, over the tiles that finished in the traced
window. None where no tile has the count."""


def read(ctx):
    per_tile = [[s["meta"]["sweeps"] for s in rec.spans
                 if s["name"] == "detect.device" and "sweeps" in s["meta"]]
                for rec in ctx["driver"].window_tiles() if rec.spans]
    if not any(per_tile):
        return None
    return sum(map(sum, per_tile)) / len(per_tile)
