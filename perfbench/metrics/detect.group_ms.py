"""Host time per tile in detection's grouping of the labels into detections:
the summed durations of the ``detect.group`` spans
(``vision/synapse_detector.detect_synapses``) over the tiles that finished
in the traced window. None where no tile has the span."""


def read(ctx):
    per_tile = [[s["dur_s"] for s in rec.spans if s["name"] == "detect.group"]
                for rec in ctx["driver"].window_tiles() if rec.spans]
    if not any(per_tile):
        return None
    return 1e3 * sum(map(sum, per_tile)) / len(per_tile)
