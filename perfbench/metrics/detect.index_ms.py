"""Host time per tile in the annotation index update: the summed durations
of the ``annotate.index`` spans (``core/annotations.AnnotationProject``)
over the tiles that finished in the traced window (a tile without
detections writes nothing and counts 0). None where no tile has the span."""


def read(ctx):
    per_tile = [[s["dur_s"] for s in rec.spans
                 if s["name"] == "annotate.index"]
                for rec in ctx["driver"].window_tiles() if rec.spans]
    if not any(per_tile):
        return None
    return 1e3 * sum(map(sum, per_tile)) / len(per_tile)
