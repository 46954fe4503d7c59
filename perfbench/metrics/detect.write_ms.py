"""Host time per tile in the annotation write path: the summed durations of
the ``write.fetch`` and ``write.store`` spans (``core/cutout.write_cutout``)
of the tiles that finished in the traced window."""

WRITE_SPANS = ("write.fetch", "write.store")


def read(ctx):
    per_tile = [sum(s["dur_s"] for s in rec.spans if s["name"] in WRITE_SPANS)
                for rec in ctx["driver"].window_tiles() if rec.spans]
    return 1e3 * sum(per_tile) / len(per_tile) if per_tile else None
