"""Thread CPU time per tile of the annotation write path, each second
counted once: the ``cpu_s`` of each ``annotate.batch`` span
(``core/annotations.AnnotationProject``; the worker thread's, which holds
the merge, the index update and any node job run in that thread) plus the
``cpu_s`` of the ``node.fetch`` and ``node.store`` spans below it that ran
on another thread (the fan-out pool's), over the tiles that finished in
the traced window (a tile without detections counts 0). None where no tile
has an ``annotate.batch`` span."""

NODE_SPANS = ("node.fetch", "node.store")


def tile_cpu_s(spans):
    """The write path's thread CPU seconds among one tile's spans."""
    by_id = {s["id"]: s for s in spans}

    def batch_above(s):
        while s is not None and s["name"] != "annotate.batch":
            s = by_id.get(s["parent"])
        return s

    total = 0.0
    for s in spans:
        if s["name"] == "annotate.batch":
            total += s.get("cpu_s", 0.0)
        elif s["name"] in NODE_SPANS:
            batch = batch_above(s)
            if batch is not None and s["thread"] != batch["thread"]:
                total += s.get("cpu_s", 0.0)
    return total


def read(ctx):
    tiles = [rec.spans for rec in ctx["driver"].window_tiles() if rec.spans]
    if not any(s["name"] == "annotate.batch" for spans in tiles for s in spans):
        return None
    return 1e3 * sum(map(tile_cpu_s, tiles)) / len(tiles)
