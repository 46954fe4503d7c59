"""Label voxels the annotation write path merges for each voxel of an
object it writes: the ``voxels`` of the ``write.merge`` spans
(``core/cutout.write_cutout``) over the ``object_voxels`` of the
``annotate.batch`` spans (``core/annotations.AnnotationProject``), each
summed over the tiles that finished in the traced window. None where no
object was written."""


def read(ctx):
    merged = written = 0
    for rec in ctx["driver"].window_tiles():
        for s in rec.spans:
            if s["name"] == "write.merge":
                merged += s["meta"].get("voxels", 0)
            elif s["name"] == "annotate.batch":
                written += s["meta"].get("object_voxels", 0)
    return merged / written if written else None
