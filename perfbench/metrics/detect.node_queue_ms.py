"""Job-ms per tile that the cluster's per-node jobs waited for a thread
of the fan-out pool: the ``queued_s`` of the ``node.fetch`` and
``node.store`` spans (``cluster/store.ClusterStore``), the image reads'
and the annotation writes' together, summed over jobs and averaged over
the tiles that finished in the traced window. Jobs that wait side by
side (the replica writes of one chunk) each count, so this is waiting
summed over jobs, not elapsed time. None where no node span records its
wait."""

NODE_SPANS = ("node.fetch", "node.store")


def read(ctx):
    per_tile = [[s["meta"]["queued_s"] for s in rec.spans
                 if s["name"] in NODE_SPANS and "queued_s" in s["meta"]]
                for rec in ctx["driver"].window_tiles() if rec.spans]
    if not any(per_tile):
        return None
    return 1e3 * sum(map(sum, per_tile)) / len(per_tile)
