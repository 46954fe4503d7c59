"""Mean host time per step of the feed: ``DataPipeline.get_batch`` and the
host-to-device copy of the batch (the benchmark's own span)."""


def read(ctx):
    times = ctx["driver"].input_s
    return 1e3 * sum(times) / len(times) if times else None
