"""Device time per tile of the vision programs, ``difference_of_gaussians``
and ``connected_components``, from the profiler trace by module name."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    dog_s, tiles = trace.module_seconds("difference_of_gaussians")
    cc_s, _ = trace.module_seconds("connected_components")
    return 1e3 * (dog_s + cc_s) / tiles if tiles else None
