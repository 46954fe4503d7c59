"""Run one benchmark cell once.

  python3 perfbench/run.py --workload em.detect --seed 7 --seconds 30 --trace 0

Reads ``BENCHMARK.json`` at the root of the checkout, runs the named cell on
the chips of the machine it is started on, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: every number compared for ``correct`` beside its limit, which
also close standard error. Without a TPU, or with fewer chips than the cell
asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache lives at one fixed path inside the
# checkout (the path is part of each entry's key) that nothing else
# writes; the program's own cache placement reads the same variable.
CACHE_DIR = ROOT / ".perfbench_cache"


def configure_jax() -> None:
    """JAX's settings for every process of the benchmark, before its first
    compile."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the compiled programs' metadata keeps each operation's name stack and
    # whole Python stack, from which ``lib/hlo_scopes.py`` gives device time
    # to layers (the compilation cache's key leaves metadata out, so every
    # process that writes the cache has to set this alike)
    jax.config.update("jax_include_full_tracebacks_in_locations", True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    configure_jax()

    from perfbench.lib import harness
    bench = harness.load_benchmark()
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        harness.log(f"no chip: {e}")
        return 3
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        harness.log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
                    f"{verdict}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
