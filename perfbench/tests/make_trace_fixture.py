"""Record the small profiler trace that ``test_xtrace.py`` reduces.

  python3 perfbench/tests/make_trace_fixture.py OUT.xplane.pb

Run on the chip: two calls of a jitted program with a 50 ms host span
(``bench.host``) between them, inside a ``bench.window`` span. Prints the
trace's planes and lines and the reduction's numbers.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench.lib import xtrace  # noqa: E402


@jax.jit
def work(x):
    for _ in range(8):
        x = jnp.tanh(x @ x) * 0.5
    return x


def main(out: str) -> None:
    x = jnp.ones((2048, 2048), jnp.float32)
    work(x).block_until_ready()
    tmp = Path(tempfile.mkdtemp())
    jax.profiler.start_trace(str(tmp))
    with xtrace.span("window"):
        work(x).block_until_ready()
        with xtrace.span("host"):
            time.sleep(0.05)
        work(x).block_until_ready()
    jax.profiler.stop_trace()
    found = sorted(tmp.rglob("*.xplane.pb"))[-1]
    shutil.copy(found, out)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        lines = {line.name: len(list(line.events)) for line in plane.lines}
        print(plane.name, lines)
        for line in plane.lines:
            for ev in list(line.events)[:2]:
                print("   ", line.name, "|", ev.name[:70], ev.start_ns,
                      ev.duration_ns, dict(ev.stats))
    s = xtrace.reduce_file(Path(out))
    print("window_s", s.window_s, "busy_s", s.busy_s, "modules", s.module_s,
          s.module_calls, "idle", s.idle_by_host)


if __name__ == "__main__":
    main(sys.argv[1])
