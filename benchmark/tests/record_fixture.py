"""How ``data/tpu_v5e_small.xplane.pb`` was recorded: a few runs of one small
jitted program (a scan, so its ``while`` holds nested operations) on one
TPU chip under ``jax.profiler``, with the harness's anchor annotation.

    chiprun -- python3 benchmark/tests/record_fixture.py

It writes the trace and a listing of its planes and lines to
``chiprun_out/fixture/``; the trace was then copied to ``data/`` by hand.
"""

from __future__ import annotations

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import xplane
    from benchmark.harness.phases import ANCHOR

    if jax.devices()[0].platform != "tpu":
        print(f"needs a TPU; JAX found {jax.devices()}", file=sys.stderr)
        return 1

    @jax.jit
    def fixture_program(x):
        def step(c, _):
            return c + jnp.sin(c) @ jnp.ones((128, 128), c.dtype), None
        return jax.lax.scan(step, x, None, length=8)[0]

    x = jnp.ones((128, 128), jnp.float32)
    fixture_program(x).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation(ANCHOR):
        t_anchor = time.perf_counter()
    for _ in range(3):
        fixture_program(x).block_until_ready()
        time.sleep(0.01)
    t_end = time.perf_counter()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(out)
    data = xplane.load(path)
    with open(os.path.join(out, "planes.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(xplane.describe(data)) + "\n")
        f.write(f"anchor_to_end_s {t_end - t_anchor}\n")
    shutil.copy(path, os.path.join(out, "tpu_v5e_small.xplane.pb"))
    print(open(os.path.join(out, "planes.txt")).read())
    print(os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
