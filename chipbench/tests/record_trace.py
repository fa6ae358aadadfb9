"""Record the small trace that ``test_xplane.py`` reads.  Run on the chip:

    chiprun --chips 1 -- python3 chipbench/tests/record_trace.py

Three steps of a small jitted program under the benchmark's own spans, with
a host sleep under ``chipbench.sleep`` before the last step, so that the
trace holds one long idle gap whose cause is known.  Writes
``chiprun_out/small.xplane.pb``; copy it to ``chipbench/tests/data/``.
"""

import glob
import os
import shutil
import time

import jax
import jax.numpy as jnp

STEPS, SLEEP_S = 3, 0.05
OUT = "chiprun_out"


@jax.jit
def step(x):
    y = jnp.tanh(x @ x)
    return y / jnp.linalg.norm(y)


def main():
    x = step(jnp.ones((1024, 1024), jnp.bfloat16))
    x.block_until_ready()
    trace_dir = os.path.join(OUT, "small_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(STEPS):
        if i == STEPS - 1:
            with jax.profiler.TraceAnnotation("chipbench.sleep"):
                time.sleep(SLEEP_S)
        with jax.profiler.TraceAnnotation("chipbench.dispatch"):
            x = step(x)
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            x.block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(path, os.path.join(OUT, "small.xplane.pb"))
    print(f"{jax.devices()[0].device_kind}: {os.path.getsize(path)} bytes")


if __name__ == "__main__":
    main()
