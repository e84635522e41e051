"""Records ``small.xplane.pb`` on the chip: a few steps of one small jitted
program with a harness span around each.  Run through the chip tool; copy the
file from ``chiprun_out/`` to this directory."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir):
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace")
    with jax.profiler.trace(trace_dir):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                step(x).block_until_ready()
            time.sleep(0.002)
    src = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)[0]
    shutil.copy(src, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(trace_dir)
    print(os.path.getsize(os.path.join(out_dir, "small.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
