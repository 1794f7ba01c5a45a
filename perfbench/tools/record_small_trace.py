#!/usr/bin/env python3
"""Record the small trace that tests/test_trace_reduction.py reads: a few
matmuls and one elementwise pass on the chip, with gaps between them in which
the host sleeps under a `bench/` annotation. Writes
chiprun_out/small.xplane.pb; copy it to perfbench/tests/data/.

    python3 perfbench/tools/record_small_trace.py
"""

import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from perfbench.harness import trace
    matmul = jax.jit(lambda a: a @ a, )
    scale = jax.jit(lambda a: a * 2.0 + 1.0)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    matmul(x).block_until_ready()
    scale(x).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="small_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench/work"):
            y = matmul(x)
            if i % 2:
                y = scale(y)
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench/sleep"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    dest = os.path.join(ROOT, "chiprun_out", "small.xplane.pb")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    shutil.copy(trace.find_xplane(log_dir), dest)
    print(trace.describe(dest))
    shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
