"""Records the small trace the reduction's test reads (run on the chip):

    python benchmark/tests/record_trace.py <out_dir>

Three bursts of a jitted matmul chain with host sleeps between them, the
second sleep inside a ``bench/sleep`` span.  Busy, idle and gap figures
of ``tiny.xplane.pb`` are worked out by hand in ``test_trace_reduce.py``.
"""

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def burst(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    burst(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = os.path.join(out_dir, "raw")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    burst(x).block_until_ready()
    time.sleep(0.02)
    burst(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench/sleep"):
        time.sleep(0.05)
    burst(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(out_dir, "tiny.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "tiny.xplane.pb")), "bytes")


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
