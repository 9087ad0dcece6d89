"""Records the small TPU trace that `test_trace_reduce.py` reads
(`perfbench/tests/data/small_tpu.xplane.pb`) and prints its structure. Run on
the chip, by hand, when the profiler's format changes:

    python3 perfbench/tests/record_trace.py <out_dir>

Three executions of one jitted program (two matmuls and a reduction over
512 x 512 bf16), with a host sync after each, under the profiler options the
benchmark uses."""

import glob
import os
import shutil
import sys


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData

    @jax.jit
    def small_step(a, b):
        return jnp.sum((a @ b) @ b, dtype=jnp.float32)

    a = jnp.ones((512, 512), jnp.bfloat16)
    b = jnp.ones((512, 512), jnp.bfloat16)
    small_step(a, b).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace_dir = os.path.join(out_dir, "trace_small")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _ in range(3):
        np.asarray(small_step(a, b))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(out_dir, "small_tpu.xplane.pb"))
    print("bytes", os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for e in events[:6]:
                print("     ", e.name, e.start_ns, e.duration_ns,
                      [(k, str(v)[:120]) for k, v in e.stats][:8])


if __name__ == "__main__":
    main(sys.argv[1])
