"""Record the small device trace that test_tracing.py reads.

Run once on the chip (python3 benchmark/tests/record_trace.py); it
writes benchmark/tests/data/small.xplane.pb: a few jitted steps inside
the harness's `bench/window` annotation, with a labelled host gap.
"""

import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    out = os.path.join(HERE, "data")
    tmp = os.path.join(out, "_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("host/wait"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    from benchmark import tracing

    src = tracing.find_xplane(tmp)
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp)
    print(tracing.reduce(os.path.join(out, "small.xplane.pb"), labels={"host/wait"}))


if __name__ == "__main__":
    main()
