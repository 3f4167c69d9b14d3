"""Records ``data/small.xplane.pb``, the trace the reduction tests read.

    python3 bench/tests/record_trace_fixture.py <out_dir>

Run on a TPU.  Inside a ``bench:window`` span: three ``bench:step`` spans,
each running a small matmul program to completion, with host sleeps
between them (the device idles there) and one ``bench:sleep`` span.
"""
import pathlib
import sys
import time

import jax
import jax.numpy as jnp

span = jax.profiler.TraceAnnotation
f = jax.jit(lambda x: jnp.tanh(x @ x))
x = jnp.ones((1024, 1024), jnp.bfloat16)
f(x).block_until_ready()
out = pathlib.Path(sys.argv[1])
jax.profiler.start_trace(str(out))
with span("bench:window"):
    for i in range(3):
        with span("bench:step"):
            y = x
            for _ in range(4):
                y = f(y)
            y.block_until_ready()
        time.sleep(0.002)
    with span("bench:sleep"):
        time.sleep(0.005)
jax.profiler.stop_trace()
