"""Record ``tpu_small.xplane.pb``: three runs of one small jitted program,
each inside ``bench.job``, inside ``bench.window`` with 20 ms of host time
before and after. Run on one TPU chip:

    python3 bench/tests/fixtures/make_trace.py <out.xplane.pb>
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

f = jax.jit(lambda x, i: (x @ x).at[i].add(1.0).sum())
x = jnp.ones((512, 512))
i = jnp.arange(64)
f(x, i).block_until_ready()
with tempfile.TemporaryDirectory() as tdir:
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.02)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.job"):
                f(x, i).block_until_ready()
            time.sleep(0.002)
        time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(tdir + "/**/*.xplane.pb", recursive=True)[0],
                sys.argv[1])
