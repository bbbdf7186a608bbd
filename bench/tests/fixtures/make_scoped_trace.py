"""Record ``tpu_scoped.xplane.pb``: three runs of one small jitted
``lax.scan`` whose body has two named scopes (``adwise.window``: a sort;
``adwise.score``: a matmul) and unscoped work, each run inside
``bench.job`` and ``repro.partition_file`` (with counters as its metadata),
between 3 ms host sleeps under ``repro.init`` and ``repro.emit``, inside
``bench.window``. Run on one TPU chip:

    python3 bench/tests/fixtures/make_scoped_trace.py <out.xplane.pb>
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def body(c, w):
    with jax.named_scope("adwise.window"):
        c = jnp.sort(c, axis=1)
    with jax.named_scope("adwise.score"):
        c = jnp.tanh(c @ w)
    return c * 0.5 + 1.0, None


f = jax.jit(lambda x, w: jax.lax.scan(body, x, w)[0].sum())
x = jnp.ones((256, 256))
w = jnp.full((8, 256, 256), 1e-3)
f(x, w).block_until_ready()
note = jax.profiler.TraceAnnotation
with tempfile.TemporaryDirectory() as tdir:
    jax.profiler.start_trace(tdir)
    with note("bench.window"):
        time.sleep(0.01)
        for _ in range(3):
            with note("bench.job"), note("repro.partition_file") as job:
                with note("repro.init"):
                    time.sleep(0.003)
                f(x, w).block_until_ready()
                with note("repro.emit"):
                    time.sleep(0.003)
                job.set_metadata(host_serial_s=0.006, host_syncs=4,
                                 scan_calls=1)
        time.sleep(0.01)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(tdir + "/**/*.xplane.pb", recursive=True)[0],
                sys.argv[1])
