"""Record the small profiler trace the trace-reduction test reads.

    python3 bench/tests/record_fixture.py <out_dir>     # on a TPU

A Pallas kernel and an XLA matmul, each called three times inside
``bench.*`` host spans with short sleeps between, inside a ``bench.window``
span; the ``.xplane.pb`` is copied to ``<out_dir>/fixture.xplane.pb``.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


@jax.jit
def kernel_call(x):
    return pl.pallas_call(_double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


@jax.jit
def matmul(x):
    return (x @ x).sum()


def main(out_dir: str) -> None:
    x = jnp.ones((512, 512), jnp.float32)
    kernel_call(x).block_until_ready()
    matmul(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.fixture.kernel"):
                kernel_call(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.fixture.sleep"):
                time.sleep(0.005)
            with jax.profiler.TraceAnnotation("bench.fixture.matmul"):
                matmul(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, f"{out_dir}/fixture.xplane.pb")


if __name__ == "__main__":
    main(sys.argv[1])
