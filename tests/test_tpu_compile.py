"""Compile-only checks of the main path's kernels for a TPU v5e.

The TPU compiler compiles for a described ``v5e:2x2`` topology with no chip
attached.  What interpret mode cannot see, it refuses here: a kernel that
asks for more scoped VMEM than Mosaic allows, a slice off the tiling, a
program that does not fit the device.  Nothing runs, so these tests say
nothing about results or times.

The topology is described in a module-scoped fixture, never while a module
is imported: only one process may load the TPU library, so every pytest
worker must collect the same tests and only the one given this file may
describe the topology.  Keep every such test in this file.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.gan_zoo import DCGAN
from repro.kernels import ops
from repro.models import gan as G
from repro.optim import adamw_init
from repro.train.trainer import StepSettings, make_gan_step

B = 128  # DCGAN's published batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; the compiled text must hold
    at least one Mosaic kernel."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") > 0
    return compiled


def _fwd_and_grad(op, x, w, one_chip):
    xs = jax.ShapeDtypeStruct(x, jnp.float32, sharding=one_chip)
    ws = jax.ShapeDtypeStruct(w, jnp.float32, sharding=one_chip)
    _compile(op, xs, ws)
    _compile(jax.grad(lambda x, w: jnp.sum(op(x, w) ** 2), argnums=(0, 1)), xs, ws)


# DCGAN generator layers as (index, H=W in, N, M, fuse_pre): the chained
# pipeline's fused engine at the widest and the last layer, and the unfused
# engine at the widest, whose weight grad once overflowed the scoped VMEM
DECONV_LAYERS = [
    (0, 4, 1024, 512, True), (3, 32, 128, 3, True), (0, 4, 1024, 512, False),
]


@pytest.mark.parametrize(
    "layer", DECONV_LAYERS,
    ids=lambda c: f"deconv{c[0]}-{'fused' if c[4] else 'unfused'}",
)
def test_deconv_engine_compiles(one_chip, layer):
    i, hw, n, m, fuse_pre = layer
    dims = DCGAN.deconvs[i].dims
    op = lambda x, w: ops.winograd_deconv2d_fused(x, w, dims, fuse_pre=fuse_pre)
    _fwd_and_grad(op, (B, hw, hw, n), (dims.kernel, dims.kernel, n, m), one_chip)


@pytest.mark.parametrize("layer", range(len(G.disc_channels(DCGAN))),
                         ids=lambda i: f"conv{i}")
def test_conv_engine_compiles(one_chip, layer):
    """Every discriminator layer, the 64->128 one whose weight grad once
    overflowed the scoped VMEM among them."""
    chans = (DCGAN.img_ch, *G.disc_channels(DCGAN))
    cdims = G.disc_conv_dims(DCGAN)[layer]
    hw = DCGAN.img_hw // 2**layer
    n, m = chans[layer], chans[layer + 1]
    op = lambda x, w: ops.winograd_conv2d(x, w, cdims)
    _fwd_and_grad(op, (B, hw, hw, n), (G.DISC_KERNEL, G.DISC_KERNEL, n, m), one_chip)


def test_kernels_are_named_by_layer(one_chip):
    """Each engine kernel's HLO instruction, the name a profiler trace gives
    its device op, reads ``<kind>_k<K>s<S>_<N>to<M>_<pass>``."""
    dims, cdims = DCGAN.deconvs[3].dims, G.disc_conv_dims(DCGAN)[0]
    cases = [
        (lambda x, w: ops.winograd_deconv2d_fused(x, w, dims, fuse_pre=True),
         (8, 32, 32, 128), (dims.kernel, dims.kernel, 128, 3), "deconv_k5s2_128to3"),
        (lambda x, w: ops.winograd_conv2d(x, w, cdims),
         (8, 64, 64, 3), (G.DISC_KERNEL, G.DISC_KERNEL, 3, 64), "conv_k4s2_3to64"),
    ]
    for op, x, w, tag in cases:
        xs = jax.ShapeDtypeStruct(x, jnp.float32, sharding=one_chip)
        ws = jax.ShapeDtypeStruct(w, jnp.float32, sharding=one_chip)
        grad = jax.grad(lambda x, w: jnp.sum(op(x, w) ** 2), argnums=(0, 1))
        text = _compile(grad, xs, ws).as_text()
        names = {re.sub(r"\.\d+$", "", n) for n in re.findall(
            r'%([\w.]+) = [^\n]*custom_call_target="tpu_custom_call"', text)}
        assert names == {f"{tag}_fwd", f"{tag}_bwd_x", f"{tag}_bwd_w"}, names


def test_conv1d_engine_compiles(one_chip):
    """The 1D engine: a K=4 causal conv over 2048 steps, 512 channels."""
    op = lambda x, w: ops.winograd_conv1d(x, w)
    _fwd_and_grad(op, (8, 2048, 512), (4, 512, 512), one_chip)


def test_gan_step_compiles(one_chip):
    """The full adversarial train step at DCGAN's published widths and
    batch, both nets on the chained engines."""
    cfg = dataclasses.replace(
        DCGAN, deconv_impl="pallas_chained", conv_impl="pallas_chained"
    )
    key = jax.random.PRNGKey(0)
    gp = jax.eval_shape(lambda: G.generator_init(key, cfg))
    dp = jax.eval_shape(lambda: G.discriminator_init(key, cfg))
    args = _sds((gp, dp, jax.eval_shape(adamw_init, gp),
                 jax.eval_shape(adamw_init, dp)), one_chip)
    z = jax.ShapeDtypeStruct((B, cfg.z_dim), jnp.float32, sharding=one_chip)
    real = jax.ShapeDtypeStruct((B, cfg.img_hw, cfg.img_hw, cfg.img_ch),
                                jnp.float32, sharding=one_chip)
    step = make_gan_step(cfg, settings=StepSettings())
    compiled = step.lower(*args, z, real).compile()
    assert compiled.as_text().count("tpu_custom_call") > 0
