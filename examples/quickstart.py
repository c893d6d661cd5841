"""Quickstart: the paper's algorithm in 60 seconds.

Builds a DCGAN deconv layer, runs all DeConv implementations, verifies they
agree, and prints the multiplication counts behind the paper's speedup.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import (
    DeconvDims, plan, standard_deconv2d, tdc_deconv2d, winograd_deconv2d,
    zero_padded_deconv2d,
)
from repro.core.complexity import LayerShape, mults_tdc, mults_winograd, mults_zero_padded
from repro.device import enable_compile_cache
from repro.kernels.ops import winograd_deconv2d_fused

enable_compile_cache()

# DCGAN layer 2: 8x8x512 -> 16x16x256, K_D=5, S=2 (Table I row 1)
dims = DeconvDims(kernel=5, stride=2, padding=2, output_padding=1)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((1, 8, 8, 64)), jnp.float32)   # ch scaled for CPU
w = jnp.asarray(rng.standard_normal((5, 5, 64, 32)), jnp.float32)

ref = standard_deconv2d(x, w, dims)
print(f"output: {x.shape} -> {ref.shape}")
for name, fn in [
    ("zero-padded [10-12]", zero_padded_deconv2d),
    ("TDC [14]", tdc_deconv2d),
    ("Winograd-TDC (this paper, pure JAX)", winograd_deconv2d),
]:
    err = float(jnp.abs(fn(x, w, dims) - ref).max())
    print(f"  {name:40s} max|err| = {err:.2e}")
err = float(jnp.abs(
    winograd_deconv2d_fused(x, w, dims, interpret=True, block_t=16, block_n=8, block_m=8) - ref
).max())
print(f"  {'Winograd-TDC (Pallas kernel, interpret)':40s} max|err| = {err:.2e}")
err = float(jnp.abs(
    winograd_deconv2d_fused(x, w, dims, fuse_pre=True, interpret=True,
                            block_ty=4, block_n=8, block_m=8) - ref
).max())
print(f"  {'  + fused pre-PE (B-transform in VMEM)':40s} max|err| = {err:.2e}")

sp = plan(dims)
print(f"\nstructural sparsity for K_D=5,S=2: C(K_C) = {sp.c_total} (paper: 49), "
      f"cases = {sorted(sp.case.ravel().tolist())} (paper: one Case-1, two Case-2, one Case-3)")

l = LayerShape(8, 8, 512, 256, dims)
print(f"\nmultiplies for the full 512->256 layer:")
print(f"  zero-padded : {mults_zero_padded(l):.3e}")
print(f"  TDC         : {mults_tdc(l):.3e}")
print(f"  Winograd-TDC: {mults_winograd(l):.3e}  "
      f"({mults_zero_padded(l)/mults_winograd(l):.2f}x fewer than zero-padded)")
