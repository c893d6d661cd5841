"""Per-workload instantiations of the shared Winograd engine core.

Historically this module *was* the engine: ten entry points, each carrying
its own copy of the grid/halo BlockSpec construction, const-operand packing,
MXU PE loop, and finalize scaffolding.  That machinery now lives once in
``kernels/engine.py`` — parameterized by input phases, sub-filter slices,
stride/padding of the finalize interleave, and dataflow direction — and this
module keeps the original public names as declarative instantiations of it:

* the **deconv** (TDC) engines are the ``phases=1, stride=S`` corner: one
  input phase, S^2 sub-filters whose outputs interleave in the finalize;
* the **conv** engines are the ``phases=S^2, stride=1, padding=0`` corner:
  de-interleaved input phases, one sub-filter spanning all packed positions
  (the phase sum happens inside the inverse transform).

Every signature, default, and output layout below is bit-identical to the
pre-split module — the existing parity/tripwire suites lock that in.  New
callers should prefer ``repro.kernels.engine`` (or the 1D entry points it
also exports) directly.
"""
from __future__ import annotations

import jax

from .engine import (  # noqa: F401  (re-exported compat surface)
    EPILOGUE_ACTIVATIONS,
    LEAKY_SLOPE,
    domain_engine,
    domain_engine_bwd_w,
    domain_engine_bwd_x,
    fused_engine,
    fused_engine_bwd_w,
    fused_engine_bwd_x,
)

__all__ = [
    "winograd_domain_engine",
    "winograd_fused_pre_engine",
    "winograd_domain_engine_bwd_x",
    "winograd_domain_engine_bwd_w",
    "winograd_fused_pre_engine_bwd_x",
    "winograd_fused_pre_engine_bwd_w",
    "winograd_conv_fused_engine",
    "winograd_conv_fused_bwd_x",
    "winograd_conv_fused_bwd_w",
]

# The unfused domain engines were already workload-agnostic (they see only
# the packed position axis); the fused deconv engines are the engine core's
# default corner (phases=1).  Aliases, not wrappers — zero drift possible.
winograd_domain_engine = domain_engine
winograd_domain_engine_bwd_x = domain_engine_bwd_x
winograd_domain_engine_bwd_w = domain_engine_bwd_w
winograd_fused_pre_engine = fused_engine
winograd_fused_pre_engine_bwd_x = fused_engine_bwd_x
winograd_fused_pre_engine_bwd_w = fused_engine_bwd_w


def winograd_conv_fused_engine(
    cells: jax.Array,  # (B, Gy, Gx, s2*m*m, N) phase-major cell layout
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    bt_mat: tuple[tuple[float, ...], ...],
    *,
    pos_idx: tuple[int, ...],  # packed position -> s2*n2 position (len C)
    m: int,
    n: int,
    ty: int,
    tx: int,
    s2: int,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
    layer: str = "winograd",
    out_mode: str = "nhwc",  # "nhwc" | "cells"
    activation: str = "none",
    scale: jax.Array | None = None,  # (M,) per-channel epilogue scale
    bias: jax.Array | None = None,  # (M,) per-channel epilogue bias
    out_h: int = 0,  # H_O crop extent
    out_w: int = 0,
) -> jax.Array:
    """Stride-S conv as S^2 de-interleaved unit-stride phases: the strided
    corner of ``engine.fused_engine`` (stride=1, padding=0, one sub-filter
    covering all packed positions so the phases sum in the post-PE)."""
    if out_mode not in ("nhwc", "cells"):
        raise ValueError(out_mode)
    if out_h <= 0 or out_w <= 0:
        raise ValueError("winograd_conv_fused_engine needs out_h/out_w")
    return fused_engine(
        cells, ww_packed, inv_packed, bt_mat,
        pos_idx=pos_idx,
        sub_slices=((0, len(pos_idx)),),
        m=m, n=n, ty=ty, tx=tx,
        m2=inv_packed.shape[1],
        phases=s2,
        block_ty=block_ty, block_n=block_n, block_m=block_m,
        interpret=interpret,
        layer=layer,
        out_mode=out_mode, activation=activation, scale=scale, bias=bias,
        stride=1, padding=0, out_h=out_h, out_w=out_w,
    )


def winograd_conv_fused_bwd_x(
    g: jax.Array,  # (B, ty, tx, m2, M) cotangent in the scratch tile layout
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    bt_mat: tuple[tuple[float, ...], ...],
    *,
    pos_idx: tuple[int, ...],
    m: int,
    n: int,
    ty: int,
    tx: int,
    gy: int,
    gx: int,
    s2: int,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
    layer: str = "winograd",
) -> jax.Array:
    """dL/dcells of the conv engine on the generic backward builder (the
    reverse line buffer runs once per phase)."""
    return fused_engine_bwd_x(
        g, ww_packed, inv_packed, bt_mat,
        pos_idx=pos_idx,
        sub_slices=((0, len(pos_idx)),),
        m=m, n=n, ty=ty, tx=tx, gy=gy, gx=gx,
        m2=g.shape[3],
        phases=s2,
        block_ty=block_ty, block_n=block_n, block_m=block_m,
        interpret=interpret,
        layer=layer,
    )


def winograd_conv_fused_bwd_w(
    cells: jax.Array,  # (B, Gy, Gx, s2*m*m, N) the forward's cell input
    g: jax.Array,  # (B, ty, tx, m2, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    bt_mat: tuple[tuple[float, ...], ...],
    *,
    pos_idx: tuple[int, ...],
    m: int,
    n: int,
    ty: int,
    tx: int,
    s2: int,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
    layer: str = "winograd",
) -> jax.Array:
    """dL/dww_packed of the conv engine on the generic backward builder
    (phase xw recomputed from cells in VMEM)."""
    return fused_engine_bwd_w(
        cells, g, inv_packed, bt_mat,
        pos_idx=pos_idx,
        sub_slices=((0, len(pos_idx)),),
        m=m, n=n, ty=ty, tx=tx,
        m2=g.shape[3],
        phases=s2,
        block_ty=block_ty, block_n=block_n, block_m=block_m,
        interpret=interpret,
        layer=layer,
    )
