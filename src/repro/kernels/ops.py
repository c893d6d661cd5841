"""Jit'd public wrappers around the Pallas kernels.

Two entry points:

``winograd_deconv2d_fused`` — same signature and semantics as
core.winograd_deconv2d but with the Winograd-domain engine running as a
fused Pallas kernel.  ``backend='ref'`` dispatches to the pure-jnp oracle
instead (useful under jit on CPU); ``interpret=True`` runs the real kernel
body in interpret mode (correctness on CPU).

``prepack`` + ``winograd_deconv2d_packed`` — the production training/serving
path.  ``prepack`` runs the G-transform and zero-skipping pack ONCE,
returning a :class:`PackedDeconv` pytree; ``winograd_deconv2d_packed``
consumes it directly, so a training step (or a serving call) never re-runs
``transform_weights``/``pack_weights``.  Gradients w.r.t. the packed weights
are produced by the Pallas backward engines — the whole step stays in the
Winograd domain.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tdc import (
    ConvDims,
    DeconvDims,
    conv_plan,
    decompose_weights_1d,
    interleave_crop,
    plan,
    plan_1d,
)
from repro.core.winograd import get_transform
from repro.core.winograd_deconv import (
    transform_conv_weights,
    transform_input_tiles,
    transform_weights,
)

from . import ref as _ref
from .engine import (
    winograd_conv1d_fused_bwd_w,
    winograd_conv1d_fused_bwd_x,
    winograd_conv1d_fused_engine,
)
from .winograd_deconv import (
    EPILOGUE_ACTIVATIONS,
    winograd_conv_fused_bwd_w,
    winograd_conv_fused_bwd_x,
    winograd_conv_fused_engine,
    winograd_domain_engine,
    winograd_domain_engine_bwd_w,
    winograd_domain_engine_bwd_x,
    winograd_fused_pre_engine,
    winograd_fused_pre_engine_bwd_w,
    winograd_fused_pre_engine_bwd_x,
)

__all__ = [
    "pack_weights",
    "unpack_weights",
    "winograd_deconv2d_fused",
    "winograd_deconv2d_packed",
    "winograd_deconv2d_cells",
    "packed_layout",
    "cells_layout",
    "cells_from_image",
    "cells_to_next",
    "chain_aligned",
    "PackedDeconv",
    "prepack",
    "pack_conv_weights",
    "conv_packed_layout",
    "PackedConv",
    "prepack_conv",
    "winograd_conv2d",
    "winograd_conv2d_packed",
    "winograd_conv2d_cells",
    "conv_cells_from_image",
    "conv_cells_to_next",
    "conv_chain_aligned",
    "cells_window_mask",
    "conv1d_layout",
    "packed_deconv1d_layout",
    "pack_conv1d_weights",
    "pack_deconv1d_weights",
    "PackedConv1d",
    "prepack_conv1d",
    "prepack_deconv1d",
    "conv1d_cells",
    "winograd_conv1d",
    "winograd_conv1d_packed",
    "winograd_deconv1d",
    "winograd_deconv1d_packed",
    "EPILOGUE_ACTIVATIONS",
    "INTERPRET_BLOCKS",
    "INTERPRET_BLOCKS_FUSED",
    "INTERPRET_BLOCKS_1D",
]

# CPU-feasible tilings for interpret-mode runs (models' *_interpret impls
# and the CPU benchmark profiles share these — keep them in one place).
INTERPRET_BLOCKS = dict(block_t=16, block_n=8, block_m=8)
INTERPRET_BLOCKS_FUSED = dict(block_ty=4, block_n=8, block_m=8)
# conv engine (the discriminator): emulated wall time scales with grid-step
# count, and the trunk's tile-row extents (32 down to 1) fit one block, so
# a taller tile-row block is strictly fewer interpret steps
INTERPRET_BLOCKS_CONV = dict(block_ty=16, block_n=8, block_m=8)
# 1D engines (audio/SSM): a single tile-row axis, so the same reasoning as
# the conv engine — one tall block per sequence
INTERPRET_BLOCKS_1D = dict(block_ty=16, block_n=8, block_m=8)


@functools.lru_cache(maxsize=None)
def packed_layout(dims: DeconvDims, m: int = 2, r: int = 3):
    """Static packed layout for (K_D, S): position indices, sub-filter slices
    and the packed inverse-transform rows.

    Returns (pos_idx, sub_slices, inv_packed_np, keep_per_sub).
    """
    sp = plan(dims, m, r)
    tf = get_transform(m, r)
    n = tf.n
    AT = np.asarray(tf.AT)
    pos_idx: list[int] = []
    sub_slices: list[tuple[int, int]] = []
    inv_rows: list[np.ndarray] = []
    keeps: list[list[tuple[int, int]]] = []
    for ry in range(dims.stride):
        for rx in range(dims.stride):
            mask = sp.masks_winograd[ry, rx]
            keep = [(u, v) for u in range(n) for v in range(n) if mask[u, v]]
            lo = len(pos_idx)
            for u, v in keep:
                pos_idx.append(u * n + v)
                inv_rows.append(np.outer(AT[:, u], AT[:, v]).reshape(m * m))
            sub_slices.append((lo, len(pos_idx)))
            keeps.append(keep)
    inv_packed = (
        np.stack(inv_rows).astype(np.float32)
        if inv_rows
        else np.zeros((0, m * m), np.float32)
    )
    return tuple(pos_idx), tuple(sub_slices), inv_packed, keeps


@functools.lru_cache(maxsize=None)
def _pack_gather_idx(dims: DeconvDims, m: int, r: int) -> np.ndarray:
    """Packed row -> flat (S*S*n*n) index into the transformed weight tensor.

    Precomputing this collapses the per-position Python loop of gathers in
    ``pack_weights`` into a single ``jnp.take`` — one gather op in the trace
    regardless of C, instead of C stacked slices."""
    pos_idx, sub_slices, _, _ = packed_layout(dims, m, r)
    n2 = get_transform(m, r).n ** 2
    idx = np.empty(len(pos_idx), np.int32)
    for s, (lo, hi) in enumerate(sub_slices):
        idx[lo:hi] = s * n2 + np.asarray(pos_idx[lo:hi], np.int32)
    return idx


def pack_weights(w: jax.Array, dims: DeconvDims, m: int = 2, r: int = 3) -> jax.Array:
    """Deconv weights (K_D,K_D,N,M) -> packed Winograd-domain (C, N, M).

    Only the C(K_C) structurally nonzero positions are stored (paper Fig. 5's
    reorganized filter layout with zero rows removed), selected by one
    precomputed index array.
    """
    idx = _pack_gather_idx(dims, m, r)
    if idx.size == 0:
        return jnp.zeros((0, *w.shape[2:]), w.dtype)
    ww = transform_weights(w, dims, m, r)  # (S,S,n,n,N,M)
    flat = ww.reshape(-1, *ww.shape[4:])  # (S*S*n*n, N, M)
    return jnp.take(flat, jnp.asarray(idx), axis=0).astype(w.dtype)


class PackedDeconv(NamedTuple):
    """Pre-packed Winograd-domain deconv weights (a pytree).

    ``ww`` is the trainable leaf — its cotangent comes straight out of the
    Pallas backward engine, so optimizing it keeps the whole training step in
    the Winograd domain.  ``inv`` is the static packed inverse-transform
    (gradient always zero); it rides along so apply sites need no layout
    lookup.
    """

    ww: jax.Array  # (C, N, M) packed transformed weights
    inv: jax.Array  # (C, m2) fp32 inverse-transform rows


def prepack(w: jax.Array, dims: DeconvDims, m: int = 2, r: int = 3) -> PackedDeconv:
    """One-time G-transform + zero-skipping pack of raw deconv weights."""
    _, _, inv_np, _ = packed_layout(dims, m, r)
    return PackedDeconv(pack_weights(w, dims, m, r), jnp.asarray(inv_np))


# ------------------------------------------------------------------ VJPs
# Forward = Pallas engine; backward = the Pallas backward engines (both
# cotangents are packed Winograd-domain contractions on the same grid
# machinery — see kernels/winograd_deconv.py).  ref.py never runs here.


def _layer_tag(kind: str, kernel: int, stride: int, ww) -> str:
    """``<kind>_k<K>s<S>_<N>to<M>`` (``ww`` the packed (..., N, M) weight):
    the geometry tag the engines name their kernels by."""
    return f"{kind}_k{kernel}s{stride}_{ww.shape[-2]}to{ww.shape[-1]}"


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13)
)
def _engine_vjp(
    xw, ww, inv, pos_idx, sub_slices, m2, interpret, bt, bn, bm,
    bwd_bt, bwd_bn, bwd_bm, layer,
):
    return winograd_domain_engine(
        xw, ww, inv, pos_idx=pos_idx, sub_slices=sub_slices, m2=m2,
        interpret=interpret, block_t=bt, block_n=bn, block_m=bm, layer=layer,
    )


def _engine_fwd(
    xw, ww, inv, pos_idx, sub_slices, m2, interpret, bt, bn, bm,
    bwd_bt, bwd_bn, bwd_bm, layer,
):
    y = _engine_vjp(
        xw, ww, inv, pos_idx, sub_slices, m2, interpret, bt, bn, bm,
        bwd_bt, bwd_bn, bwd_bm, layer,
    )
    return y, (xw, ww, inv)


def _engine_bwd(
    pos_idx, sub_slices, m2, interpret, bt, bn, bm, bwd_bt, bwd_bn, bwd_bm,
    layer, res, g,
):
    xw, ww, inv = res
    dxw = winograd_domain_engine_bwd_x(
        g, ww, inv, pos_idx=pos_idx, sub_slices=sub_slices, m2=m2,
        n2=xw.shape[1], interpret=interpret,
        block_t=bwd_bt, block_n=bwd_bn, block_m=bwd_bm, layer=layer,
    )
    dww = winograd_domain_engine_bwd_w(
        xw, g, inv, pos_idx=pos_idx, sub_slices=sub_slices, m2=m2,
        interpret=interpret, block_t=bwd_bt, block_n=bwd_bn, block_m=bwd_bm,
        layer=layer,
    )
    return dxw.astype(xw.dtype), dww.astype(ww.dtype), jnp.zeros_like(inv)


_engine_vjp.defvjp(_engine_fwd, _engine_bwd)


def cells_layout(x_pad: jax.Array, ty: int, tx: int, m: int, n: int) -> jax.Array:
    """Padded NHWC image -> the fused engine's cell layout (B, Gy, Gx, m*m, N).

    Pure reshape/transpose (space-to-depth by the tile stride m) — XLA fuses
    it into the producing op, so unlike ``transform_input_tiles`` nothing
    tile-overlapping ever materializes in HBM.
    """
    B, Hp, Wp, N = x_pad.shape
    q = -(-n // m)
    gy, gx = ty + q - 1, tx + q - 1
    need_h, need_w = gy * m, gx * m
    x_pad = jnp.pad(
        x_pad,
        ((0, 0), (0, max(0, need_h - Hp)), (0, max(0, need_w - Wp)), (0, 0)),
    )[:, :need_h, :need_w, :]
    return jnp.transpose(
        x_pad.reshape(B, gy, m, gx, m, N), (0, 1, 3, 2, 4, 5)
    ).reshape(B, gy, gx, m * m, N)


def cells_from_image(x: jax.Array, dims: DeconvDims, m: int = 2, r: int = 3) -> jax.Array:
    """NHWC input -> the fused engine's padded cell layout for ``dims``:
    the deconv left-pad (kc-1) plus the tile-coverage right-pad, then
    ``cells_layout`` — the standard prologue of the fuse_pre path."""
    tf = get_transform(m, r)
    B, H, W, N = x.shape
    hj, wj = dims.j_extent(H), dims.j_extent(W)
    ty, tx = -(-hj // m), -(-wj // m)
    kc = dims.kc
    x_pad = jnp.pad(
        x,
        (
            (0, 0),
            (kc - 1, max(0, m * (ty - 1) + tf.n - (H + kc - 1))),
            (kc - 1, max(0, m * (tx - 1) + tf.n - (W + kc - 1))),
            (0, 0),
        ),
    )
    return cells_layout(x_pad, ty, tx, m, tf.n).astype(x.dtype)


def chain_aligned(dims: DeconvDims, next_dims: DeconvDims, m: int = 2) -> bool:
    """True when layer ``dims``'s emitted cell layout lines up with layer
    ``next_dims``'s input cell layout on whole-cell boundaries.

    The next layer's padded input row i equals this layer's padded-interleave
    row i + d with d = P - (kc' - 1); when d is a multiple of the cell stride
    m the conversion is a pure cell-row slice (``cells_to_next``), i.e. zero
    relayout.  All stride-2 paper geometries (K5S2 -> K5S2, K4S2 -> K4S2)
    have d = 0; ArtGAN's trailing K4S2 -> K3S1 hop has d = -1 and falls back
    to the XLA relayout.
    """
    return (dims.padding - (next_dims.kc - 1)) % m == 0


def cells_to_next(
    emitted: jax.Array,  # (B, >=ty*S, tx*S, m*m, >=M) from emit_cells
    dims: DeconvDims,
    next_dims: DeconvDims,
    out_hw: tuple[int, int],  # this layer's (H_O, W_O) = next layer's input
    m: int = 2,
    r: int = 3,
) -> jax.Array:
    """Turn an ``emit_cells`` output into the next layer's input cell layout
    — whole cell rows/cols only, so XLA sees at most a slice, never a
    relayout.  Requires ``chain_aligned``.

    The pallas emit_cells output arrives *raw* (block-padded rows/channels,
    all zero past the crop window); when the shift d is 0 and it already
    covers the next layer's extent it passes through untouched — the next
    engine call pads/crops to its own block geometry, so an aligned chain
    hop costs zero XLA copies."""
    if not chain_aligned(dims, next_dims, m):
        raise ValueError(
            f"cell layouts misaligned: P={dims.padding} vs kc'={next_dims.kc} "
            f"shift not divisible by m={m}"
        )
    tf = get_transform(m, r)
    HO, WO = out_hw
    hj2, wj2 = next_dims.j_extent(HO), next_dims.j_extent(WO)
    ty2, tx2 = -(-hj2 // m), -(-wj2 // m)
    q = -(-tf.n // m)
    gy2, gx2 = ty2 + q - 1, tx2 + q - 1
    d = (dims.padding - (next_dims.kc - 1)) // m
    GyE, GxE = emitted.shape[1], emitted.shape[2]
    if d == 0 and GyE >= gy2 and GxE >= gx2:
        return emitted  # extra rows/cols/channels are zero: engine absorbs
    pad_before = max(0, -d)
    arr = jnp.pad(
        emitted,
        (
            (0, 0),
            (pad_before, max(0, d + gy2 - GyE)),
            (pad_before, max(0, d + gx2 - GxE)),
            (0, 0),
            (0, 0),
        ),
    )
    start = d + pad_before
    return arr[:, start : start + gy2, start : start + gx2]


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18),
)
def _fused_pre_vjp(
    cells, ww, inv, bt_mat, pos_idx, sub_slices, m, n, ty, tx, m2,
    interpret, bty, bn, bm, bwd_bty, bwd_bn, bwd_bm, layer,
):
    """Fused pre-PE engine with a custom VJP; both cotangents run as fused
    Pallas kernels too (the input cotangent emits the cell layout directly)."""
    return winograd_fused_pre_engine(
        cells, ww, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty, tx=tx, m2=m2,
        interpret=interpret, block_ty=bty, block_n=bn, block_m=bm, layer=layer,
    )


def _fused_pre_fwd(
    cells, ww, inv, bt_mat, pos_idx, sub_slices, m, n, ty, tx, m2,
    interpret, bty, bn, bm, bwd_bty, bwd_bn, bwd_bm, layer,
):
    y = _fused_pre_vjp(
        cells, ww, inv, bt_mat, pos_idx, sub_slices, m, n, ty, tx, m2,
        interpret, bty, bn, bm, bwd_bty, bwd_bn, bwd_bm, layer,
    )
    return y, (cells, ww, inv)


def _fused_pre_bwd(
    bt_mat, pos_idx, sub_slices, m, n, ty, tx, m2, interpret, bty, bn, bm,
    bwd_bty, bwd_bn, bwd_bm, layer, res, g,
):
    cells, ww, inv = res
    gy, gx = cells.shape[1], cells.shape[2]
    dcells = winograd_fused_pre_engine_bwd_x(
        g, ww, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty, tx=tx,
        gy=gy, gx=gx, m2=m2, interpret=interpret,
        block_ty=bwd_bty, block_n=bwd_bn, block_m=bwd_bm, layer=layer,
    )
    dww = winograd_fused_pre_engine_bwd_w(
        cells, g, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty, tx=tx, m2=m2,
        interpret=interpret, block_ty=bwd_bty, block_n=bwd_bn, block_m=bwd_bm,
        layer=layer,
    )
    return dcells.astype(cells.dtype), dww.astype(ww.dtype), jnp.zeros_like(inv)


_fused_pre_vjp.defvjp(_fused_pre_fwd, _fused_pre_bwd)


# ------------------------------------------------- epilogue-fused engine VJP
# Forward: the epilogue-fused Pallas engine (post-PE + affine + activation +
# depth-to-space in VMEM, NHWC pixels or next-layer cells out).  Backward:
# an *activation-cotangent prologue* in XLA (act'/affine from the saved
# post-activation output, inverse interleave back to the scratch layout),
# then the existing fused Pallas backward engines — no new backward kernels.


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(5, 22)))
def _fused_epi_vjp(
    cells, ww, inv, scale, bias, bt_mat, pos_idx, sub_slices, m, n, ty, tx,
    m2, out_mode, activation, stride, padding, out_h, out_w, interpret, blocks,
    layer,
):
    bty, bn, bm = blocks[:3]
    return winograd_fused_pre_engine(
        cells, ww, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty, tx=tx, m2=m2,
        block_ty=bty, block_n=bn, block_m=bm, interpret=interpret,
        out_mode=out_mode, activation=activation, scale=scale, bias=bias,
        stride=stride, padding=padding, out_h=out_h, out_w=out_w, layer=layer,
    )


def _fused_epi_fwd(
    cells, ww, inv, scale, bias, bt_mat, pos_idx, sub_slices, m, n, ty, tx,
    m2, out_mode, activation, stride, padding, out_h, out_w, interpret, blocks,
    layer,
):
    y = _fused_epi_vjp(
        cells, ww, inv, scale, bias, bt_mat, pos_idx, sub_slices, m, n, ty,
        tx, m2, out_mode, activation, stride, padding, out_h, out_w,
        interpret, blocks, layer,
    )
    # the post-activation output doubles as the activation residual: every
    # supported activation's derivative (and, for the scale cotangent, its
    # pre-activation value wherever the derivative is nonzero) is recoverable
    # from it, so no second engine output is needed
    return y, (cells, ww, inv, scale, bias, y)


def _epilogue_cotangent(g_img, y_img, scale, bias, activation, M):
    """Activation-cotangent prologue shared by the deconv and conv epilogue
    VJPs: from the output cotangent and the SAVED post-activation output
    (both fp32 images), recover the pre-affine cotangent plus the scale and
    bias cotangents.  Returns (g_aff, dscale, dbias)."""
    from .winograd_deconv import LEAKY_SLOPE

    f32 = jnp.float32
    if activation == "relu":
        dact, pre = (y_img > 0).astype(f32), y_img
    elif activation == "leaky_relu":
        dact = jnp.where(y_img >= 0, 1.0, LEAKY_SLOPE)
        pre = jnp.where(y_img >= 0, y_img, y_img / LEAKY_SLOPE)
    elif activation == "tanh":
        dact = 1.0 - y_img * y_img
        pre = jnp.arctanh(jnp.clip(y_img, -1.0 + 1e-6, 1.0 - 1e-6))
    else:
        dact, pre = jnp.ones_like(y_img), y_img
    dpre = g_img * dact
    sc = jnp.ones((M,), f32) if scale is None else scale.astype(f32)
    bi = jnp.zeros((M,), f32) if bias is None else bias.astype(f32)
    dbias = jnp.sum(dpre, axis=(0, 1, 2))
    # raw engine output v = (pre - bias) / scale; where act' = 0 the value of
    # v is irrelevant (dpre = 0), so the relu information loss is harmless.
    # An exactly-zero scale channel destroys v entirely — its true dscale is
    # unrecoverable from the saved activation, so it gets 0 instead of a NaN
    # that would poison the whole leaf through the optimizer's global norm
    # (zero-scale channels carry no signal; the unfused XLA-epilogue path
    # remains exact for that degenerate case).
    sc_safe = jnp.where(sc == 0, 1.0, sc)
    v = jnp.where(sc == 0, 0.0, (pre - bi) / sc_safe)
    dscale = jnp.sum(dpre * v, axis=(0, 1, 2))
    return dpre * sc, dscale, dbias


def _fused_epi_bwd(
    bt_mat, pos_idx, sub_slices, m, n, ty, tx, m2, out_mode, activation,
    stride, padding, out_h, out_w, interpret, blocks, layer, res, g,
):
    cells, ww, inv, scale, bias, y_out = res
    _, _, _, bwd_bty, bwd_bn, bwd_bm = blocks
    S, ms = stride, m * stride
    B, M = cells.shape[0], ww.shape[2]
    f32 = jnp.float32

    if out_mode == "cells":
        def uncell(c):  # raw cells out -> padded-interleave coords
            # the forward's raw output is block-padded past ty*S rows and M
            # channels; everything there is identically zero regardless of
            # the inputs, so cotangents for it are dropped
            c = c[:, : ty * S, :, :, :M]
            return jnp.transpose(
                c.reshape(B, ty * S, tx * S, m, m, M), (0, 1, 3, 2, 4, 5)
            ).reshape(B, ty * ms, tx * ms, M)

        g_img = uncell(g.astype(f32))
        y_img = uncell(y_out.astype(f32))
        # the forward zeroed everything outside the crop window, so the
        # cotangent there must not flow back
        g_img = jnp.pad(
            g_img[:, padding : padding + out_h, padding : padding + out_w, :],
            (
                (0, 0),
                (padding, ty * ms - padding - out_h),
                (padding, tx * ms - padding - out_w),
                (0, 0),
            ),
        )
    else:
        g_img = g.astype(f32)  # (B, ty*m*S, tx*m*S, M)
        y_img = y_out.astype(f32)

    # --- activation-cotangent prologue (from the post-activation value)
    g_aff, dscale, dbias = _epilogue_cotangent(
        g_img, y_img, scale, bias, activation, M
    )

    # --- inverse interleave: back to the (B, ty, tx, S2*m2, M) scratch layout
    g_scr = jnp.transpose(
        g_aff.reshape(B, ty, m, S, tx, m, S, M), (0, 1, 4, 3, 6, 2, 5, 7)
    ).reshape(B, ty, tx, S * S * m * m, M).astype(g.dtype)

    gy, gx = cells.shape[1], cells.shape[2]
    dcells = winograd_fused_pre_engine_bwd_x(
        g_scr, ww, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty, tx=tx,
        gy=gy, gx=gx, m2=m2, interpret=interpret,
        block_ty=bwd_bty, block_n=bwd_bn, block_m=bwd_bm, layer=layer,
    )
    if dcells.shape[-1] < cells.shape[-1]:
        # a chained input carries block-padded trailing channels the engine
        # contracts against zero weight rows — their cotangent is zero
        dcells = jnp.pad(
            dcells,
            ((0, 0),) * 4 + ((0, cells.shape[-1] - dcells.shape[-1]),),
        )
    dww = winograd_fused_pre_engine_bwd_w(
        cells, g_scr, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty, tx=tx, m2=m2,
        interpret=interpret, block_ty=bwd_bty, block_n=bwd_bn, block_m=bwd_bm,
        layer=layer,
    )[:, : ww.shape[1], :]  # chained inputs may be channel-padded past N
    ds = None if scale is None else dscale.astype(scale.dtype)
    db = None if bias is None else dbias.astype(bias.dtype)
    return (
        dcells.astype(cells.dtype), dww.astype(ww.dtype), jnp.zeros_like(inv),
        ds, db,
    )


_fused_epi_vjp.defvjp(_fused_epi_fwd, _fused_epi_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "dims", "in_hw", "m", "r", "backend", "interpret", "epilogue",
        "emit_cells", "block_ty", "block_n", "block_m",
        "bwd_block_ty", "bwd_block_n", "bwd_block_m",
    ),
)
def winograd_deconv2d_cells(
    cells: jax.Array,  # (B, Gy, Gx, m*m, N) this layer's input cell layout
    packed: PackedDeconv,
    dims: DeconvDims,
    in_hw: tuple[int, int],  # the (H, W) the cells were built from
    *,
    m: int = 2,
    r: int = 3,
    backend: str = "pallas",
    interpret: bool = False,
    epilogue: str = "none",
    scale: jax.Array | None = None,  # (M,) per-channel epilogue scale
    bias: jax.Array | None = None,  # (M,) per-channel epilogue bias
    emit_cells: bool = False,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    bwd_block_ty: int | None = None,
    bwd_block_n: int | None = None,
    bwd_block_m: int | None = None,
) -> jax.Array:
    """Cell-to-cell chained deconv: consume the fused engine's cell layout
    directly (e.g. the previous layer's ``emit_cells`` output via
    ``cells_to_next``), run the epilogue-fused engine, and return either the
    final NHWC image (B, H_O, W_O, M) or — with ``emit_cells`` — the next
    layer's cell layout, never leaving the engine domain.
    """
    tf = get_transform(m, r)
    H, W = in_hw
    HO, WO = dims.out_size(H), dims.out_size(W)
    hj, wj = dims.j_extent(H), dims.j_extent(W)
    ty, tx = -(-hj // m), -(-wj // m)
    m2 = m * m
    pos_idx, sub_slices, _, _ = packed_layout(dims, m, r)
    bt_mat = tuple(tuple(float(v) for v in row) for row in tf.BT)
    out_mode = "cells" if emit_cells else "nhwc"
    if backend == "pallas":
        blocks = (
            block_ty, block_n, block_m,
            block_ty if bwd_block_ty is None else bwd_block_ty,
            block_n if bwd_block_n is None else bwd_block_n,
            block_m if bwd_block_m is None else bwd_block_m,
        )
        y = _fused_epi_vjp(
            cells, packed.ww, packed.inv, scale, bias, bt_mat, pos_idx,
            sub_slices, m, tf.n, ty, tx, m2, out_mode, epilogue, dims.stride,
            dims.padding, HO, WO, interpret, blocks,
            _layer_tag("deconv", dims.kernel, dims.stride, packed.ww),
        )
    elif backend == "ref":
        y = _ref.fused_epilogue_engine_ref(
            cells, packed.ww, packed.inv, bt_mat, scale, bias,
            pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=tf.n, ty=ty, tx=tx,
            m2=m2, out_mode=out_mode, activation=epilogue, stride=dims.stride,
            padding=dims.padding, out_h=HO, out_w=WO,
        )
    else:
        raise ValueError(backend)
    if emit_cells:
        return y
    P = dims.padding
    return y[:, P : P + HO, P : P + WO, :]


@functools.partial(
    jax.jit,
    static_argnames=(
        "dims", "m", "r", "backend", "interpret", "fuse_pre",
        "epilogue", "emit_cells",
        "block_t", "block_n", "block_m", "block_ty",
        "bwd_block_t", "bwd_block_n", "bwd_block_m", "bwd_block_ty",
    ),
)
def winograd_deconv2d_packed(
    x: jax.Array,
    packed: PackedDeconv,
    dims: DeconvDims,
    *,
    m: int = 2,
    r: int = 3,
    backend: str = "pallas",
    interpret: bool = False,
    fuse_pre: bool = False,
    epilogue: str | None = None,
    scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    emit_cells: bool = False,
    block_t: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    block_ty: int = 8,
    bwd_block_t: int | None = None,
    bwd_block_n: int | None = None,
    bwd_block_m: int | None = None,
    bwd_block_ty: int | None = None,
) -> jax.Array:
    """Winograd DeConv from pre-packed weights.  x: (B,H,W,N).

    The apply half of the prepack-then-apply API: no G-transform, no pack —
    the packed (C, N, M) weights go straight to the engine, and ``jax.grad``
    w.r.t. ``packed.ww`` comes straight out of the Pallas backward engine
    (training in the Winograd domain).  ``bwd_block_*`` tile the backward
    kernels; ``None`` mirrors the forward choice.

    ``epilogue`` (an activation name) with optional per-channel ``scale`` /
    ``bias`` computes act(scale * deconv(x) + bias); with ``fuse_pre`` on the
    pallas/ref backends it runs inside the engine finalize (bias, activation
    and the depth-to-space interleave never touch HBM separately), elsewhere
    it falls back to an XLA epilogue.  ``emit_cells`` (fuse_pre only)
    returns the next layer's cell layout instead of the NHWC image — see
    ``winograd_deconv2d_cells`` / ``cells_to_next`` for chaining.
    """
    tf = get_transform(m, r)
    B, H, W, N = x.shape
    M = packed.ww.shape[-1]
    S = dims.stride
    HO, WO = dims.out_size(H), dims.out_size(W)
    hj, wj = dims.j_extent(H), dims.j_extent(W)
    ty, tx = -(-hj // m), -(-wj // m)
    kc = dims.kc

    wants_epi = (
        emit_cells or epilogue is not None or scale is not None
        or bias is not None
    )
    if wants_epi and fuse_pre and backend in ("pallas", "ref"):
        return winograd_deconv2d_cells(
            cells_from_image(x, dims, m, r), packed, dims, (H, W),
            m=m, r=r, backend=backend, interpret=interpret,
            epilogue=epilogue or "none", scale=scale, bias=bias,
            emit_cells=emit_cells, block_ty=block_ty, block_n=block_n,
            block_m=block_m, bwd_block_ty=bwd_block_ty,
            bwd_block_n=bwd_block_n, bwd_block_m=bwd_block_m,
        )
    if emit_cells:
        raise ValueError("emit_cells requires fuse_pre with a pallas/ref backend")

    pos_idx, sub_slices, _, _ = packed_layout(dims, m, r)
    x_pad = jnp.pad(
        x,
        (
            (0, 0),
            (kc - 1, max(0, m * (ty - 1) + tf.n - (H + kc - 1))),
            (kc - 1, max(0, m * (tx - 1) + tf.n - (W + kc - 1))),
            (0, 0),
        ),
    )
    m2 = m * m
    bwd_t = block_t if bwd_block_t is None else bwd_block_t
    bwd_n = block_n if bwd_block_n is None else bwd_block_n
    bwd_m = block_m if bwd_block_m is None else bwd_block_m
    bwd_ty = block_ty if bwd_block_ty is None else bwd_block_ty
    layer = _layer_tag("deconv", dims.kernel, S, packed.ww)
    if fuse_pre:
        cells = cells_layout(x_pad, ty, tx, m, tf.n).astype(x.dtype)
        bt_mat = tuple(tuple(float(v) for v in row) for row in tf.BT)
        if backend == "pallas":
            y = _fused_pre_vjp(
                cells, packed.ww, packed.inv, bt_mat, pos_idx, sub_slices,
                m, tf.n, ty, tx, m2, interpret, block_ty, block_n, block_m,
                bwd_ty, bwd_n, bwd_m, layer,
            )
        elif backend == "ref":
            y = _ref.fused_pre_engine_ref(
                cells, packed.ww, packed.inv, bt_mat,
                pos_idx=pos_idx, sub_slices=sub_slices,
                m=m, n=tf.n, ty=ty, tx=tx, m2=m2,
            )
        else:
            raise ValueError(backend)
        y = y.reshape(B * ty * tx, -1, M)
    else:
        xw = transform_input_tiles(x_pad, (ty, tx), m, r).astype(x.dtype)
        xw_mat = xw.reshape(B * ty * tx, tf.n * tf.n, N)
        if backend == "pallas":
            y = _engine_vjp(
                xw_mat, packed.ww, packed.inv, pos_idx, sub_slices, m2,
                interpret, block_t, block_n, block_m, bwd_t, bwd_n, bwd_m, layer,
            )
        elif backend == "ref":
            y = _ref.engine_ref(
                xw_mat, packed.ww, packed.inv,
                pos_idx=pos_idx, sub_slices=sub_slices, m2=m2,
            )
        else:
            raise ValueError(backend)

    # (T, S2*m2, M) -> (S,S,B,Ty*m,Tx*m,M) -> interleave
    y = y.reshape(B, ty, tx, S, S, m, m, M)
    y = jnp.transpose(y, (3, 4, 0, 1, 5, 2, 6, 7)).reshape(S, S, B, ty * m, tx * m, M)
    y = y[:, :, :, :hj, :wj, :].astype(x.dtype)
    out = interleave_crop(y, dims, (HO, WO))
    if wants_epi:  # unfused / other backends: XLA epilogue, same semantics
        out = _ref.epilogue_apply_ref(out, scale, bias, epilogue or "none")
    return out.astype(x.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "dims", "m", "r", "backend", "interpret", "fuse_pre",
        "epilogue", "emit_cells",
        "block_t", "block_n", "block_m", "block_ty",
        "bwd_block_t", "bwd_block_n", "bwd_block_m", "bwd_block_ty",
    ),
)
def winograd_deconv2d_fused(
    x: jax.Array,
    w: jax.Array,
    dims: DeconvDims,
    *,
    m: int = 2,
    r: int = 3,
    backend: str = "pallas",
    interpret: bool = False,
    fuse_pre: bool = False,
    epilogue: str | None = None,
    scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    emit_cells: bool = False,
    block_t: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    block_ty: int = 8,
    bwd_block_t: int | None = None,
    bwd_block_n: int | None = None,
    bwd_block_m: int | None = None,
    bwd_block_ty: int | None = None,
) -> jax.Array:
    """Winograd DeConv with the Pallas engine. x:(B,H,W,N) w:(KD,KD,N,M).

    ``fuse_pre=True`` runs the pre-PE B-transform inside the engine kernel
    (paper Fig. 7's fully fused pre/com/post-PE pipeline): the input reaches
    the kernel in the m x m cell layout and the (T, n^2, N) transformed-tile
    intermediate never materializes in HBM.  ``block_ty`` is the fused
    variant's tile-row block (its T block is block_ty * tx tiles);
    ``block_t`` blocks the unfused variant's flat tile axis.

    ``epilogue`` / ``scale`` / ``bias`` / ``emit_cells`` fuse the per-channel
    affine, activation and depth-to-space (or the next layer's cell layout)
    into the engine finalize — see ``winograd_deconv2d_packed``.

    This convenience wrapper re-packs ``w`` on every call; hot paths should
    ``prepack`` once and call ``winograd_deconv2d_packed``.
    """
    return winograd_deconv2d_packed(
        x, prepack(w, dims, m, r), dims,
        m=m, r=r, backend=backend, interpret=interpret, fuse_pre=fuse_pre,
        epilogue=epilogue, scale=scale, bias=bias, emit_cells=emit_cells,
        block_t=block_t, block_n=block_n, block_m=block_m, block_ty=block_ty,
        bwd_block_t=bwd_block_t, bwd_block_n=bwd_block_n,
        bwd_block_m=bwd_block_m, bwd_block_ty=bwd_block_ty,
    )


# ---------------------------------------------------------------------------
# Winograd Conv (the discriminator path).  A stride-S conv phase-decomposes
# into S^2 unit-stride sub-correlations over de-interleaved input phases
# that SUM into one output (core/tdc.py::conv_plan — the inverse of the TDC
# deconv-to-conv conversion), which maps onto the existing engine machinery
# with the phase pair playing the sub-filter role: packed (C, N, M) weights
# whose positions index the s2*n^2 space, one shared inverse transform, one
# m x m output tile.  Same prepack-then-apply API as the deconv side.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def conv_packed_layout(cdims: ConvDims, m: int = 2, r: int = 3):
    """Static packed layout for a strided conv: position indices into the
    s2*n^2 phase-major Winograd position space (doubling as the pack gather
    index) and the packed inverse-transform rows.

    Returns (pos_idx, inv_packed_np, plan).
    """
    sp = conv_plan(cdims, m, r)
    tf = get_transform(m, r)
    n = tf.n
    AT = np.asarray(tf.AT)
    S = cdims.stride
    pos_idx: list[int] = []
    inv_rows: list[np.ndarray] = []
    for ry in range(S):
        for rx in range(S):
            s = ry * S + rx
            mask = sp.masks_winograd[ry, rx]
            for u in range(n):
                for v in range(n):
                    if mask[u, v]:
                        pos_idx.append(s * n * n + u * n + v)
                        inv_rows.append(
                            np.outer(AT[:, u], AT[:, v]).reshape(m * m)
                        )
    inv_packed = np.stack(inv_rows).astype(np.float32)
    return tuple(pos_idx), inv_packed, sp


def pack_conv_weights(w: jax.Array, cdims: ConvDims, m: int = 2, r: int = 3) -> jax.Array:
    """Conv weights (K, K, N, M) -> packed Winograd-domain (C, N, M): only
    the structurally nonzero positions of the G-transformed phase
    sub-filters are stored (C = 36 for K4S2 vs 64 dense, 16 for K3S1)."""
    pos_idx, _, _ = conv_packed_layout(cdims, m, r)
    ww = transform_conv_weights(w, cdims, m, r)  # (S,S,n,n,N,M)
    flat = ww.reshape(-1, *ww.shape[4:])  # (S*S*n*n, N, M)
    return jnp.take(flat, jnp.asarray(pos_idx, jnp.int32), axis=0).astype(w.dtype)


class PackedConv(NamedTuple):
    """Pre-packed Winograd-domain conv weights (a pytree) — the conv mirror
    of :class:`PackedDeconv`: ``ww`` is the trainable leaf, ``inv`` the
    static packed inverse transform."""

    ww: jax.Array  # (C, N, M)
    inv: jax.Array  # (C, m2) fp32


def prepack_conv(w: jax.Array, cdims: ConvDims, m: int = 2, r: int = 3) -> PackedConv:
    """One-time G-transform + zero-skipping pack of raw conv weights."""
    _, inv_np, _ = conv_packed_layout(cdims, m, r)
    return PackedConv(pack_conv_weights(w, cdims, m, r), jnp.asarray(inv_np))


@functools.lru_cache(maxsize=None)
def _unpack_matrix(dims, m: int, r: int) -> np.ndarray:
    """(K^2, C) least-squares inverse of the linear pack map w -> ww_packed
    (spatial taps only: the map acts independently per (N, M) pair).  The
    pack is injective (G has full column rank and every tap reaches some
    kept position), so pinv recovers raw weights exactly from consistently
    packed ones and least-squares-projects arbitrary trained ones."""
    K = dims.kernel
    pack = pack_conv_weights if isinstance(dims, ConvDims) else pack_weights
    cols = []
    for k in range(K * K):
        basis = np.zeros((K, K, 1, 1), np.float32)
        basis[k // K, k % K, 0, 0] = 1.0
        cols.append(np.asarray(pack(jnp.asarray(basis), dims, m, r)).reshape(-1))
    return np.linalg.pinv(np.stack(cols, axis=1))


def unpack_weights(ww_packed: jax.Array, dims, m: int = 2, r: int = 3) -> jax.Array:
    """Packed Winograd-domain (C, N, M) -> raw (K, K, N, M) weights via
    least squares through the G-transform + pack (checkpoint-export inverse
    of ``pack_weights`` / ``pack_conv_weights``; ``dims`` picks the family).
    """
    K = dims.kernel
    pinv = jnp.asarray(_unpack_matrix(dims, m, r), ww_packed.dtype)
    w = jnp.einsum("kc,cnm->knm", pinv, ww_packed.astype(pinv.dtype))
    return w.reshape(K, K, *ww_packed.shape[1:]).astype(ww_packed.dtype)


def cells_window_mask(rows: int, cols: int, m: int, padding: int,
                      out_h: int, out_w: int) -> jax.Array:
    """(rows, cols, m*m, 1) fp32 crop-window mask of an emitted cell layout:
    cell (rr, cc) intra (pp, qq) holds pixel (m*rr + pp, m*cc + qq), valid in
    [padding, padding + out_h) x [padding, padding + out_w) — the host-side
    mirror of the in-kernel masks (used by the two-pass chained BN, which
    must re-zero out-of-window cells after its XLA affine+activation)."""
    r_io = jnp.arange(rows, dtype=jnp.int32)[:, None, None, None]
    c_io = jnp.arange(cols, dtype=jnp.int32)[None, :, None, None]
    a_io = jnp.arange(m * m, dtype=jnp.int32)[None, None, :, None]
    row_px = m * r_io + a_io // m
    col_px = m * c_io + a_io % m
    return (
        (row_px >= padding) & (row_px < padding + out_h)
        & (col_px >= padding) & (col_px < padding + out_w)
    ).astype(jnp.float32)


def conv_cells_from_image(x: jax.Array, cdims: ConvDims, m: int = 2, r: int = 3) -> jax.Array:
    """NHWC input -> the conv engine's phase-major cell layout
    (B, Gy, Gx, S^2*m*m, N): de-interleave the S^2 input phases, permute
    them into tap-residue pair order, left-pad every phase by L cells and
    space-to-depth each by the tile stride m.  Pure pad/reshape/transpose —
    XLA fuses it into the producing op."""
    tf = get_transform(m, r)
    B, H, W, N = x.shape
    S, L = cdims.stride, cdims.phase_pad
    HO, WO = cdims.out_size(H), cdims.out_size(W)
    ty, tx = -(-HO // m), -(-WO // m)
    q = -(-tf.n // m)
    gy, gx = ty + q - 1, tx + q - 1
    hp = max(-(-H // S), gy * m - L)
    wp = max(-(-W // S), gx * m - L)
    xp = jnp.pad(x, ((0, 0), (0, S * hp - H), (0, S * wp - W), (0, 0)))
    phases = jnp.transpose(
        xp.reshape(B, hp, S, wp, S, N), (0, 2, 4, 1, 3, 5)
    )  # (B, phi_y, phi_x, hp, wp, N)
    perm = jnp.asarray([cdims.phase_of(rho) for rho in range(S)], jnp.int32)
    pairs = jnp.take(jnp.take(phases, perm, axis=1), perm, axis=2)
    pairs = jnp.pad(pairs, ((0, 0), (0, 0), (0, 0), (L, 0), (L, 0), (0, 0)))
    pairs = pairs[:, :, :, : gy * m, : gx * m, :]
    cells = pairs.reshape(B, S, S, gy, m, gx, m, N)
    return jnp.transpose(cells, (0, 3, 5, 1, 2, 4, 6, 7)).reshape(
        B, gy, gx, S * S * m * m, N
    ).astype(x.dtype)


def conv_chain_aligned(cdims: ConvDims, next_cdims: ConvDims, m: int = 2) -> bool:
    """True when this conv layer's emitted output-image cell layout converts
    to the next conv layer's phase-cell layout by a pure (static) cell-level
    gather — i.e. with no pixel-level re-split.  Holds whenever the next
    stride equals the cell stride m (the discriminator's stride-2 trunk
    under F(2,3): output cells ARE the next layer's phase pairs), or for a
    unit-stride hop whose pad is cell-aligned."""
    if next_cdims.stride == m:
        return True
    if next_cdims.stride == 1:
        return next_cdims.padding % m == 0
    return False


def conv_cells_to_next(
    emitted: jax.Array,  # (B, >=ty, >=tx, m*m, >=M) from emit_cells
    cdims: ConvDims,
    next_cdims: ConvDims,
    out_hw: tuple[int, int],  # this layer's (H_O, W_O) = next layer's input
    m: int = 2,
    r: int = 3,
) -> jax.Array:
    """Turn a conv ``emit_cells`` output into the next conv layer's
    phase-major cell layout.  Requires ``conv_chain_aligned``: with
    S' == m each emitted cell row IS one phase row of the next layer
    (de-interleave = intra-cell axis relabel, a transpose), so the hop
    costs one XLA gather over an already-cell-resident tensor instead of
    the NHWC materialize + re-pad + space-to-depth of the generic path."""
    if not conv_chain_aligned(cdims, next_cdims, m):
        raise ValueError(
            f"conv cell layouts misaligned: next stride {next_cdims.stride} "
            f"pad {next_cdims.padding} vs cell stride m={m}"
        )
    tf = get_transform(m, r)
    HO, WO = out_hw
    S2n, L2 = next_cdims.stride, next_cdims.phase_pad
    HO2, WO2 = next_cdims.out_size(HO), next_cdims.out_size(WO)
    ty2, tx2 = -(-HO2 // m), -(-WO2 // m)
    q = -(-tf.n // m)
    gy2, gx2 = ty2 + q - 1, tx2 + q - 1
    B = emitted.shape[0]
    nch = emitted.shape[-1]
    if S2n == 1:
        lc = next_cdims.padding // m  # cell-aligned by conv_chain_aligned
        arr = jnp.pad(
            emitted,
            (
                (0, 0),
                (lc, max(0, gy2 - lc - emitted.shape[1])),
                (lc, max(0, gx2 - lc - emitted.shape[2])),
                (0, 0),
                (0, 0),
            ),
        )
        return arr[:, :gy2, :gx2]
    # S' == m: emitted cell (m*g + p - L2) intra (phi_y, phi_x) is next
    # phase-pair pixel (m*g + p, m*gx' + q) — pad by L2 CELL rows, regroup.
    arr = jnp.pad(
        emitted,
        (
            (0, 0),
            (L2, max(0, gy2 * m - L2 - emitted.shape[1])),
            (L2, max(0, gx2 * m - L2 - emitted.shape[2])),
            (0, 0),
            (0, 0),
        ),
    )[:, : gy2 * m, : gx2 * m]
    arr = arr.reshape(B, gy2, m, gx2, m, m, m, nch)  # (b,g,p,gx',q,phiy,phix,ch)
    perm = jnp.asarray([next_cdims.phase_of(rho) for rho in range(S2n)], jnp.int32)
    arr = jnp.take(jnp.take(arr, perm, axis=5), perm, axis=6)  # phases -> pairs
    return jnp.transpose(arr, (0, 1, 3, 5, 6, 2, 4, 7)).reshape(
        B, gy2, gx2, m * m * m * m, nch
    )


# -------------------------------------------------- conv engine custom VJP
# Forward: the fused conv engine.  Backward: the shared activation-cotangent
# prologue in XLA, then the conv Pallas backward engines — jax.grad of the
# discriminator never runs a reference conv.


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(5, 19)))
def _conv_epi_vjp(
    cells, ww, inv, scale, bias, bt_mat, pos_idx, m, n, ty, tx, s2,
    out_mode, activation, out_h, out_w, interpret, blocks, layer,
):
    bty, bn, bm = blocks[:3]
    return winograd_conv_fused_engine(
        cells, ww, inv, bt_mat,
        pos_idx=pos_idx, m=m, n=n, ty=ty, tx=tx, s2=s2,
        block_ty=bty, block_n=bn, block_m=bm, interpret=interpret,
        out_mode=out_mode, activation=activation, scale=scale, bias=bias,
        out_h=out_h, out_w=out_w, layer=layer,
    )


def _conv_epi_fwd(
    cells, ww, inv, scale, bias, bt_mat, pos_idx, m, n, ty, tx, s2,
    out_mode, activation, out_h, out_w, interpret, blocks, layer,
):
    y = _conv_epi_vjp(
        cells, ww, inv, scale, bias, bt_mat, pos_idx, m, n, ty, tx, s2,
        out_mode, activation, out_h, out_w, interpret, blocks, layer,
    )
    return y, (cells, ww, inv, scale, bias, y)


def _conv_epi_bwd(
    bt_mat, pos_idx, m, n, ty, tx, s2, out_mode, activation, out_h, out_w,
    interpret, blocks, layer, res, g,
):
    cells, ww, inv, scale, bias, y_out = res
    _, _, _, bwd_bty, bwd_bn, bwd_bm = blocks
    B, M = cells.shape[0], ww.shape[2]
    f32 = jnp.float32

    if out_mode == "cells":
        def uncell(c):  # raw cells out -> output-image pixels
            c = c[:, :ty, :tx, :, :M]
            return jnp.transpose(
                c.reshape(B, ty, tx, m, m, M), (0, 1, 3, 2, 4, 5)
            ).reshape(B, ty * m, tx * m, M)

        g_img = uncell(g.astype(f32))
        y_img = uncell(y_out.astype(f32))
        # the forward zeroed everything outside the crop window
        g_img = jnp.pad(
            g_img[:, :out_h, :out_w, :],
            ((0, 0), (0, ty * m - out_h), (0, tx * m - out_w), (0, 0)),
        )
    else:
        g_img = g.astype(f32)  # (B, ty*m, tx*m, M)
        y_img = y_out.astype(f32)

    g_aff, dscale, dbias = _epilogue_cotangent(
        g_img, y_img, scale, bias, activation, M
    )
    g_scr = jnp.transpose(
        g_aff.reshape(B, ty, m, tx, m, M), (0, 1, 3, 2, 4, 5)
    ).reshape(B, ty, tx, m * m, M).astype(g.dtype)

    gy, gx = cells.shape[1], cells.shape[2]
    dcells = winograd_conv_fused_bwd_x(
        g_scr, ww, inv, bt_mat,
        pos_idx=pos_idx, m=m, n=n, ty=ty, tx=tx, gy=gy, gx=gx, s2=s2,
        interpret=interpret, block_ty=bwd_bty, block_n=bwd_bn, block_m=bwd_bm,
        layer=layer,
    )
    if dcells.shape[-1] < cells.shape[-1]:
        # a chained input carries block-padded trailing channels the engine
        # contracts against zero weight rows — their cotangent is zero
        dcells = jnp.pad(
            dcells,
            ((0, 0),) * 4 + ((0, cells.shape[-1] - dcells.shape[-1]),),
        )
    dww = winograd_conv_fused_bwd_w(
        cells, g_scr, inv, bt_mat,
        pos_idx=pos_idx, m=m, n=n, ty=ty, tx=tx, s2=s2,
        interpret=interpret, block_ty=bwd_bty, block_n=bwd_bn, block_m=bwd_bm,
        layer=layer,
    )[:, : ww.shape[1], :]  # chained inputs may be channel-padded past N
    ds = None if scale is None else dscale.astype(scale.dtype)
    db = None if bias is None else dbias.astype(bias.dtype)
    return (
        dcells.astype(cells.dtype), dww.astype(ww.dtype), jnp.zeros_like(inv),
        ds, db,
    )


_conv_epi_vjp.defvjp(_conv_epi_fwd, _conv_epi_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cdims", "in_hw", "m", "r", "backend", "interpret", "epilogue",
        "emit_cells", "block_ty", "block_n", "block_m",
        "bwd_block_ty", "bwd_block_n", "bwd_block_m",
    ),
)
def winograd_conv2d_cells(
    cells: jax.Array,  # (B, Gy, Gx, S^2*m*m, N) phase-major cell layout
    packed: PackedConv,
    cdims: ConvDims,
    in_hw: tuple[int, int],  # the (H, W) the cells were built from
    *,
    m: int = 2,
    r: int = 3,
    backend: str = "pallas",
    interpret: bool = False,
    epilogue: str = "none",
    scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    emit_cells: bool = False,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    bwd_block_ty: int | None = None,
    bwd_block_n: int | None = None,
    bwd_block_m: int | None = None,
) -> jax.Array:
    """Cell-to-cell chained Winograd conv: consume the phase-major cell
    layout directly (e.g. a previous conv layer's ``emit_cells`` output via
    ``conv_cells_to_next``), run the fused engine, and return the NHWC
    image (B, H_O, W_O, M) or — with ``emit_cells`` — the output image's
    cell layout for the next chained layer."""
    tf = get_transform(m, r)
    H, W = in_hw
    HO, WO = cdims.out_size(H), cdims.out_size(W)
    ty, tx = -(-HO // m), -(-WO // m)
    s2 = cdims.stride ** 2
    pos_idx, _, _ = conv_packed_layout(cdims, m, r)
    bt_mat = tuple(tuple(float(v) for v in row) for row in tf.BT)
    out_mode = "cells" if emit_cells else "nhwc"
    if backend == "pallas":
        blocks = (
            block_ty, block_n, block_m,
            block_ty if bwd_block_ty is None else bwd_block_ty,
            block_n if bwd_block_n is None else bwd_block_n,
            block_m if bwd_block_m is None else bwd_block_m,
        )
        y = _conv_epi_vjp(
            cells, packed.ww, packed.inv, scale, bias, bt_mat, pos_idx,
            m, tf.n, ty, tx, s2, out_mode, epilogue, HO, WO, interpret, blocks,
            _layer_tag("conv", cdims.kernel, cdims.stride, packed.ww),
        )
    elif backend == "ref":
        y = _ref.conv_engine_ref(
            cells, packed.ww, packed.inv, bt_mat, scale, bias,
            pos_idx=pos_idx, m=m, n=tf.n, ty=ty, tx=tx, s2=s2,
            out_mode=out_mode, activation=epilogue, out_h=HO, out_w=WO,
        )
    else:
        raise ValueError(backend)
    if emit_cells:
        return y
    return y[:, :HO, :WO, :]


@functools.partial(
    jax.jit,
    static_argnames=(
        "cdims", "m", "r", "backend", "interpret", "epilogue", "emit_cells",
        "block_ty", "block_n", "block_m",
        "bwd_block_ty", "bwd_block_n", "bwd_block_m",
    ),
)
def winograd_conv2d_packed(
    x: jax.Array,  # (B, H, W, N) NHWC
    packed: PackedConv,
    cdims: ConvDims,
    *,
    m: int = 2,
    r: int = 3,
    backend: str = "pallas",
    interpret: bool = False,
    epilogue: str | None = None,
    scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    emit_cells: bool = False,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    bwd_block_ty: int | None = None,
    bwd_block_n: int | None = None,
    bwd_block_m: int | None = None,
) -> jax.Array:
    """Strided Winograd conv from pre-packed weights: the discriminator
    mirror of ``winograd_deconv2d_packed``.  ``epilogue``/``scale``/``bias``
    fuse the per-channel affine (conv bias, folded eval BN) + activation
    into the engine finalize; ``emit_cells`` chains into the next conv
    layer via ``conv_cells_to_next``."""
    return winograd_conv2d_cells(
        conv_cells_from_image(x, cdims, m, r), packed, cdims,
        (x.shape[1], x.shape[2]),
        m=m, r=r, backend=backend, interpret=interpret,
        epilogue=epilogue or "none", scale=scale, bias=bias,
        emit_cells=emit_cells, block_ty=block_ty, block_n=block_n,
        block_m=block_m, bwd_block_ty=bwd_block_ty, bwd_block_n=bwd_block_n,
        bwd_block_m=bwd_block_m,
    )


def winograd_conv2d(
    x: jax.Array,
    w: jax.Array,  # (K, K, N, M) conv weights (cross-correlation)
    cdims: ConvDims,
    **kw,
) -> jax.Array:
    """Convenience wrapper that re-packs ``w`` on every call; hot paths
    should ``prepack_conv`` once and call ``winograd_conv2d_packed``."""
    return winograd_conv2d_packed(x, prepack_conv(w, cdims), cdims, **kw)


# ---------------------------------------------------------------------------
# 1D Winograd (de)conv (audio/SSM stacks) — the rank-1 instantiations of the
# engine core.  Stride-1 conv1d (the Mamba2 d_conv causal conv) is one
# sub-filter spanning all n positions; 1D TDC deconv (the MusicGen-style
# audio decoder) is the 1D analogue of the deconv path: S flipped
# sub-kernels packed by structural nonzeros, outputs interleaving in the
# engine finalize.  Same prepack-then-apply API as the 2D families; the
# engine core is LINEAR here (activation/bias stay in XLA where jax.grad
# handles them), so the custom VJP has only the three engine cotangents.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def conv1d_layout(kernel: int, m: int = 2):
    """Static packed layout of a stride-1 conv1d under F(m, K): every one of
    the n = m + K - 1 Winograd positions is kept (a dense 1D kernel has no
    structural zeros), one sub-filter spans them all.

    Returns (pos_idx, sub_slices, inv_packed_np, bt_mat, n).
    """
    tf = get_transform(m, kernel)
    n = tf.n
    AT = np.asarray(tf.AT)  # (m, n)
    inv = np.ascontiguousarray(AT.T).astype(np.float32)  # (n, m)
    bt_mat = tuple(tuple(float(v) for v in row) for row in tf.BT)
    return tuple(range(n)), ((0, n),), inv, bt_mat, n


@functools.lru_cache(maxsize=None)
def packed_deconv1d_layout(dims: DeconvDims, m: int = 2, r: int = 3):
    """Static packed layout of a 1D TDC deconv: position indices into the
    shared n-space, per-residue sub-filter slices, and the packed 1D
    inverse-transform rows (only the structurally nonzero positions of each
    transformed sub-kernel are kept — the 1D analogue of Fig. 5's pack).

    Returns (pos_idx, sub_slices, inv_packed_np, keeps).
    """
    sp = plan_1d(dims, m, r)
    tf = get_transform(m, r)
    n = tf.n
    AT = np.asarray(tf.AT)
    pos_idx: list[int] = []
    sub_slices: list[tuple[int, int]] = []
    inv_rows: list[np.ndarray] = []
    keeps: list[list[int]] = []
    for rho in range(dims.stride):
        mask = sp.masks_winograd[rho]
        keep = [u for u in range(n) if mask[u]]
        lo = len(pos_idx)
        for u in keep:
            pos_idx.append(u)
            inv_rows.append(AT[:, u])
        sub_slices.append((lo, len(pos_idx)))
        keeps.append(keep)
    inv = (
        np.stack(inv_rows).astype(np.float32)
        if inv_rows
        else np.zeros((0, m), np.float32)
    )
    return tuple(pos_idx), tuple(sub_slices), inv, keeps


def pack_conv1d_weights(w: jax.Array, kernel: int, m: int = 2) -> jax.Array:
    """Conv1d weights (K, N, M) -> packed Winograd-domain (n, N, M) via the
    1D G-transform (dense: every position is structurally nonzero)."""
    if w.shape[0] != kernel:
        raise ValueError(f"weight tap dim {w.shape[0]} != K={kernel}")
    tf = get_transform(m, kernel)
    G = jnp.asarray(np.asarray(tf.G), jnp.float32)  # (n, r)
    return jnp.einsum("ur,rnm->unm", G, w.astype(jnp.float32)).astype(w.dtype)


def pack_deconv1d_weights(w: jax.Array, dims: DeconvDims, m: int = 2, r: int = 3) -> jax.Array:
    """Deconv1d weights (K_D, N, M) -> packed Winograd-domain (C, N, M):
    decompose into the S flipped sub-kernels, G-transform each, keep only
    the structurally nonzero rows."""
    pos_idx, sub_slices, _, keeps = packed_deconv1d_layout(dims, m, r)
    tf = get_transform(m, r)
    G = jnp.asarray(np.asarray(tf.G), jnp.float32)
    subw = decompose_weights_1d(w, dims, r)  # (S, r, N, M)
    wt = jnp.einsum("ur,srnm->sunm", G, subw.astype(jnp.float32))  # (S, n, N, M)
    flat = wt.reshape(-1, *wt.shape[2:])  # (S*n, N, M)
    idx = np.asarray(
        [rho * tf.n + u for rho, keep in enumerate(keeps) for u in keep],
        np.int32,
    )
    if idx.size == 0:
        return jnp.zeros((0, *w.shape[1:]), w.dtype)
    return jnp.take(flat, jnp.asarray(idx), axis=0).astype(w.dtype)


class PackedConv1d(NamedTuple):
    """Pre-packed Winograd-domain 1D (de)conv weights (a pytree) — the 1D
    mirror of :class:`PackedDeconv`: ``ww`` is the trainable leaf, ``inv``
    the static packed 1D inverse transform."""

    ww: jax.Array  # (C, N, M)
    inv: jax.Array  # (C, m) fp32


def prepack_conv1d(w: jax.Array, kernel: int, m: int = 2) -> PackedConv1d:
    """One-time 1D G-transform of raw stride-1 conv1d weights (K, N, M)."""
    _, _, inv_np, _, _ = conv1d_layout(kernel, m)
    return PackedConv1d(pack_conv1d_weights(w, kernel, m), jnp.asarray(inv_np))


def prepack_deconv1d(w: jax.Array, dims: DeconvDims, m: int = 2, r: int = 3) -> PackedConv1d:
    """One-time G-transform + zero-skipping pack of raw deconv1d weights."""
    _, _, inv_np, _ = packed_deconv1d_layout(dims, m, r)
    return PackedConv1d(pack_deconv1d_weights(w, dims, m, r), jnp.asarray(inv_np))


def conv1d_cells(x_pad: jax.Array, ty: int, m: int, n: int) -> jax.Array:
    """Padded (B, Lp, N) sequence -> the 1D engine's cell layout
    (B, Gy, m, N): space-to-depth by the tile stride m (pure reshape)."""
    B, Lp, N = x_pad.shape
    q = -(-n // m)
    gy = ty + q - 1
    need = gy * m
    x_pad = jnp.pad(x_pad, ((0, 0), (0, max(0, need - Lp)), (0, 0)))[:, :need, :]
    return x_pad.reshape(B, gy, m, N)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 12)))
def _engine1d_vjp(
    cells, ww, inv, bt_mat, pos_idx, sub_slices, m, n, ty, stride, interpret_blocks,
    layer,
):
    """1D fused engine with a custom VJP: forward in "nlc" mode (the padded
    interleave), dL/dww through the rank-agnostic Pallas domain backward,
    dL/dcells through the same plus the cheap rank-1 host-side B-scatter."""
    interpret, blocks = interpret_blocks
    bty, bn, bm = blocks[:3]
    return winograd_conv1d_fused_engine(
        cells, ww, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty,
        block_ty=bty, block_n=bn, block_m=bm, interpret=interpret,
        out_mode="nlc", stride=stride, layer=layer,
    )


def _engine1d_fwd(
    cells, ww, inv, bt_mat, pos_idx, sub_slices, m, n, ty, stride, interpret_blocks,
    layer,
):
    y = _engine1d_vjp(
        cells, ww, inv, bt_mat, pos_idx, sub_slices, m, n, ty, stride,
        interpret_blocks, layer,
    )
    return y, (cells, ww, inv)


def _engine1d_bwd(
    bt_mat, pos_idx, sub_slices, m, n, ty, stride, interpret_blocks, layer, res, g,
):
    cells, ww, inv = res
    interpret, blocks = interpret_blocks
    bwd_bt, bwd_bn, bwd_bm = blocks[3:]
    B = cells.shape[0]
    S = stride
    # inverse of the nlc interleave (row m*S*j + S*p + rho) back to the
    # scratch tile layout's sub-filter-major rows (rho*m + p)
    g_scr = jnp.transpose(
        g.reshape(B, ty, m, S, g.shape[-1]), (0, 1, 3, 2, 4)
    ).reshape(B, ty, S * m, g.shape[-1])
    dcells = winograd_conv1d_fused_bwd_x(
        g_scr, ww, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty,
        gy=cells.shape[1], block_t=bwd_bt, block_n=bwd_bn, block_m=bwd_bm,
        interpret=interpret, layer=layer,
    )
    if dcells.shape[-1] < cells.shape[-1]:
        # a chained input carries block-padded trailing channels the engine
        # contracts against zero weight rows — their cotangent is zero
        dcells = jnp.pad(
            dcells, ((0, 0),) * 3 + ((0, cells.shape[-1] - dcells.shape[-1]),)
        )
    dww = winograd_conv1d_fused_bwd_w(
        cells, g_scr, inv, bt_mat,
        pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty,
        block_t=bwd_bt, block_n=bwd_bn, block_m=bwd_bm, interpret=interpret,
        layer=layer,
    )[:, : ww.shape[1], :]  # chained inputs may be channel-padded past N
    return dcells.astype(cells.dtype), dww.astype(ww.dtype), jnp.zeros_like(inv)


_engine1d_vjp.defvjp(_engine1d_fwd, _engine1d_bwd)


def _conv1d_pads(kernel: int, padding: str) -> tuple[int, int]:
    if padding == "causal":
        return kernel - 1, 0
    if padding == "same":
        return (kernel - 1) // 2, kernel - 1 - (kernel - 1) // 2
    if padding == "valid":
        return 0, 0
    raise ValueError(padding)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernel", "m", "padding", "backend", "interpret",
        "block_ty", "block_n", "block_m",
        "bwd_block_ty", "bwd_block_n", "bwd_block_m",
    ),
)
def winograd_conv1d_packed(
    x: jax.Array,  # (B, L, N)
    packed: PackedConv1d,
    kernel: int,
    *,
    m: int = 2,
    padding: str = "causal",  # "causal" | "same" | "valid"
    backend: str = "pallas",
    interpret: bool = False,
    block_ty: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    bwd_block_ty: int | None = None,
    bwd_block_n: int | None = None,
    bwd_block_m: int | None = None,
) -> jax.Array:
    """Stride-1 Winograd conv1d from pre-packed weights: x (B, L, N) ->
    (B, L_O, M) with L_O = L (causal/same) or L - K + 1 (valid).

    ``causal`` left-pads K-1 (the SSM prefill convention: output t sees
    inputs (t-K+1..t]); ``same`` splits the pad low-first like ``lax``.
    The engine is linear — bias/activation belong outside, where ``jax.grad``
    differentiates them for free and the custom VJP handles only the
    Winograd-domain cotangents."""
    pos_idx, sub_slices, _, bt_mat, n = conv1d_layout(kernel, m)
    B, L, N = x.shape
    pad_lo, pad_hi = _conv1d_pads(kernel, padding)
    LO = L + pad_lo + pad_hi - (kernel - 1)
    ty = -(-LO // m)
    x_pad = jnp.pad(
        x, ((0, 0), (pad_lo, max(0, m * (ty - 1) + n - (L + pad_lo))), (0, 0))
    )
    cells = conv1d_cells(x_pad, ty, m, n).astype(x.dtype)
    if backend == "pallas":
        blocks = (
            block_ty, block_n, block_m,
            block_ty if bwd_block_ty is None else bwd_block_ty,
            block_n if bwd_block_n is None else bwd_block_n,
            block_m if bwd_block_m is None else bwd_block_m,
        )
        y = _engine1d_vjp(
            cells, packed.ww, packed.inv, bt_mat, pos_idx, sub_slices,
            m, n, ty, 1, (interpret, blocks),
            _layer_tag("conv1d", kernel, 1, packed.ww),
        )
    elif backend == "ref":
        y = _ref.conv1d_engine_ref(
            cells, packed.ww, packed.inv, bt_mat,
            pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=n, ty=ty, stride=1,
        )
    else:
        raise ValueError(backend)
    return y[:, :LO, :].astype(x.dtype)


def winograd_conv1d(
    x: jax.Array,
    w: jax.Array,  # (K, N, M) conv1d weights (cross-correlation taps)
    *,
    m: int = 2,
    **kw,
) -> jax.Array:
    """Convenience wrapper that re-packs ``w`` on every call; hot paths
    should ``prepack_conv1d`` once and call ``winograd_conv1d_packed``."""
    return winograd_conv1d_packed(
        x, prepack_conv1d(w, w.shape[0], m), w.shape[0], m=m, **kw
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "dims", "m", "r", "backend", "interpret",
        "block_ty", "block_n", "block_m",
        "bwd_block_ty", "bwd_block_n", "bwd_block_m",
    ),
)
def winograd_deconv1d_packed(
    x: jax.Array,  # (B, L, N)
    packed: PackedConv1d,
    dims: DeconvDims,
    *,
    m: int = 2,
    r: int = 3,
    backend: str = "pallas",
    interpret: bool = False,
    block_ty: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    bwd_block_ty: int | None = None,
    bwd_block_n: int | None = None,
    bwd_block_m: int | None = None,
) -> jax.Array:
    """1D TDC Winograd deconv from pre-packed weights: x (B, L, N) ->
    (B, L_O, M) with L_O = S*(L-1) + K_D - 2P + OP — the audio decoder's
    upsampling layer, running the S sub-correlations in the engine and the
    stride-S interleave in its finalize."""
    tf = get_transform(m, r)
    pos_idx, sub_slices, _, _ = packed_deconv1d_layout(dims, m, r)
    bt_mat = tuple(tuple(float(v) for v in row) for row in tf.BT)
    B, L, N = x.shape
    kc = dims.kc
    LO = dims.out_size(L)
    lj = dims.j_extent(L)
    ty = -(-lj // m)
    x_pad = jnp.pad(
        x, ((0, 0), (kc - 1, max(0, m * (ty - 1) + tf.n - (L + kc - 1))), (0, 0))
    )
    cells = conv1d_cells(x_pad, ty, m, tf.n).astype(x.dtype)
    if backend == "pallas":
        blocks = (
            block_ty, block_n, block_m,
            block_ty if bwd_block_ty is None else bwd_block_ty,
            block_n if bwd_block_n is None else bwd_block_n,
            block_m if bwd_block_m is None else bwd_block_m,
        )
        y = _engine1d_vjp(
            cells, packed.ww, packed.inv, bt_mat, pos_idx, sub_slices,
            m, tf.n, ty, dims.stride, (interpret, blocks),
            _layer_tag("deconv1d", dims.kernel, dims.stride, packed.ww),
        )
    elif backend == "ref":
        y = _ref.conv1d_engine_ref(
            cells, packed.ww, packed.inv, bt_mat,
            pos_idx=pos_idx, sub_slices=sub_slices, m=m, n=tf.n, ty=ty,
            stride=dims.stride,
        )
    else:
        raise ValueError(backend)
    P = dims.padding
    return y[:, P : P + LO, :].astype(x.dtype)


def winograd_deconv1d(
    x: jax.Array,
    w: jax.Array,  # (K_D, N, M) deconv1d weights
    dims: DeconvDims,
    **kw,
) -> jax.Array:
    """Convenience wrapper that re-packs ``w`` on every call; hot paths
    should ``prepack_deconv1d`` once and call ``winograd_deconv1d_packed``."""
    return winograd_deconv1d_packed(x, prepack_deconv1d(w, dims, **{
        k: v for k, v in kw.items() if k in ("m", "r")
    }), dims, **kw)
