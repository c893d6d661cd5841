"""The reusable Winograd engine core: one line-buffer/PE/dataflow template.

The paper's architecture section is a *design methodology* — a single
line-buffer + PE-array + post-PE template instantiated per layer shape.
This module is that template for the TPU: it owns every piece of shared
machinery (grid/halo BlockSpec construction, block rounding, const-operand
encode/decode, the com-PE MXU loop with its batched interpret-mode fast
path, the adder-network B-transform, and the nhwc/cells finalize +
epilogue), parameterized by a small spec:

  * ``phases``     — S^k de-interleaved input phases that SUM (strided
                     conv decomposition); 1 for deconv, where the S^k
                     sub-outputs INTERLEAVE instead.
  * ``sub_slices`` — per sub-filter (start, end) ranges of the packed
                     position axis (the structural-sparsity masks).
  * ``stride``/``padding`` — the finalize's depth-to-space interleave and
                     crop-window geometry (stride=1, padding=0 for conv).
  * dataflow       — fwd (``fused_engine``), bwd_x (``fused_engine_bwd_x``,
                     reverse line-buffer halo), bwd_w
                     (``fused_engine_bwd_w``, tile recompute + T-reduce).
  * rank           — the 2D image engines above, plus the 1D sequence
                     engines (``winograd_conv1d_fused_engine`` + bwd) for
                     the audio/SSM stacks, which reuse the same com-PE /
                     post-PE stages on rank-1 transforms.

Per-workload entry points (the six 2D deconv engines and three conv
engines) live in ``kernels/winograd_deconv.py`` as declarative
instantiations of these builders.

Maps the paper's PE array (Fig. 7) onto the TPU:

  pre-PE   -> two variants.  Unfused (winograd_domain_engine): host-side
              B-transform + reorganization to the n^2 x N layout (XLA;
              cheap but bandwidth-bound — overlapping n x n tiles re-read
              every input pixel (n/m)^2 times from HBM).  Fused
              (winograd_fused_pre_engine): the engine consumes the padded
              input directly in an m x m cell layout and runs the
              B-transform in VMEM as unrolled adds — the TPU analogue of
              the paper's line buffer (Sec. V).  Both use the *packed*
              weight layout: only the C(K_C) structurally-nonzero Winograd
              positions are stored, so zero weights never reach VMEM — the
              idle-cycle skipping of Fig. 6 becomes a smaller grid of MXU
              matmuls.
  com-PE   -> this kernel: grid (T_blocks, M_blocks, N_blocks); per step an
              unrolled sequence of (T_t x N_t) @ (N_t x M_t) MXU matmuls, one
              per packed position, accumulated in fp32 VMEM scratch across
              the N grid axis (the channel-accumulate of Fig. 5).
  post-PE  -> fused sparse inverse transform on the last N step: per
              sub-filter, contract packed positions with the precomputed
              (A^T e_p A) tensors — zero output positions never computed.

The depth-to-space interleave is a pure layout op left to XLA (free on TPU:
it fuses into the following op's read).

VMEM budget per grid step (defaults T_t=128, N_t=128, M_t=128, C=49):
  xw block 128*16*128*4B = 1.0 MB, ww block 49*128*128*2B = 1.6 MB,
  scratch 49*128*128*4B = 3.2 MB, out block 128*64*128*4B = 4.2 MB -> ~10 MB,
  within the ~16 MB v5e VMEM including double-buffering headroom for in/out.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params

__all__ = [
    "LEAKY_SLOPE",
    "EPILOGUE_ACTIVATIONS",
    "domain_engine",
    "domain_engine_bwd_x",
    "domain_engine_bwd_w",
    "fused_engine",
    "fused_engine_bwd_x",
    "fused_engine_bwd_w",
    "winograd_conv1d_fused_engine",
    "winograd_conv1d_fused_bwd_x",
    "winograd_conv1d_fused_bwd_w",
]


LEAKY_SLOPE = 0.2  # must match models.layers.leaky_relu

EPILOGUE_ACTIVATIONS = ("none", "relu", "leaky_relu", "tanh")


def _apply_epilogue(y, scale, bias, activation: str):
    """Per-output-channel affine + activation in fp32 (the paper's bias/act
    stage, fused into the post-PE finalize so it runs on VMEM-resident data).
    ``scale``/``bias`` broadcast over the trailing M axis; None skips."""
    if scale is not None:
        y = y * scale
    if bias is not None:
        y = y + bias
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "leaky_relu":
        y = jnp.where(y >= 0, y, LEAKY_SLOPE * y)
    elif activation == "tanh":
        y = jnp.tanh(y)
    elif activation != "none":
        raise ValueError(f"unsupported epilogue activation {activation!r}")
    return y


# Scoped-VMEM budget.  Mosaic's default scoped limit (16 MiB on v5e) is
# below what the unrolled compiled bodies need at the published GAN widths
# (the conv bwd_w at the discriminator's 4-phase cells wants ~16.6 MiB).
# The pipeline holds two buffers of every in/out block plus the scratch, and
# the body's unrolled temporaries (stitched tiles, transformed xw, per
# position partials) are of the same order again, so each call asks for
# twice its pipeline footprint — never below the default, and well inside
# the v5e's 128 MiB of physical VMEM.
_VMEM_MIN_BYTES = 16 * 2**20
_VMEM_MAX_BYTES = 100 * 2**20


def _vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of one block: the last two dims occupy whole
    (sublane, 128-lane) tiles — 8 sublanes for 32-bit, more when packed."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = (1, 1, *shape)
    sublanes = 8 * max(1, 4 // itemsize)
    return int(np.prod(lead)) * _rup(rows, sublanes) * _rup(cols, 128) * itemsize


def _compiler_params(blocks, scratch):
    """Mosaic params for the engines' (parallel, parallel, arbitrary) grids,
    with a scoped-VMEM limit sized from ``blocks`` (every pipelined in/out
    block as (shape, dtype)) and ``scratch`` ((shape, dtype) of the VMEM
    accumulator)."""
    pipeline = 2 * sum(_vmem_bytes(s, d) for s, d in blocks) + _vmem_bytes(*scratch)
    limit = min(max(2 * pipeline, _VMEM_MIN_BYTES), _VMEM_MAX_BYTES)
    return tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit,
    )


def _const_operand(bt_mat, pos_idx):
    """Pack the static B^T matrix and packed-position indices into one tiny
    fp32 operand: Pallas kernels cannot capture array constants (even in
    interpret mode), and the batched interpret fast paths need both as
    arrays (einsum / gather / scatter-add).  Rows [0, n) hold B^T, rows
    [n, n+C) hold pos_idx (exact in fp32: positions < s2*n^2 <= 64).  The
    unrolled compiled paths never read it."""
    n = len(bt_mat)
    C = len(pos_idx)
    w = max(n, 1)
    arr = np.zeros((n + C, w), np.float32)
    if n:
        arr[:n, :n] = np.asarray(bt_mat, np.float32)
    arr[n:, 0] = np.asarray(pos_idx, np.float32)
    return arr


def _decode_consts(const_ref, n: int):
    """(B^T fp32 (n, n) or None, pos int32 (C,)) from the const operand."""
    c = const_ref[...]
    bt = c[:n, :n] if n else None
    return bt, c[n:, 0].astype(jnp.int32)


def _com_pe(xw, ww_ref, acc_ref, *, pos_idx, batched: bool = False, pos=None):
    """com-PE: one MXU matmul per packed (structurally nonzero) position.

    ``batched`` is the interpret-mode fast path: one gather + ONE batched
    dot_general over the packed axis instead of C unrolled matmuls — the
    math (each position's independent N-contraction in fp32) is identical,
    but interpret-mode wall time tracks op count, so collapsing the loop is
    the difference between the emulated engine beating or trailing the
    pure-jnp reference.  The compiled TPU path keeps the unrolled loop (one
    explicit MXU matmul per position, Fig. 5's channel-accumulate)."""
    if batched:
        x_sel = jnp.take(xw, pos, axis=1)  # (T_t, C, N_t)
        acc_ref[...] += jax.lax.dot_general(
            jnp.transpose(x_sel, (1, 0, 2)), ww_ref[...],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (C, T_t, M_t)
        return
    for p, pos in enumerate(pos_idx):
        x_p = xw[:, pos, :]  # (T_t, N_t) static row select
        w_p = ww_ref[p, :, :]  # (N_t, M_t)
        acc_ref[p, :, :] += jax.lax.dot(
            x_p, w_p, precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        )


def _post_pe_sub_outputs(acc_ref, inv_ref, sub_slices):
    """post-PE sparse inverse transform: per sub-filter the (m2, T_t, M_t)
    fp32 sub-pixel outputs, or None for structurally empty sub-filters
    (the K_D < S corner — those output pixels receive no weight taps)."""
    outs = []
    for lo, hi in sub_slices:
        if hi == lo:
            outs.append(None)
            continue
        acc = acc_ref[lo:hi, :, :]  # (c_s, T_t, M_t)
        inv = inv_ref[lo:hi, :]  # (c_s, m2)
        # y[a, t, m] = sum_p inv[p, a] * acc[p, t, m]
        outs.append(
            jax.lax.dot_general(
                inv.astype(jnp.float32),
                acc,
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
    return outs


def _com_post_pe(
    xw,  # (T_t, n2, N_t) transformed input tiles (VMEM value)
    ww_ref,  # (C, N_t, M_t) packed nonzero transformed weights
    inv_ref,  # (C, m2) fp32 inverse-transform rows
    out_ref,  # (T_t, S2*m2, M_t)
    acc_ref,  # scratch (C, T_t, M_t) fp32
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m2: int,
    n_steps: int,
    batched: bool = False,
    pos=None,
):
    """Shared com-PE + post-PE stage of both engine variants (scratch-layout
    output: per-tile sub-pixel rows, sub-filter-major)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _com_pe(xw, ww_ref, acc_ref, pos_idx=pos_idx, batched=batched, pos=pos)

    # --- post-PE: sparse inverse transform, only on the final N step
    @pl.when(k == n_steps - 1)
    def _finalize():
        ys = _post_pe_sub_outputs(acc_ref, inv_ref, sub_slices)
        for s, y in enumerate(ys):
            if y is None:  # structurally empty sub-filter (K_D < S corner)
                out_ref[:, s * m2 : (s + 1) * m2, :] = jnp.zeros(
                    (out_ref.shape[0], m2, out_ref.shape[2]), out_ref.dtype
                )
                continue
            out_ref[:, s * m2 : (s + 1) * m2, :] = jnp.transpose(
                y, (1, 0, 2)
            ).astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# Epilogue-fused finalizes.  Instead of the (T_t, S2*m2, M_t) scratch layout
# (whose depth-to-space interleave, bias and activation then run as separate
# XLA passes over HBM), the last N step applies the per-channel affine +
# activation in VMEM and writes either
#   * final NHWC pixels of the *padded interleave* (rows/cols [0, S*m*t)),
#     which the host crops to [P, P+H_O) — "nhwc"; or
#   * the next layer's padded m x m cell layout (the inverse of
#     ops.cells_layout) with everything outside the [P, P+H_O) x [P, P+W_O)
#     crop window zeroed in-kernel — "cells", so the following
#     winograd_fused_pre_engine consumes it with zero XLA relayout.
# ---------------------------------------------------------------------------


def _stack_sub_outputs(ys, m2: int):
    """(S2, m2, T_t, M_t) fp32: the post-PE outputs with structurally empty
    sub-filters filled by zeros (one stack — the assembly below is then a
    single transpose, not a web of small concatenates)."""
    t_t = next(y for y in ys if y is not None).shape[1]
    m_t = next(y for y in ys if y is not None).shape[2]
    zero = jnp.zeros((m2, t_t, m_t), jnp.float32)
    return jnp.stack([zero if y is None else y for y in ys], axis=0)


def _finalize_nhwc(
    ys,  # per sub-filter (m2, T_t, M_t) fp32 or None
    out_ref,  # (1, bty*m*S, tx*m*S, M_t)
    *,
    m: int,
    stride: int,
    tx: int,
    scale,  # (M_t,) fp32 or None
    bias,
    activation: str,
):
    """Depth-to-space in VMEM: tile (j, t) sub-pixel (s=(ry,rx), a=(p,q))
    lands at padded-interleave row m*S*j + S*p + ry, col m*S*t + S*q + rx —
    a pure transpose of the stacked post-PE outputs."""
    S = stride
    ms = m * S
    bty = out_ref.shape[1] // ms
    bm = out_ref.shape[3]
    full = _stack_sub_outputs(ys, m * m).reshape(S, S, m, m, bty, tx, bm)
    # (ry, rx, p, q, bty, tx, bm) -> (bty, p, ry, tx, q, rx, bm)
    y = jnp.transpose(full, (4, 2, 0, 5, 3, 1, 6)).reshape(bty * ms, tx * ms, bm)
    y = _apply_epilogue(y, scale, bias, activation)
    out_ref[...] = y[None].astype(out_ref.dtype)


def _finalize_cells(
    ys,  # per sub-filter (m2, T_t, M_t) fp32 or None
    out_ref,  # (1, bty*S, tx*S, m*m, M_t)
    mask,  # (bty*S, tx*S, m*m, 1) fp32 crop-window mask (precomputed host-side)
    *,
    m: int,
    stride: int,
    tx: int,
    scale,
    bias,
    activation: str,
):
    """Emit the m x m cell layout of the epilogue'd padded interleave, with
    pixels outside the [P, P+H_O) x [P, P+W_O) crop window zeroed — exactly
    what ops.cells_layout of the *next* layer's padded input holds (up to a
    whole-cell-row shift handled host-side), so layer i+1's fused pre-PE
    consumes this output directly.  The crop-window mask is static per grid
    row, so it arrives as a precomputed operand (XLA constant-folds it) and
    costs one VPU multiply here instead of an iota/compare chain."""
    S = stride
    bty = out_ref.shape[1] // S
    bm = out_ref.shape[4]
    m2c = m * m
    if S == m or S == 1:
        # interleave row S*p + ry regrouped by cells (m*gy + pp) is a pure
        # axis relabel here: S==m -> (gy, pp) = (p, ry); S==1 -> gy trivial,
        # pp = p.  One stack + one transpose covers every paper geometry.
        full = _stack_sub_outputs(ys, m2c).reshape(S, S, m, m, bty, tx, bm)
        perm = (4, 2, 5, 3, 0, 1, 6) if S == m else (4, 0, 5, 1, 2, 3, 6)
        out = jnp.transpose(full, perm).reshape(bty * S, tx * S, m2c, bm)
    else:  # general (e.g. K_D < S geometries): per-position gather
        zero = jnp.zeros((bty, tx, bm), jnp.float32)
        cellpos = []
        for pp in range(m):
            for qq in range(m):
                grid_rows = []
                for gy in range(S):
                    rl = gy * m + pp  # interleave row within the tile row
                    p, ry = rl // S, rl % S
                    grid_cols = []
                    for gx in range(S):
                        cl = gx * m + qq
                        q, rx = cl // S, cl % S
                        y_s = ys[ry * S + rx]
                        grid_cols.append(
                            zero if y_s is None else y_s[p * m + q].reshape(bty, tx, bm)
                        )
                    grid_rows.append(jnp.stack(grid_cols, axis=2))  # (bty, tx, S, bm)
                g = jnp.stack(grid_rows, axis=1)  # (bty, S, tx, S, bm)
                cellpos.append(g.reshape(bty * S, tx * S, bm))
        out = jnp.stack(cellpos, axis=2)  # (bty*S, tx*S, m*m, bm)
    out = _apply_epilogue(out, scale, bias, activation)
    out_ref[...] = (out * mask)[None].astype(out_ref.dtype)


def _engine_kernel(
    xw_ref,  # (T_t, n2, N_t) transformed input tiles
    ww_ref,  # (C, N_t, M_t) packed nonzero transformed weights
    inv_ref,  # (C, m2) fp32 inverse-transform rows
    const_ref,  # (C, 1) fp32 packed positions (batched path only)
    out_ref,  # (T_t, S2*m2, M_t)
    acc_ref,  # scratch (C, T_t, M_t) fp32
    *,
    pos_idx: tuple[int, ...],  # packed position -> winograd position (len C)
    sub_slices: tuple[tuple[int, int], ...],  # per sub-filter (start, end) in packed dim
    m2: int,
    n_steps: int,
    batched: bool,
):
    _, pos = _decode_consts(const_ref, 0) if batched else (None, None)
    _com_post_pe(
        xw_ref[...], ww_ref, inv_ref, out_ref, acc_ref,
        pos_idx=pos_idx, sub_slices=sub_slices, m2=m2, n_steps=n_steps,
        batched=batched, pos=pos,
    )


@functools.partial(
    jax.jit,
    static_argnames=("pos_idx", "sub_slices", "m2", "block_t", "block_n", "block_m", "interpret"),
)
def domain_engine(
    xw: jax.Array,  # (T, n2, N)
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m2: int,
    block_t: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns (T, S2*m2, M): per-tile sub-pixel outputs, sub-filter-major.

    Pads T/N/M up to block multiples, runs the fused engine, crops.
    """
    T, n2, N = xw.shape
    C, _, M = ww_packed.shape
    S2 = len(sub_slices)
    bt, bn, bm = min(block_t, _rup(T, 8)), min(block_n, _rup(N, 128)), min(block_m, _rup(M, 128))
    Tp, Np, Mp = _rup(T, bt), _rup(N, bn), _rup(M, bm)
    xw_p = jnp.pad(xw, ((0, Tp - T), (0, 0), (0, Np - N)))
    ww_p = jnp.pad(ww_packed, ((0, 0), (0, Np - N), (0, Mp - M)))
    grid = (Tp // bt, Mp // bm, Np // bn)

    out = pl.pallas_call(
        functools.partial(
            _engine_kernel,
            pos_idx=pos_idx,
            sub_slices=sub_slices,
            m2=m2,
            n_steps=grid[2],
            batched=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, n2, bn), lambda i, j, k: (i, 0, k)),
            pl.BlockSpec((C, bn, bm), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((C, m2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((C, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, S2 * m2, bm), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, S2 * m2, Mp), xw.dtype),
        scratch_shapes=[pltpu.VMEM((C, bt, bm), jnp.float32)],
        compiler_params=_compiler_params(
            [((bt, n2, bn), xw.dtype), ((C, bn, bm), ww_packed.dtype),
             ((bt, S2 * m2, bm), xw.dtype)],
            ((C, bt, bm), jnp.float32),
        ),
        interpret=interpret,
    )(xw_p, ww_p, inv_packed, jnp.asarray(_const_operand((), pos_idx)))
    return out[:T, :, :M]


def _rup(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# ---------------------------------------------------------------------------
# Fused pre-PE variant: the engine consumes the padded input directly (in the
# m x m "cell" layout below) and runs the B-transform in VMEM, so the
# (T, n^2, N) transformed-tile intermediate never round-trips through HBM.
#
# Input layout ("cells", built host-side as a pure reshape/transpose):
#   cells[b, gy, gx, p*m+q, c] = x_pad[b, m*gy+p, m*gx+q, c]
# i.e. space-to-depth by the output tile stride m.  An n x n Winograd tile at
# tile coords (ty, tx) is exactly the Q x Q patch of cells at (ty..ty+Q-1,
# tx..tx+Q-1) with Q = ceil(n / m), cropped to n — so overlapping tile reads
# become *non-overlapping* cell reads plus a one-cell halo.  The halo is
# expressed by passing the cells array twice: once blocked by bty cell rows
# (index iy) and once as a thin Q-1-row block starting at (iy+1)*bty — the
# TPU analogue of the paper's line buffer (Sec. V), which keeps each input
# row resident instead of re-fetching it per overlapping tile.
# ---------------------------------------------------------------------------


def _adder_apply(coef: tuple[tuple[float, ...], ...], vals):
    """out[u] = sum_a coef[u][a] * vals[a] as unrolled scalar multiply-adds
    (the paper's adder-network transform: for F(2,3) every entry is 0 or ±1,
    so this is pure VPU adds — and Pallas kernels cannot capture array
    constants anyway)."""
    out = []
    for row in coef:
        acc = None
        for a, c in enumerate(row):
            if c == 0.0:
                continue
            term = vals[a] if c == 1.0 else (-vals[a] if c == -1.0 else vals[a] * c)
            acc = term if acc is None else acc + term
        out.append(acc if acc is not None else jnp.zeros_like(vals[0]))
    return out


def _cells_value_to_xw(cells, *, bt_const, m, n, bty, tx, in_dtype,
                       batched: bool = False, bt=None):
    """Fused pre-PE on a staged VMEM value: stitch n x n tiles from m x m
    cell rows (line buffer) and apply B^T Z B.  ``cells`` is
    (bty + halo, Gxp, m2c, N_t); returns xw (bty*tx, n*n, N_t) in
    ``in_dtype``.  Shared by the deconv engines (whole cell block) and the
    conv engines (per phase sub-block of the S^2-major cell axis).
    ``batched`` (interpret fast path) replaces the unrolled adder network
    with one einsum against the B^T constant — same contraction, two ops
    instead of ~n^2 unrolled adds (op count is what interpret time buys)."""
    bn = cells.shape[3]
    q = -(-n // m)

    # --- pre-PE step 1: stitch n x n tiles out of m x m cells (line buffer).
    # Tile (j, t) row a = m*dy + p comes from cell (j+dy, t+dx) row p.
    rows = []
    for dy in range(q):
        cols = []
        for dx in range(q):
            piece = cells[dy : dy + bty, dx : dx + tx]  # (bty, tx, m2c, N_t)
            cols.append(piece.reshape(bty, tx, m, m, bn))
        rows.append(jnp.concatenate(cols, axis=3))  # (bty, tx, m, q*m, N_t)
    z = jnp.concatenate(rows, axis=2)[:, :, :n, :n, :]  # (bty, tx, n, n, N_t)
    z = z.reshape(bty * tx, n, n, bn).astype(jnp.float32)

    # --- pre-PE step 2: B^T Z B.
    if batched:  # bt arrives via the const operand (kernels cannot capture)
        xw = jnp.einsum("ua,tabc,vb->tuvc", bt, z, bt)
        xw = xw.reshape(bty * tx, n * n, bn)
    else:  # adder network: unrolled VPU adds (F(2,3) entries are 0/±1)
        zr = _adder_apply(bt_const, [z[:, a, :, :] for a in range(n)])  # (T_t, n, N_t) each
        xw_uv = []
        for u in range(n):
            xw_uv.extend(_adder_apply(bt_const, [zr[u][:, b, :] for b in range(n)]))
        xw = jnp.stack(xw_uv, axis=1)  # (T_t, n*n, N_t)
    # Match the unfused path, which stores transformed tiles in the input
    # dtype before the channel contraction.
    return xw.astype(in_dtype)


def _cells_to_xw(c0_ref, c1_ref, *, bt_const, m, n, tx, in_dtype,
                 phases: int = 1, batched: bool = False, bt=None):
    """Stage the main + halo cell-row blocks and run the fused pre-PE.

    ``phases=1`` (deconv): the whole cell block is one m x m layout; xw is
    (bty*tx, n2, N_t).  ``phases=S^2`` (strided conv): the cell axis is
    phase-major (one m x m cell block per phase sub-filter — see
    ops.conv_cells_from_image); each phase's block is stitched +
    B-transformed through the same line buffer and concatenated, giving
    xw (bty*tx, phases*n2, N_t) — packed positions index the phases*n2
    space."""
    bty = c0_ref.shape[1]
    cells = jnp.concatenate([c0_ref[0], c1_ref[0]], axis=0)  # (bty+h, Gxp, phases*m2c, N_t)
    if phases == 1:
        return _cells_value_to_xw(
            cells, bt_const=bt_const, m=m, n=n, bty=bty, tx=tx, in_dtype=in_dtype,
            batched=batched, bt=bt,
        )
    m2c = m * m
    return jnp.concatenate(
        [
            _cells_value_to_xw(
                cells[:, :, s * m2c : (s + 1) * m2c, :],
                bt_const=bt_const, m=m, n=n, bty=bty, tx=tx, in_dtype=in_dtype,
                batched=batched, bt=bt,
            )
            for s in range(phases)
        ],
        axis=1,
    )


def _fused_kernel(
    c0_ref,  # (1, bty, Gxp, phases*m2c, N_t) cell rows [iy*bty, (iy+1)*bty)
    c1_ref,  # (1, h, Gxp, phases*m2c, N_t) halo cell rows [(iy+1)*bty, (iy+1)*bty+h)
    ww_ref,  # (C, N_t, M_t)
    inv_ref,  # (C, m2)
    const_ref,  # (n+C, n) fp32 B^T + packed positions (batched path only)
    out_ref,  # (bty*tx, S2*m2, M_t)
    acc_ref,  # scratch (C, bty*tx, M_t) fp32
    *,
    bt_const: tuple[tuple[float, ...], ...],  # B^T as nested tuple (n, n)
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    tx: int,
    m2: int,
    phases: int,
    n_steps: int,
    in_dtype,
    batched: bool,
):
    bt_arr, pos = _decode_consts(const_ref, n) if batched else (None, None)
    xw = _cells_to_xw(c0_ref, c1_ref, bt_const=bt_const, m=m, n=n, tx=tx,
                      in_dtype=in_dtype, phases=phases, batched=batched, bt=bt_arr)
    _com_post_pe(
        xw, ww_ref, inv_ref, out_ref, acc_ref,
        pos_idx=pos_idx, sub_slices=sub_slices, m2=m2, n_steps=n_steps,
        batched=batched, pos=pos,
    )


def _fused_epi_kernel(
    c0_ref,  # (1, bty, Gxp, phases*m2c, N_t) cell rows
    c1_ref,  # (1, h, Gxp, phases*m2c, N_t) halo cell rows
    ww_ref,  # (C, N_t, M_t)
    inv_ref,  # (C, m2)
    const_ref,  # (n+C, n) fp32 B^T + packed positions (batched path only)
    scale_ref,  # (1, M_t) fp32 per-channel scale
    bias_ref,  # (1, M_t) fp32 per-channel bias
    mask_ref,  # cells mode: (bty*S, tx*S, m*m, 1) fp32 crop-window mask
    out_ref,  # nhwc: (1, bty*m*S, tx*m*S, M_t) | cells: (1, bty*S, tx*S, m*m, M_t)
    acc_ref,  # scratch (C, bty*tx, M_t) fp32
    *,
    bt_const: tuple[tuple[float, ...], ...],
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    tx: int,
    phases: int,
    n_steps: int,
    in_dtype,
    out_mode: str,  # "nhwc" | "cells"
    activation: str,
    stride: int,
    has_scale: bool,
    has_bias: bool,
    batched: bool,
):
    """Fused pre-PE + com-PE + epilogue-fused post-PE: the finalize applies
    scale/bias/activation and the stride-S depth-to-space in VMEM, writing
    final pixels (or the next layer's cell layout) instead of scratch rows."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bt_arr, pos = _decode_consts(const_ref, n) if batched else (None, None)
    xw = _cells_to_xw(c0_ref, c1_ref, bt_const=bt_const, m=m, n=n, tx=tx,
                      in_dtype=in_dtype, phases=phases, batched=batched, bt=bt_arr)
    _com_pe(xw, ww_ref, acc_ref, pos_idx=pos_idx, batched=batched, pos=pos)

    @pl.when(k == n_steps - 1)
    def _finalize():
        ys = _post_pe_sub_outputs(acc_ref, inv_ref, sub_slices)
        scale = scale_ref[0].astype(jnp.float32) if has_scale else None
        bias = bias_ref[0].astype(jnp.float32) if has_bias else None
        if out_mode == "nhwc":
            _finalize_nhwc(
                ys, out_ref, m=m, stride=stride, tx=tx,
                scale=scale, bias=bias, activation=activation,
            )
        elif out_mode == "cells":
            _finalize_cells(
                ys, out_ref, mask_ref[...], m=m, stride=stride, tx=tx,
                scale=scale, bias=bias, activation=activation,
            )
        else:
            raise ValueError(out_mode)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bt_mat", "pos_idx", "sub_slices", "m", "n", "ty", "tx", "m2", "phases",
        "block_ty", "block_n", "block_m", "interpret",
        "out_mode", "activation", "stride", "padding", "out_h", "out_w",
    ),
)
def fused_engine(
    cells: jax.Array,  # (B, Gy, Gx, phases*m*m, N) space-to-depth padded input
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    bt_mat: tuple[tuple[float, ...], ...],  # B^T as a static (n, n) nested tuple
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    ty: int,
    tx: int,
    m2: int,
    phases: int = 1,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
    out_mode: str = "scratch",  # "scratch" | "nhwc" | "cells"
    activation: str = "none",
    scale: jax.Array | None = None,  # (M,) per-channel epilogue scale
    bias: jax.Array | None = None,  # (M,) per-channel epilogue bias
    stride: int = 0,  # S; required for the epilogue out modes
    padding: int = 0,  # P (crop offset of the padded interleave)
    out_h: int = 0,  # H_O (crop window height)
    out_w: int = 0,  # W_O
) -> jax.Array:
    """Fused pre-PE + com-PE + post-PE engine — the generic 2D builder.

    The two workload families are the two corners of (phases, stride):
      * deconv (TDC): ``phases=1``, ``stride=S`` — one input phase, the S^2
        sub-filter outputs INTERLEAVE in the finalize (depth-to-space),
        ``padding=P`` crops the padded interleave.
      * strided conv: ``phases=S^2``, ``stride=1``, ``padding=0`` — the
        de-interleaved input phases ride a phase-major cell axis, packed
        positions index the phases*n^2 space, and the phase outputs SUM
        inside the post-PE inverse transform (``sub_slices=((0, C),)``).

    ``out_mode="scratch"`` (default) consumes the cell layout directly and
    returns (B, ty, tx, S2*m2, M) — the same per-tile sub-pixel outputs as
    ``domain_engine`` on the reorganized (T, phases*n2, N) matrix, without
    materializing it in HBM.

    The epilogue out modes fuse the per-channel affine + ``activation`` and
    the stride-S depth-to-space into the finalize (everything the scratch
    layout leaves to XLA):
      * ``"nhwc"`` returns the epilogue'd *padded interleave*
        (B, ty*m*S, tx*m*S, M); crop rows/cols [P, P+H_O) for the NHWC image.
      * ``"cells"`` returns the next layer's padded m x m cell layout
        (B, ty*S, tx*S, m*m, M) with pixels outside the crop window zeroed —
        the inverse of ``ops.cells_layout``, so the next ``fused_engine``
        call chains on it with no XLA relayout.

    Grid: (B * ty_blocks, M_blocks, N_blocks); each step stages a
    (block_ty + halo) strip of cell rows in VMEM, B-transforms it, and feeds
    the packed-position MXU matmuls.
    """
    B, Gy, Gx, m2c, N = cells.shape  # m2c = phases * m * m
    C, _, M = ww_packed.shape
    S2 = len(sub_slices)
    q = -(-n // m)

    bty = min(block_ty, ty)
    n_ty_blocks = -(-ty // bty)
    bn = min(block_n, _rup(N, 128))
    bm = min(block_m, _rup(M, 128))
    Np, Mp = _rup(N, bn), _rup(M, bm)
    # The halo operand only needs the q-1 cell rows past the main block, not
    # a full second bty block — fetching bty rows would double the input DMA
    # on the exact bandwidth-bound path this kernel exists to fix.  Its block
    # row count h must divide the (iy+1)*bty element offset; fall back to a
    # full block otherwise (never taken for the supported q=2 geometries).
    h = q - 1 if q > 1 and bty % (q - 1) == 0 else bty
    # Pad y a full extra block so the last halo read is in-bounds and both
    # specs' block shapes divide the array; x needs tx + q - 1 cell columns
    # in-block.  (Padding is HBM capacity only — DMA per step is bty + h.)
    # A chained input (another layer's raw cells-out, see below) may carry
    # extra all-zero rows past the tile extent — crop, don't pad negative.
    Gyp = (n_ty_blocks + 1) * bty
    Gxp = max(Gx, tx + q - 1)
    if Gy > Gyp:
        cells = cells[:, :Gyp]
        Gy = Gyp
    cells_p = jnp.pad(
        cells, ((0, 0), (0, Gyp - Gy), (0, Gxp - Gx), (0, 0), (0, Np - N))
    )
    # a chained input may also carry trailing all-zero channels (the previous
    # layer's block-padded M axis): pad ww up to the cells' channel extent
    ww_p = jnp.pad(ww_packed, ((0, 0), (0, Np - ww_packed.shape[1]), (0, Mp - M)))
    grid = (B * n_ty_blocks, Mp // bm, Np // bn)

    cell_block = (1, bty, Gxp, m2c, bn)
    in_specs = [
        pl.BlockSpec(
            cell_block,
            lambda i, j, k: (i // n_ty_blocks, i % n_ty_blocks, 0, 0, k),
        ),
        pl.BlockSpec(
            (1, h, Gxp, m2c, bn),
            lambda i, j, k: (
                i // n_ty_blocks,
                (i % n_ty_blocks + 1) * (bty // h),
                0, 0, k,
            ),
        ),
        pl.BlockSpec((C, bn, bm), lambda i, j, k: (0, k, j)),
        pl.BlockSpec((C, m2), lambda i, j, k: (0, 0)),
        pl.BlockSpec((n + C, n), lambda i, j, k: (0, 0)),
    ]
    const_op = jnp.asarray(_const_operand(bt_mat, pos_idx))
    # the out blocks of every output mode, counted together as a bound on the
    # one this call builds: the scratch rows (S2*m2 per tile), the nhwc
    # interleave, and the cells layout plus its crop-window mask block
    sm = m * max(stride, 1)
    out_blocks = [
        ((bty * tx, S2 * m2, bm), cells.dtype),
        ((bty * sm, tx * sm, bm), cells.dtype),
        ((bty * stride, tx * stride, m * m, bm), cells.dtype),
        ((bty * stride, tx * stride, m * m, 1), jnp.float32),
    ]
    common = dict(
        grid=grid,
        scratch_shapes=[pltpu.VMEM((C, bty * tx, bm), jnp.float32)],
        compiler_params=_compiler_params(
            [(cell_block, cells.dtype), ((1, h, Gxp, m2c, bn), cells.dtype),
             ((C, bn, bm), ww_packed.dtype), *out_blocks],
            ((C, bty * tx, bm), jnp.float32),
        ),
        interpret=interpret,
    )

    if out_mode == "scratch":
        out = pl.pallas_call(
            functools.partial(
                _fused_kernel,
                bt_const=bt_mat,
                pos_idx=pos_idx,
                sub_slices=sub_slices,
                m=m,
                n=n,
                tx=tx,
                m2=m2,
                phases=phases,
                n_steps=grid[2],
                in_dtype=cells.dtype,
                batched=interpret,
            ),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bty * tx, S2 * m2, bm), lambda i, j, k: (i, 0, j)),
            out_shape=jax.ShapeDtypeStruct(
                (B * n_ty_blocks * bty * tx, S2 * m2, Mp), cells.dtype
            ),
            **common,
        )(cells_p, cells_p, ww_p, inv_packed, const_op)
        out = out.reshape(B, n_ty_blocks * bty, tx, S2 * m2, Mp)
        return out[:, :ty, :, :, :M]

    # --- epilogue out modes: scale/bias ride along as (1, Mp) fp32 operands
    if out_mode not in ("nhwc", "cells"):
        raise ValueError(out_mode)
    if stride <= 0 or out_h <= 0 or out_w <= 0:
        raise ValueError("epilogue out modes need stride/out_h/out_w")
    ones = jnp.ones((M,), jnp.float32) if scale is None else scale
    zeros = jnp.zeros((M,), jnp.float32) if bias is None else bias
    scale_p = jnp.pad(ones.reshape(1, M).astype(jnp.float32), ((0, 0), (0, Mp - M)))
    bias_p = jnp.pad(zeros.reshape(1, M).astype(jnp.float32), ((0, 0), (0, Mp - M)))
    ms = m * stride
    if out_mode == "cells":
        # crop-window mask, precomputed once per call (static shapes, so XLA
        # constant-folds it): emitted cell (rr, cc) intra (pp, qq) holds
        # interleave pixel (m*rr + pp, m*cc + qq), valid in [P, P+H_O) x
        # [P, P+W_O).  One (rows, tx*S, m2, 1) operand; the kernel applies
        # it as a single multiply.
        rows = n_ty_blocks * bty * stride
        r_io = jnp.arange(rows, dtype=jnp.int32)[:, None, None, None]
        c_io = jnp.arange(tx * stride, dtype=jnp.int32)[None, :, None, None]
        a_io = jnp.arange(m * m, dtype=jnp.int32)[None, None, :, None]
        row_px = m * r_io + a_io // m
        col_px = m * c_io + a_io % m
        mask = (
            (row_px >= padding) & (row_px < padding + out_h)
            & (col_px >= padding) & (col_px < padding + out_w)
        ).astype(jnp.float32)
        mask_spec = pl.BlockSpec(
            (bty * stride, tx * stride, m * m, 1),
            lambda i, j, k: (i % n_ty_blocks, 0, 0, 0),
        )
    else:
        mask = jnp.ones((1, 1, 1, 1), jnp.float32)
        mask_spec = pl.BlockSpec((1, 1, 1, 1), lambda i, j, k: (0, 0, 0, 0))
    in_specs = in_specs + [
        pl.BlockSpec((1, bm), lambda i, j, k: (0, j)),
        pl.BlockSpec((1, bm), lambda i, j, k: (0, j)),
        mask_spec,
    ]
    if out_mode == "nhwc":
        out_specs = pl.BlockSpec(
            (1, bty * ms, tx * ms, bm), lambda i, j, k: (i // n_ty_blocks, i % n_ty_blocks, 0, j)
        )
        out_shape = jax.ShapeDtypeStruct(
            (B, n_ty_blocks * bty * ms, tx * ms, Mp), cells.dtype
        )
    else:
        out_specs = pl.BlockSpec(
            (1, bty * stride, tx * stride, m * m, bm),
            lambda i, j, k: (i // n_ty_blocks, i % n_ty_blocks, 0, 0, j),
        )
        out_shape = jax.ShapeDtypeStruct(
            (B, n_ty_blocks * bty * stride, tx * stride, m * m, Mp), cells.dtype
        )
    out = pl.pallas_call(
        functools.partial(
            _fused_epi_kernel,
            bt_const=bt_mat,
            pos_idx=pos_idx,
            sub_slices=sub_slices,
            m=m,
            n=n,
            tx=tx,
            phases=phases,
            n_steps=grid[2],
            in_dtype=cells.dtype,
            out_mode=out_mode,
            activation=activation,
            stride=stride,
            has_scale=scale is not None,
            has_bias=bias is not None,
            batched=interpret,
        ),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        **common,
    )(cells_p, cells_p, ww_p, inv_packed, const_op, scale_p, bias_p, mask)
    if out_mode == "nhwc":
        return out[:, : ty * ms, :, :M]
    # cells mode: return the raw padded array — the in-kernel crop-window
    # mask already zeroed every row past ty*S and the zero-padded scale/bias
    # zeroed every channel past M, so the next engine call (which pads or
    # crops its input to its own block geometry anyway) consumes this with
    # NO intermediate XLA copy.  ``ops.cells_to_next`` trims only when the
    # chain shift or a short row count actually requires it.
    return out


# ---------------------------------------------------------------------------
# Backward engines.  Both cotangents of the forward engine are themselves
# packed Winograd-domain contractions, so they map onto the same grid /
# BlockSpec machinery as the forward com-PE:
#
#   gw[p,t,m]  = sum_a inv[p,a] * g[t, s(p)*m2+a, m]   (post-PE transposed)
#   dxw[t,j,n] = sum_{p: pos_p=j} sum_m gw[p,t,m] * ww[p,n,m]   (reduce M)
#   dww[p,n,m] = sum_t xw[t,pos_p,n] * gw[p,t,m]                (reduce T)
#
# Structural zeros are skipped exactly as in the forward pass: only the C
# packed positions ever touch VMEM, and Winograd positions no packed p maps
# to are written as zeros without compute.
# ---------------------------------------------------------------------------


def _gw_from_cotangent(g, inv_ref, sub_slices, m2):
    """Per-packed-position weighted cotangent gw (C, T_t, M_t) fp32 from the
    output cotangent g (T_t, S2*m2, M_t): the transpose of the post-PE sparse
    inverse transform, one small MXU contraction per sub-filter."""
    parts = []
    for s, (lo, hi) in enumerate(sub_slices):
        if hi == lo:  # structurally empty sub-filter
            continue
        gs = g[:, s * m2 : (s + 1) * m2, :]  # (T_t, m2, M_t)
        inv_s = inv_ref[lo:hi, :].astype(jnp.float32)  # (c_s, m2)
        parts.append(
            jax.lax.dot_general(
                inv_s, gs, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (c_s, T_t, M_t)
        )
    return jnp.concatenate(parts, axis=0)


def _scatter_packed_to_winograd(gw, ww_ref, pos_idx, n2, batched: bool = False,
                                pos=None):
    """dxw (T_t, n2, N_t) fp32: per packed position one MXU matmul
    gw[p] @ ww[p]^T, accumulated into its Winograd position (positions that
    several sub-filters keep share a row; unkept positions stay zero).
    ``batched`` (interpret fast path): one batched dot + one scatter-add."""
    if batched:
        contrib = jax.lax.dot_general(
            gw, ww_ref[...].astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (C, T_t, N_t)
        out = jnp.zeros((gw.shape[1], n2, ww_ref.shape[1]), jnp.float32)
        return out.at[:, pos, :].add(jnp.transpose(contrib, (1, 0, 2)))
    parts: list = [None] * n2
    for p, pos in enumerate(pos_idx):
        w_p = ww_ref[p, :, :].astype(jnp.float32)  # (N_t, M_t)
        contrib = jax.lax.dot_general(
            gw[p], w_p, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (T_t, N_t)
        parts[pos] = contrib if parts[pos] is None else parts[pos] + contrib
    zero = jnp.zeros((gw.shape[1], ww_ref.shape[1]), jnp.float32)
    return jnp.stack([v if v is not None else zero for v in parts], axis=1)


def _bwd_w_accumulate(xw, gw, acc_ref, *, pos_idx, batched: bool = False,
                      pos=None):
    """dww accumulate: per packed position xw[:, pos]^T @ gw[p] (reduce the
    tile axis).  ``batched`` collapses the loop into one gather + one
    batched dot (interpret fast path, identical per-position math)."""
    if batched:
        xs = jnp.transpose(
            jnp.take(xw, pos, axis=1), (1, 0, 2)
        ).astype(jnp.float32)  # (C, T_t, N_t)
        acc_ref[...] += jax.lax.dot_general(
            xs, gw, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (C, N_t, M_t)
        return
    for p, pos in enumerate(pos_idx):
        x_p = xw[:, pos, :].astype(jnp.float32)  # (T_t, N_t)
        acc_ref[p, :, :] += jax.lax.dot_general(
            x_p, gw[p], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (N_t, M_t)


def _engine_bwd_x_kernel(
    g_ref,  # (T_t, S2*m2, M_t) output cotangent
    ww_ref,  # (C, N_t, M_t) packed transformed weights
    inv_ref,  # (C, m2) fp32
    const_ref,  # (C, 1) fp32 packed positions (batched path only)
    out_ref,  # (T_t, n2, N_t) input-tile cotangent
    acc_ref,  # scratch (T_t, n2, N_t) fp32
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m2: int,
    n2: int,
    n_steps: int,
    batched: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)
    gw = _gw_from_cotangent(g, inv_ref, sub_slices, m2)  # (C, T_t, M_t)
    _, pos = _decode_consts(const_ref, 0) if batched else (None, None)
    acc_ref[...] += _scatter_packed_to_winograd(gw, ww_ref, pos_idx, n2, batched, pos)

    @pl.when(k == n_steps - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("pos_idx", "sub_slices", "m2", "n2", "block_t", "block_n", "block_m", "interpret"),
)
def domain_engine_bwd_x(
    g: jax.Array,  # (T, S2*m2, M) cotangent of the forward output
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m2: int,
    n2: int,
    block_t: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dL/dxw (T, n2, N) of ``domain_engine``: the M axis becomes
    the accumulated grid axis; everything else mirrors the forward engine."""
    T, s2m2, M = g.shape
    C, N, _ = ww_packed.shape
    bt = min(block_t, _rup(T, 8))
    bn = min(block_n, _rup(N, 128))
    bm = min(block_m, _rup(M, 128))
    Tp, Np, Mp = _rup(T, bt), _rup(N, bn), _rup(M, bm)
    g_p = jnp.pad(g, ((0, Tp - T), (0, 0), (0, Mp - M)))
    ww_p = jnp.pad(ww_packed, ((0, 0), (0, Np - N), (0, Mp - M)))
    grid = (Tp // bt, Np // bn, Mp // bm)

    out = pl.pallas_call(
        functools.partial(
            _engine_bwd_x_kernel,
            pos_idx=pos_idx,
            sub_slices=sub_slices,
            m2=m2,
            n2=n2,
            n_steps=grid[2],
            batched=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, s2m2, bm), lambda i, j, k: (i, 0, k)),
            pl.BlockSpec((C, bn, bm), lambda i, j, k: (0, j, k)),
            pl.BlockSpec((C, m2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((C, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, n2, bn), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, n2, Np), g.dtype),
        scratch_shapes=[pltpu.VMEM((bt, n2, bn), jnp.float32)],
        compiler_params=_compiler_params(
            [((bt, s2m2, bm), g.dtype), ((C, bn, bm), ww_packed.dtype),
             ((bt, n2, bn), g.dtype)],
            ((bt, n2, bn), jnp.float32),
        ),
        interpret=interpret,
    )(g_p, ww_p, inv_packed, jnp.asarray(_const_operand((), pos_idx)))
    return out[:T, :, :N]


def _engine_bwd_w_kernel(
    xw_ref,  # (T_t, n2, N_t) transformed input tiles
    g_ref,  # (T_t, S2*m2, M_t) output cotangent
    inv_ref,  # (C, m2) fp32
    const_ref,  # (C, 1) fp32 packed positions (batched path only)
    out_ref,  # (C, N_t, M_t) packed-weight cotangent
    acc_ref,  # scratch (C, N_t, M_t) fp32
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m2: int,
    n_steps: int,
    batched: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)
    gw = _gw_from_cotangent(g, inv_ref, sub_slices, m2)  # (C, T_t, M_t)
    _, pos = _decode_consts(const_ref, 0) if batched else (None, None)
    _bwd_w_accumulate(xw_ref[...], gw, acc_ref, pos_idx=pos_idx,
                      batched=batched, pos=pos)

    @pl.when(k == n_steps - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("pos_idx", "sub_slices", "m2", "block_t", "block_n", "block_m", "interpret"),
)
def domain_engine_bwd_w(
    xw: jax.Array,  # (T, n2, N)
    g: jax.Array,  # (T, S2*m2, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m2: int,
    block_t: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dL/dww_packed (C, N, M) of ``domain_engine``: the tile axis T
    becomes the accumulated grid axis (the channel-accumulate of the forward
    engine, transposed onto the weight cotangent)."""
    T, n2, N = xw.shape
    _, s2m2, M = g.shape
    C = len(pos_idx)
    bt = min(block_t, _rup(T, 8))
    bn = min(block_n, _rup(N, 128))
    bm = min(block_m, _rup(M, 128))
    Tp, Np, Mp = _rup(T, bt), _rup(N, bn), _rup(M, bm)
    xw_p = jnp.pad(xw, ((0, Tp - T), (0, 0), (0, Np - N)))
    g_p = jnp.pad(g, ((0, Tp - T), (0, 0), (0, Mp - M)))
    grid = (Np // bn, Mp // bm, Tp // bt)

    out = pl.pallas_call(
        functools.partial(
            _engine_bwd_w_kernel,
            pos_idx=pos_idx,
            sub_slices=sub_slices,
            m2=m2,
            n_steps=grid[2],
            batched=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, n2, bn), lambda i, j, k: (k, 0, i)),
            pl.BlockSpec((bt, s2m2, bm), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((C, m2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((C, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((C, bn, bm), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, Np, Mp), g.dtype),
        scratch_shapes=[pltpu.VMEM((C, bn, bm), jnp.float32)],
        compiler_params=_compiler_params(
            [((bt, n2, bn), xw.dtype), ((bt, s2m2, bm), g.dtype),
             ((C, bn, bm), g.dtype)],
            ((C, bn, bm), jnp.float32),
        ),
        interpret=interpret,
    )(xw_p, g_p, inv_packed, jnp.asarray(_const_operand((), pos_idx)))
    return out[:, :N, :M]


# ---------------------------------------------------------------------------
# Fused pre-PE backward: the input cotangent never leaves the Winograd domain
# either.  dcells = scatter of B (dXw) B^T over the overlapping tiles — the
# transpose of the forward line buffer.  The halo runs in *reverse*: an
# output block of cell rows [iy*bty, +bty) receives contributions from tile
# rows [iy*bty - (q-1), iy*bty + bty), so the tile cotangent is passed twice
# — once blocked by bty rows and once as a thin (q-1)-row block *preceding*
# the main block (one leading zero block makes the iy=0 read in-bounds).
# ---------------------------------------------------------------------------


def _dxw_block_to_cells(dxw, *, b_const, m, n, tx, bty, h, gxc, bn,
                        batched: bool = False, bt=None):
    """dXw block (h+bty, tx, n, n, N_t) fp32 -> cell-layout input cotangent
    (bty, gxc, m*m, N_t) fp32.

    dZ = B dXw B^T via the adder network with transposed coefficients, then
    the transpose of the tile gather: cell (j, c) intra position (p, qq)
    sums dz[m*dy+p][m*dx+qq] of tile (j - dy, c - dx); with tile rows
    staged at local offset +h, tile row j - dy sits at slice j + h - dy.
    Shared by the deconv bwd_x kernel (whole block) and the conv bwd_x
    kernel (once per phase sub-filter)."""
    q = -(-n // m)
    if batched:  # interpret fast path: one einsum against the B operand
        bc = jnp.transpose(bt)  # b_const = B^T transposed
        dzt = jnp.einsum("au,htuvc,bv->abhtc", bc, dxw, bc)
        dz = [[dzt[a, b] for b in range(n)] for a in range(n)]
    else:
        rows = _adder_apply(b_const, [dxw[:, :, u] for u in range(n)])
        dz = [
            _adder_apply(b_const, [rows[a][:, :, v] for v in range(n)])
            for a in range(n)
        ]  # dz[a][b]: (h+bty, tx, N_t)
    cellv = []
    for p in range(m):
        for qq in range(m):
            acc = None
            for dy in range(q):
                if m * dy + p >= n:
                    continue
                for dx in range(q):
                    if m * dx + qq >= n:
                        continue
                    piece = dz[m * dy + p][m * dx + qq][h - dy : h - dy + bty]
                    pads = []
                    if dx:
                        pads.append(jnp.zeros((bty, dx, bn), jnp.float32))
                    pads.append(piece)
                    if gxc - tx - dx:
                        pads.append(jnp.zeros((bty, gxc - tx - dx, bn), jnp.float32))
                    shifted = pads[0] if len(pads) == 1 else jnp.concatenate(pads, axis=1)
                    acc = shifted if acc is None else acc + shifted
            cellv.append(
                acc if acc is not None else jnp.zeros((bty, gxc, bn), jnp.float32)
            )
    return jnp.stack(cellv, axis=2)  # (bty, gxc, m*m, N_t)


def _fused_bwd_x_kernel(
    g0_ref,  # (1, bty, tx, S2*m2, M_t) tile-cotangent rows [iy*bty, +bty)
    g1_ref,  # (1, h, tx, S2*m2, M_t) halo rows [iy*bty - h, iy*bty)
    ww_ref,  # (C, N_t, M_t)
    inv_ref,  # (C, m2) fp32
    const_ref,  # (n+C, n) fp32 B^T + packed positions (batched path only)
    out_ref,  # (1, bty, gxc, phases*m*m, N_t) cell-layout input cotangent
    acc_ref,  # scratch ((h+bty)*tx, phases*n2, N_t) fp32
    *,
    b_const: tuple[tuple[float, ...], ...],  # (B^T)^T as a static nested tuple
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    tx: int,
    m2: int,
    phases: int,
    n_steps: int,
    batched: bool,
):
    k = pl.program_id(2)
    bty = out_ref.shape[1]
    gxc = out_ref.shape[2]
    h = g1_ref.shape[1]
    bn = ww_ref.shape[1]
    n2 = n * n

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bt_arr, pos = _decode_consts(const_ref, n) if batched else (None, None)
    g_all = jnp.concatenate([g1_ref[0], g0_ref[0]], axis=0)  # (h+bty, tx, S2m2, M_t)
    gt = g_all.reshape((h + bty) * tx, g_all.shape[2], g_all.shape[3]).astype(jnp.float32)
    gw = _gw_from_cotangent(gt, inv_ref, sub_slices, m2)  # (C, T_t, M_t)
    acc_ref[...] += _scatter_packed_to_winograd(gw, ww_ref, pos_idx, phases * n2,
                                                batched, pos)

    @pl.when(k == n_steps - 1)
    def _finalize():
        if phases == 1:
            dxw = acc_ref[...].reshape(h + bty, tx, n, n, bn)
            out = _dxw_block_to_cells(
                dxw, b_const=b_const, m=m, n=n, tx=tx, bty=bty, h=h, gxc=gxc,
                bn=bn, batched=batched, bt=bt_arr,
            )
        else:  # per-phase reverse line buffer, phase-major cell axis out
            dxw = acc_ref[...].reshape(h + bty, tx, phases, n, n, bn)
            out = jnp.concatenate(
                [
                    _dxw_block_to_cells(
                        dxw[:, :, s], b_const=b_const, m=m, n=n, tx=tx, bty=bty,
                        h=h, gxc=gxc, bn=bn, batched=batched, bt=bt_arr,
                    )
                    for s in range(phases)
                ],
                axis=2,
            )
        out_ref[...] = out[None].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bt_mat", "pos_idx", "sub_slices", "m", "n", "ty", "tx", "gy", "gx",
        "m2", "phases", "block_ty", "block_n", "block_m", "interpret",
    ),
)
def fused_engine_bwd_x(
    g: jax.Array,  # (B, ty, tx, S2*m2, M) cotangent of the fused engine output
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    bt_mat: tuple[tuple[float, ...], ...],  # B^T as a static (n, n) nested tuple
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    ty: int,
    tx: int,
    gy: int,
    gx: int,
    m2: int,
    phases: int = 1,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dL/dcells (B, gy, gx, phases*m*m, N) of ``fused_engine``.

    Grid (B * (ty_blocks + 1), N_blocks, M_blocks); the extra output block
    row absorbs the last tile row's q-1 spilled cell rows, and M is the
    accumulated axis.  The B-transpose adder network and the overlap scatter
    run in VMEM on the final M step, so the (T, phases*n2, N) tile cotangent
    never materializes in HBM — the line buffer argument, transposed.  With
    ``phases=S^2`` the packed scatter targets the phases*n^2 position space
    and the reverse line buffer runs once per phase sub-filter.
    """
    B, _, _, s2m2, M = g.shape
    C, N, _ = ww_packed.shape
    q = -(-n // m)
    bty = min(block_ty, ty)
    ntb = -(-ty // bty)
    nob = ntb + 1
    h = q - 1 if q > 1 and bty % (q - 1) == 0 else bty
    if h < q - 1:
        raise ValueError(f"block_ty={block_ty} smaller than the q-1={q-1} halo")
    bn = min(block_n, _rup(N, 128))
    bm = min(block_m, _rup(M, 128))
    Np, Mp = _rup(N, bn), _rup(M, bm)
    # One leading zero block keeps the preceding-rows halo read in-bounds at
    # iy=0; trailing zeros back the extra output block row.  (HBM capacity
    # only — DMA per step is bty + h tile rows.)
    g_p = jnp.pad(
        g, ((0, 0), (bty, (nob + 1) * bty - bty - ty), (0, 0), (0, 0), (0, Mp - M))
    )
    ww_p = jnp.pad(ww_packed, ((0, 0), (0, Np - N), (0, Mp - M)))
    grid = (B * nob, Np // bn, Mp // bm)
    m2c = phases * m * m

    out = pl.pallas_call(
        functools.partial(
            _fused_bwd_x_kernel,
            b_const=tuple(zip(*bt_mat)),
            pos_idx=pos_idx,
            sub_slices=sub_slices,
            m=m,
            n=n,
            tx=tx,
            m2=m2,
            phases=phases,
            n_steps=grid[2],
            batched=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, bty, tx, s2m2, bm),
                lambda i, j, k: (i // nob, i % nob + 1, 0, 0, k),
            ),
            pl.BlockSpec(
                (1, h, tx, s2m2, bm),
                lambda i, j, k: (i // nob, (i % nob + 1) * (bty // h) - 1, 0, 0, k),
            ),
            pl.BlockSpec((C, bn, bm), lambda i, j, k: (0, j, k)),
            pl.BlockSpec((C, m2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((n + C, n), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, bty, gx, m2c, bn), lambda i, j, k: (i // nob, i % nob, 0, 0, j)
        ),
        out_shape=jax.ShapeDtypeStruct((B, nob * bty, gx, m2c, Np), g.dtype),
        scratch_shapes=[pltpu.VMEM(((h + bty) * tx, phases * n * n, bn), jnp.float32)],
        compiler_params=_compiler_params(
            [((bty, tx, s2m2, bm), g.dtype), ((h, tx, s2m2, bm), g.dtype),
             ((C, bn, bm), ww_packed.dtype), ((bty, gx, m2c, bn), g.dtype)],
            (((h + bty) * tx, phases * n * n, bn), jnp.float32),
        ),
        interpret=interpret,
    )(g_p, g_p, ww_p, inv_packed, jnp.asarray(_const_operand(bt_mat, pos_idx)))
    out = out[:, :, :, :, :N]
    if out.shape[1] < gy:  # cell rows past the tile extent are structurally zero
        out = jnp.pad(out, ((0, 0), (0, gy - out.shape[1]), (0, 0), (0, 0), (0, 0)))
    return out[:, :gy]


def _fused_bwd_w_kernel(
    c0_ref,  # (1, bty, Gxp, phases*m2c, N_t) cell rows (as in the fused forward)
    c1_ref,  # (1, h, Gxp, phases*m2c, N_t) halo cell rows
    g_ref,  # (1, bty, tx, S2*m2, M_t) output cotangent for this tile-row block
    inv_ref,  # (C, m2) fp32
    const_ref,  # (n+C, n) fp32 B^T + packed positions (batched path only)
    out_ref,  # (C, N_t, M_t) packed-weight cotangent
    acc_ref,  # scratch (C, N_t, M_t) fp32
    *,
    bt_const: tuple[tuple[float, ...], ...],  # B^T as a static nested tuple
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    tx: int,
    m2: int,
    phases: int,
    n_steps: int,
    in_dtype,
    batched: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Recompute the transformed tiles from cells in VMEM (same line-buffer +
    # adder-network stage as the forward kernel), then contract with the
    # inverse-weighted cotangent over this block's tiles.
    bt_arr, pos = _decode_consts(const_ref, n) if batched else (None, None)
    xw = _cells_to_xw(c0_ref, c1_ref, bt_const=bt_const, m=m, n=n, tx=tx,
                      in_dtype=in_dtype, phases=phases, batched=batched, bt=bt_arr)
    g = g_ref[0].reshape(xw.shape[0], g_ref.shape[3], g_ref.shape[4]).astype(jnp.float32)
    gw = _gw_from_cotangent(g, inv_ref, sub_slices, m2)  # (C, T_t, M_t)
    _bwd_w_accumulate(xw, gw, acc_ref, pos_idx=pos_idx, batched=batched, pos=pos)

    @pl.when(k == n_steps - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bt_mat", "pos_idx", "sub_slices", "m", "n", "ty", "tx", "m2", "phases",
        "block_ty", "block_n", "block_m", "interpret",
    ),
)
def fused_engine_bwd_w(
    cells: jax.Array,  # (B, Gy, Gx, phases*m*m, N) the forward's cell-layout input
    g: jax.Array,  # (B, ty, tx, S2*m2, M)
    inv_packed: jax.Array,  # (C, m2) fp32
    bt_mat: tuple[tuple[float, ...], ...],
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    ty: int,
    tx: int,
    m2: int,
    phases: int = 1,
    block_ty: int = 8,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dL/dww_packed (C, N, M) of ``fused_engine``: the grid reduces over
    (batch x tile-row blocks), re-deriving each block's transformed tiles
    (per phase sub-filter when ``phases > 1``) from the cell layout in VMEM
    exactly as the forward does (so xw never round-trips through HBM in the
    backward pass either).
    """
    B, Gy, Gx, m2c, N = cells.shape
    _, _, _, s2m2, M = g.shape
    C = len(pos_idx)
    q = -(-n // m)
    bty = min(block_ty, ty)
    ntb = -(-ty // bty)
    bn = min(block_n, _rup(N, 128))
    bm = min(block_m, _rup(M, 128))
    Np, Mp = _rup(N, bn), _rup(M, bm)
    h = q - 1 if q > 1 and bty % (q - 1) == 0 else bty
    Gyp = (ntb + 1) * bty
    Gxp = max(Gx, tx + q - 1)
    cells_p = jnp.pad(
        cells, ((0, 0), (0, Gyp - Gy), (0, Gxp - Gx), (0, 0), (0, Np - N))
    )
    g_p = jnp.pad(g, ((0, 0), (0, ntb * bty - ty), (0, 0), (0, 0), (0, Mp - M)))
    grid = (Np // bn, Mp // bm, B * ntb)

    out = pl.pallas_call(
        functools.partial(
            _fused_bwd_w_kernel,
            bt_const=bt_mat,
            pos_idx=pos_idx,
            sub_slices=sub_slices,
            m=m,
            n=n,
            tx=tx,
            m2=m2,
            phases=phases,
            n_steps=grid[2],
            in_dtype=cells.dtype,
            batched=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, bty, Gxp, m2c, bn),
                lambda i, j, k: (k // ntb, k % ntb, 0, 0, i),
            ),
            pl.BlockSpec(
                (1, h, Gxp, m2c, bn),
                lambda i, j, k: (k // ntb, (k % ntb + 1) * (bty // h), 0, 0, i),
            ),
            pl.BlockSpec(
                (1, bty, tx, s2m2, bm),
                lambda i, j, k: (k // ntb, k % ntb, 0, 0, j),
            ),
            pl.BlockSpec((C, m2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((n + C, n), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((C, bn, bm), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, Np, Mp), g.dtype),
        scratch_shapes=[pltpu.VMEM((C, bn, bm), jnp.float32)],
        compiler_params=_compiler_params(
            [((bty, Gxp, m2c, bn), cells.dtype), ((h, Gxp, m2c, bn), cells.dtype),
             ((bty, tx, s2m2, bm), g.dtype), ((C, bn, bm), g.dtype)],
            ((C, bn, bm), jnp.float32),
        ),
        interpret=interpret,
    )(cells_p, cells_p, g_p, inv_packed,
      jnp.asarray(_const_operand(bt_mat, pos_idx)))
    return out[:, :N, :M]


# ---------------------------------------------------------------------------
# 1D engines (audio/SSM stacks).  The same line-buffer / com-PE / post-PE
# template instantiated on rank-1 transforms: cells are
#   cells[b, g, s*m + p, c] = x_pad_phase_s[b, m*g + p, c]
# (space-to-depth by the output tile stride m along the sequence axis; one
# m-row block per input phase s — phases = S for a stride-S conv1d, 1 for
# conv1d stride 1 and for TDC deconv1d).  An n-row Winograd tile at tile
# coord j is cell rows [j, j + q) cropped to n (q = ceil(n/m)), so the
# overlapping tile reads become non-overlapping cell reads plus a thin
# (q-1)-row halo — the identical BlockSpec pattern as the 2D engines, one
# axis shorter.  The com-PE and post-PE stages are reused VERBATIM
# (_com_pe / _com_post_pe / _post_pe_sub_outputs are rank-agnostic: they
# only see the packed position axis and m2 = m output rows per tile).
#
# The backward dataflow is where 1D pays for itself: the rank-1 transforms
# are O(n) adds, so dL/dcells and dL/dww run on the UNFUSED rank-agnostic
# domain engines (the heavy packed-position MXU contractions stay in
# Pallas) with the B-scatter / B-transform as cheap host-side einsums —
# winograd_conv1d_fused_bwd_x / _bwd_w below are declarative compositions,
# not new kernels.
# ---------------------------------------------------------------------------


def _cells1d_to_xw(c0_ref, c1_ref, *, bt_const, m, n, phases, in_dtype,
                   batched: bool = False, bt=None):
    """1D fused pre-PE: stitch n-row tiles from m-row cell blocks (line
    buffer) and apply the one-sided B^T transform per phase.  Returns xw
    (bty, phases*n, N_t) in ``in_dtype``."""
    bty = c0_ref.shape[1]
    q = -(-n // m)
    cells = jnp.concatenate([c0_ref[0], c1_ref[0]], axis=0)  # (bty+h, phases*m, N_t)
    parts = []
    for s in range(phases):
        blk = cells[:, s * m : (s + 1) * m, :]  # (bty+h, m, N_t)
        rows = [blk[dy : dy + bty] for dy in range(q)]  # (bty, m, N_t) each
        z = jnp.concatenate(rows, axis=1)[:, :n, :].astype(jnp.float32)  # (bty, n, N_t)
        if batched:  # interpret fast path: one einsum against the B^T operand
            xw_s = jnp.einsum("ua,tac->tuc", bt, z)
        else:  # adder network: unrolled VPU adds
            xw_s = jnp.stack(
                _adder_apply(bt_const, [z[:, a, :] for a in range(n)]), axis=1
            )
        parts.append(xw_s)
    xw = parts[0] if phases == 1 else jnp.concatenate(parts, axis=1)
    return xw.astype(in_dtype)


def _finalize_nlc(ys, out_ref, *, m, stride, scale, bias, activation):
    """1D depth-to-space in VMEM: tile j sub-pixel (rho, p) lands at
    padded-interleave row m*S*j + S*p + rho — one transpose of the stacked
    post-PE outputs, then the fused epilogue."""
    S = stride
    bty = out_ref.shape[1] // (m * S)
    bm = out_ref.shape[2]
    full = _stack_sub_outputs(ys, m)  # (S, m, bty, bm)
    y = jnp.transpose(full, (2, 1, 0, 3)).reshape(bty * m * S, bm)
    y = _apply_epilogue(y, scale, bias, activation)
    out_ref[...] = y[None].astype(out_ref.dtype)


def _fused1d_kernel(
    c0_ref,  # (1, bty, phases*m, N_t) cell rows [i*bty, (i+1)*bty)
    c1_ref,  # (1, h, phases*m, N_t) halo cell rows
    ww_ref,  # (C, N_t, M_t)
    inv_ref,  # (C, m) fp32
    const_ref,  # (n+C, n) fp32 B^T + packed positions (batched path only)
    out_ref,  # (bty, S2*m, M_t)
    acc_ref,  # scratch (C, bty, M_t) fp32
    *,
    bt_const: tuple[tuple[float, ...], ...],
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    phases: int,
    n_steps: int,
    in_dtype,
    batched: bool,
):
    bt_arr, pos = _decode_consts(const_ref, n) if batched else (None, None)
    xw = _cells1d_to_xw(c0_ref, c1_ref, bt_const=bt_const, m=m, n=n,
                        phases=phases, in_dtype=in_dtype, batched=batched,
                        bt=bt_arr)
    _com_post_pe(
        xw, ww_ref, inv_ref, out_ref, acc_ref,
        pos_idx=pos_idx, sub_slices=sub_slices, m2=m, n_steps=n_steps,
        batched=batched, pos=pos,
    )


def _fused1d_epi_kernel(
    c0_ref,  # (1, bty, phases*m, N_t) cell rows
    c1_ref,  # (1, h, phases*m, N_t) halo cell rows
    ww_ref,  # (C, N_t, M_t)
    inv_ref,  # (C, m) fp32
    const_ref,  # (n+C, n) fp32
    scale_ref,  # (1, M_t) fp32
    bias_ref,  # (1, M_t) fp32
    out_ref,  # (1, bty*m*S, M_t) padded-interleave rows
    acc_ref,  # scratch (C, bty, M_t) fp32
    *,
    bt_const: tuple[tuple[float, ...], ...],
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    phases: int,
    n_steps: int,
    in_dtype,
    activation: str,
    stride: int,
    has_scale: bool,
    has_bias: bool,
    batched: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bt_arr, pos = _decode_consts(const_ref, n) if batched else (None, None)
    xw = _cells1d_to_xw(c0_ref, c1_ref, bt_const=bt_const, m=m, n=n,
                        phases=phases, in_dtype=in_dtype, batched=batched,
                        bt=bt_arr)
    _com_pe(xw, ww_ref, acc_ref, pos_idx=pos_idx, batched=batched, pos=pos)

    @pl.when(k == n_steps - 1)
    def _finalize():
        ys = _post_pe_sub_outputs(acc_ref, inv_ref, sub_slices)
        scale = scale_ref[0].astype(jnp.float32) if has_scale else None
        bias = bias_ref[0].astype(jnp.float32) if has_bias else None
        _finalize_nlc(ys, out_ref, m=m, stride=stride, scale=scale, bias=bias,
                      activation=activation)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bt_mat", "pos_idx", "sub_slices", "m", "n", "ty", "phases",
        "block_ty", "block_n", "block_m", "interpret",
        "out_mode", "activation", "stride",
    ),
)
def winograd_conv1d_fused_engine(
    cells: jax.Array,  # (B, Gy, phases*m, N) space-to-depth padded sequence
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m) fp32
    bt_mat: tuple[tuple[float, ...], ...],  # B^T as a static (n, n) nested tuple
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    ty: int,
    phases: int = 1,
    block_ty: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
    out_mode: str = "nlc",  # "scratch" | "nlc"
    activation: str = "none",
    scale: jax.Array | None = None,  # (M,) per-channel epilogue scale
    bias: jax.Array | None = None,  # (M,) per-channel epilogue bias
    stride: int = 1,  # S (interleave factor of the nlc finalize)
) -> jax.Array:
    """The 1D instantiation of the fused engine (audio deconv / SSM conv).

    Covers stride-1 conv1d (``phases=1, stride=1``, one sub-filter spanning
    all n positions), phase-decomposed strided conv1d (``phases=S``), and
    TDC deconv1d (``phases=1, stride=S``, S sub-filters interleaving) —
    causal vs SAME padding is entirely in the caller's cell construction.

    ``out_mode="scratch"`` returns (B, ty, S2*m, M) per-tile sub-pixel rows;
    ``"nlc"`` fuses the epilogue + stride-S interleave and returns the
    padded interleave (B, ty*m*S, M) — crop rows [P, P+L_O) for the output
    sequence.  Grid (B * ty_blocks, M_blocks, N_blocks), each step staging
    block_ty + (q-1) halo cell rows — the 2D line buffer, one axis shorter.
    """
    B, Gy, pm, N = cells.shape  # pm == phases * m
    C, _, M = ww_packed.shape
    S2 = len(sub_slices)
    q = -(-n // m)

    bty = min(block_ty, ty)
    ntb = -(-ty // bty)
    bn = min(block_n, _rup(N, 128))
    bm = min(block_m, _rup(M, 128))
    Np, Mp = _rup(N, bn), _rup(M, bm)
    h = q - 1 if q > 1 and bty % (q - 1) == 0 else bty
    Gyp = (ntb + 1) * bty
    if Gy > Gyp:  # chained/over-padded input: crop, don't pad negative
        cells = cells[:, :Gyp]
        Gy = Gyp
    cells_p = jnp.pad(cells, ((0, 0), (0, Gyp - Gy), (0, 0), (0, Np - N)))
    ww_p = jnp.pad(ww_packed, ((0, 0), (0, Np - ww_packed.shape[1]), (0, Mp - M)))
    grid = (B * ntb, Mp // bm, Np // bn)

    in_specs = [
        pl.BlockSpec((1, bty, pm, bn), lambda i, j, k: (i // ntb, i % ntb, 0, k)),
        pl.BlockSpec(
            (1, h, pm, bn),
            lambda i, j, k: (i // ntb, (i % ntb + 1) * (bty // h), 0, k),
        ),
        pl.BlockSpec((C, bn, bm), lambda i, j, k: (0, k, j)),
        pl.BlockSpec((C, m), lambda i, j, k: (0, 0)),
        pl.BlockSpec((n + C, n), lambda i, j, k: (0, 0)),
    ]
    const_op = jnp.asarray(_const_operand(bt_mat, pos_idx))
    common = dict(
        grid=grid,
        scratch_shapes=[pltpu.VMEM((C, bty, bm), jnp.float32)],
        compiler_params=_compiler_params(
            [((bty, pm, bn), cells.dtype), ((h, pm, bn), cells.dtype),
             ((C, bn, bm), ww_packed.dtype), ((bty, S2 * m, bm), cells.dtype),
             ((bty * m * stride, bm), cells.dtype)],
            ((C, bty, bm), jnp.float32),
        ),
        interpret=interpret,
    )

    if out_mode == "scratch":
        out = pl.pallas_call(
            functools.partial(
                _fused1d_kernel,
                bt_const=bt_mat,
                pos_idx=pos_idx,
                sub_slices=sub_slices,
                m=m,
                n=n,
                phases=phases,
                n_steps=grid[2],
                in_dtype=cells.dtype,
                batched=interpret,
            ),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bty, S2 * m, bm), lambda i, j, k: (i, 0, j)),
            out_shape=jax.ShapeDtypeStruct((B * ntb * bty, S2 * m, Mp), cells.dtype),
            **common,
        )(cells_p, cells_p, ww_p, inv_packed, const_op)
        out = out.reshape(B, ntb * bty, S2 * m, Mp)
        return out[:, :ty, :, :M]

    if out_mode != "nlc":
        raise ValueError(out_mode)
    if stride <= 0:
        raise ValueError("out_mode='nlc' needs stride >= 1")
    ones = jnp.ones((M,), jnp.float32) if scale is None else scale
    zeros = jnp.zeros((M,), jnp.float32) if bias is None else bias
    scale_p = jnp.pad(ones.reshape(1, M).astype(jnp.float32), ((0, 0), (0, Mp - M)))
    bias_p = jnp.pad(zeros.reshape(1, M).astype(jnp.float32), ((0, 0), (0, Mp - M)))
    ms = m * stride
    in_specs = in_specs + [
        pl.BlockSpec((1, bm), lambda i, j, k: (0, j)),
        pl.BlockSpec((1, bm), lambda i, j, k: (0, j)),
    ]
    out = pl.pallas_call(
        functools.partial(
            _fused1d_epi_kernel,
            bt_const=bt_mat,
            pos_idx=pos_idx,
            sub_slices=sub_slices,
            m=m,
            n=n,
            phases=phases,
            n_steps=grid[2],
            in_dtype=cells.dtype,
            activation=activation,
            stride=stride,
            has_scale=scale is not None,
            has_bias=bias is not None,
            batched=interpret,
        ),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bty * ms, bm), lambda i, j, k: (i // ntb, i % ntb, j)
        ),
        out_shape=jax.ShapeDtypeStruct((B, ntb * bty * ms, Mp), cells.dtype),
        **common,
    )(cells_p, cells_p, ww_p, inv_packed, const_op, scale_p, bias_p)
    return out[:, : ty * ms, :M]


def _xw_from_cells_1d(cells, bt_mat, *, m, n, ty, phases):
    """Host-side 1D B-transform of the cell layout: (B, Gy, phases*m, N) ->
    (B*ty, phases*n, N) fp32 — the unfused pre-PE (rank-1, so one cheap
    einsum), feeding the rank-agnostic domain engines in the 1D backward."""
    q = -(-n // m)
    B, Gy, pm, N = cells.shape
    need = ty + q - 1
    if Gy < need:
        cells = jnp.pad(cells, ((0, 0), (0, need - Gy), (0, 0), (0, 0)))
    bt = jnp.asarray(bt_mat, jnp.float32)
    parts = []
    for s in range(phases):
        blk = cells[:, :, s * m : (s + 1) * m, :]  # (B, Gy', m, N)
        rows = jnp.concatenate(
            [blk[:, dy : dy + ty] for dy in range(q)], axis=2
        )[:, :, :n, :]  # (B, ty, n, N)
        parts.append(jnp.einsum("ua,btac->btuc", bt, rows.astype(jnp.float32)))
    xw = parts[0] if phases == 1 else jnp.concatenate(parts, axis=2)
    return xw.reshape(B * ty, phases * n, N)


def winograd_conv1d_fused_bwd_x(
    g: jax.Array,  # (B, ty, S2*m, M) scratch-layout cotangent
    ww_packed: jax.Array,  # (C, N, M)
    inv_packed: jax.Array,  # (C, m) fp32
    bt_mat: tuple[tuple[float, ...], ...],
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    ty: int,
    gy: int,
    phases: int = 1,
    block_t: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dL/dcells (B, gy, phases*m, N) of the 1D fused engine: the packed
    MXU contraction runs in ``domain_engine_bwd_x``; the rank-1 B-scatter
    over the q overlapping tiles per cell is O(n) XLA adds."""
    B, _, s2m, M = g.shape
    q = -(-n // m)
    dxw = domain_engine_bwd_x(
        g.reshape(B * ty, s2m, M), ww_packed, inv_packed,
        pos_idx=pos_idx, sub_slices=sub_slices, m2=m, n2=phases * n,
        block_t=block_t, block_n=block_n, block_m=block_m, interpret=interpret,
    )  # (B*ty, phases*n, N)
    N = dxw.shape[2]
    b_mat = jnp.asarray(bt_mat, jnp.float32).T  # B = (B^T)^T
    dz = jnp.einsum(
        "au,btsuc->btsac", b_mat,
        dxw.reshape(B, ty, phases, n, N).astype(jnp.float32),
    )  # (B, ty, phases, n, N)
    dz = jnp.pad(dz, ((0, 0), (0, 0), (0, 0), (0, q * m - n), (0, 0)))
    dz = dz.reshape(B, ty, phases, q, m, N)
    # cell g receives tile j = g - dy at intra-tile cell offset dy
    acc = jnp.zeros((B, ty + q - 1, phases, m, N), jnp.float32)
    for dy in range(q):
        acc = acc.at[:, dy : dy + ty].add(dz[:, :, :, dy])
    out = acc.reshape(B, ty + q - 1, phases * m, N).astype(g.dtype)
    if out.shape[1] < gy:  # cell rows past the tile extent are structurally zero
        out = jnp.pad(out, ((0, 0), (0, gy - out.shape[1]), (0, 0), (0, 0)))
    return out[:, :gy]


def winograd_conv1d_fused_bwd_w(
    cells: jax.Array,  # (B, Gy, phases*m, N) the forward's cell-layout input
    g: jax.Array,  # (B, ty, S2*m, M)
    inv_packed: jax.Array,  # (C, m) fp32
    bt_mat: tuple[tuple[float, ...], ...],
    *,
    pos_idx: tuple[int, ...],
    sub_slices: tuple[tuple[int, int], ...],
    m: int,
    n: int,
    ty: int,
    phases: int = 1,
    block_t: int = 128,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dL/dww_packed (C, N, M) of the 1D fused engine: recompute the rank-1
    transformed tiles host-side, reduce the tile axis in
    ``domain_engine_bwd_w``."""
    B, _, s2m, M = g.shape
    xw = _xw_from_cells_1d(cells, bt_mat, m=m, n=n, ty=ty, phases=phases)
    return domain_engine_bwd_w(
        xw.astype(cells.dtype), g.reshape(B * ty, s2m, M), inv_packed,
        pos_idx=pos_idx, sub_slices=sub_slices, m2=m,
        block_t=block_t, block_n=block_n, block_m=block_m, interpret=interpret,
    )

