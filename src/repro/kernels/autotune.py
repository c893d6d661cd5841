"""Block-size / fusion autotuner for the Winograd-DeConv Pallas engine.

The paper fixes its tiling (T_m=4, T_n=128) by an analytic roofline DSE
(Sec. IV-C, reproduced in benchmarks/dse.py and following Ahmad & Pasha,
arXiv:1903.01811); on TPU the analytic model mispredicts because Mosaic's
scheduling and VMEM double-buffering are opaque, so we *measure*: enumerate
(block_t | block_ty, block_n, block_m) x {fused, unfused pre-PE} — and,
since PR 2, the backward engines' block sizes — and time the jitted engine
end-to-end.

Entry points:
  candidate_configs(...)  -> the default sweep grid (optional bwd axes)
  autotune_deconv(...)    -> timed sweep for one (dims, input shape) cell,
                             sorted fastest-first; mode selects what is
                             timed: "fwd" (inference), "grad"
                             (value_and_grad, exercising the Pallas backward
                             engines), or "step" (full AdamW update —
                             prepacked configs keep the whole step in the
                             Winograd domain)
  best_config(...)        -> just the winner

Used by benchmarks/dse.py (reports the sweep next to the analytic model),
benchmarks/train_step.py (the train-step benchmark) and
benchmarks/hillclimb.py (--autotune-deconv).  On CPU the kernels run in
interpret mode — timings there order host-loop overheads, not MXU work, so
they validate the machinery; on a real TPU backend the same sweep measures
the thing the paper's DSE approximates.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tdc import DeconvDims
from repro.device import interpret_mode
from repro.optim import adamw_init, adamw_update

from . import ops

__all__ = [
    "EngineConfig", "candidate_configs", "small_candidates",
    "epilogue_candidates", "conv_candidates", "conv1d_candidates",
    "autotune_deconv", "autotune_conv", "autotune_conv1d", "best_config",
    "make_timed_fn", "make_timed_conv_fn", "make_timed_conv1d_fn", "time_one",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """One point of the engine design space.

    ``bwd_block_*`` tile the backward engines (None mirrors the forward
    choice); ``prepack`` times the prepacked-weights path (G-transform +
    pack hoisted out of the step entirely).  ``epilogue`` (an activation
    name) times the epilogue-fused finalize (bias/act + depth-to-space in
    VMEM) and ``emit_cells`` the cell-layout output mode that chains into
    the next layer — the fused-pre epilogue axes of the design space.
    """

    fuse_pre: bool
    block_t: int = 128  # unfused: flat tile-axis block
    block_ty: int = 8  # fused: tile-row block (T block = block_ty * tx)
    block_n: int = 128
    block_m: int = 128
    bwd_block_t: Optional[int] = None
    bwd_block_ty: Optional[int] = None
    bwd_block_n: Optional[int] = None
    bwd_block_m: Optional[int] = None
    prepack: bool = False
    epilogue: Optional[str] = None  # None | "none" | "relu" | "leaky_relu" | "tanh"
    emit_cells: bool = False

    def kwargs(self) -> dict:
        return dict(
            fuse_pre=self.fuse_pre,
            block_t=self.block_t,
            block_ty=self.block_ty,
            block_n=self.block_n,
            block_m=self.block_m,
            bwd_block_t=self.bwd_block_t,
            bwd_block_ty=self.bwd_block_ty,
            bwd_block_n=self.bwd_block_n,
            bwd_block_m=self.bwd_block_m,
            epilogue=self.epilogue,
            emit_cells=self.emit_cells,
        )


def candidate_configs(
    *,
    block_t: Sequence[int] = (64, 128, 256),
    block_ty: Sequence[int] = (4, 8, 16),
    block_n: Sequence[int] = (128, 256),
    block_m: Sequence[int] = (128, 256),
    bwd_block_t: Sequence[Optional[int]] = (None,),
    bwd_block_ty: Sequence[Optional[int]] = (None,),
    bwd_block_n: Sequence[Optional[int]] = (None,),
    bwd_block_m: Sequence[Optional[int]] = (None,),
    include_fused: bool = True,
    include_unfused: bool = True,
    prepack: bool = False,
    epilogue: Sequence[Optional[str]] = (None,),
    emit_cells: Sequence[bool] = (False,),
) -> list[EngineConfig]:
    """The default sweep grid over block sizes and the pre-PE fusion choice.

    The backward axes default to a single None (mirror-forward) point so
    forward-only sweeps stay the same size; pass explicit lists (e.g.
    ``bwd_block_n=(64, 128, 256)``) to sweep the backward engines too.
    ``epilogue``/``emit_cells`` sweep the fused finalize's epilogue and
    cell-chaining output modes (fused-pre configs only — the unfused engine
    has no in-kernel depth-to-space).
    """
    out: list[EngineConfig] = []
    for bn in block_n:
        for bm in block_m:
            for bbn in bwd_block_n:
                for bbm in bwd_block_m:
                    if include_unfused:
                        out.extend(
                            EngineConfig(
                                False, block_t=bt, block_n=bn, block_m=bm,
                                bwd_block_t=bbt, bwd_block_n=bbn,
                                bwd_block_m=bbm, prepack=prepack,
                            )
                            for bt in block_t
                            for bbt in bwd_block_t
                        )
                    if include_fused:
                        out.extend(
                            EngineConfig(
                                True, block_ty=bty, block_n=bn, block_m=bm,
                                bwd_block_ty=bbty, bwd_block_n=bbn,
                                bwd_block_m=bbm, prepack=prepack,
                                epilogue=epi, emit_cells=ec,
                            )
                            for bty in block_ty
                            for bbty in bwd_block_ty
                            for epi in epilogue
                            for ec in emit_cells
                        )
    return out


def epilogue_candidates(block_ty: Sequence[int] = (4, 8)) -> list[EngineConfig]:
    """Compact fused-pre sweep over the epilogue/chain axes: scratch-out vs
    epilogue-fused NHWC vs cell-layout chaining, per tile-row block."""
    out: list[EngineConfig] = []
    for bty in block_ty:
        out.append(EngineConfig(True, block_ty=bty, block_n=128, block_m=128))
        out.append(
            EngineConfig(True, block_ty=bty, block_n=128, block_m=128,
                         epilogue="leaky_relu")
        )
        out.append(
            EngineConfig(True, block_ty=bty, block_n=128, block_m=128,
                         epilogue="leaky_relu", emit_cells=True)
        )
    return out


def conv_candidates(
    block_ty: Sequence[int] = (4, 8, 16),
    *,
    epilogue: Sequence[Optional[str]] = (None, "leaky_relu"),
    emit_cells: Sequence[bool] = (False, True),
    prepack: bool = True,
) -> list[EngineConfig]:
    """Sweep grid for the Winograd Conv engine (always fused: the conv
    engine consumes the phase-major cell layout), including the epilogue /
    cell-chaining output axes — the conv mirror of epilogue_candidates."""
    out: list[EngineConfig] = []
    for bty in block_ty:
        for epi in epilogue:
            for ec in emit_cells:
                if ec and epi is None:
                    continue  # chained emit always rides an epilogue config
                out.append(
                    EngineConfig(
                        True, block_ty=bty, block_n=128, block_m=128,
                        prepack=prepack, epilogue=epi, emit_cells=ec,
                    )
                )
    return out


def small_candidates() -> list[EngineConfig]:
    """The compact fused-vs-unfused grid both benchmarks sweep by default —
    small enough for CPU interpret mode, one axis of block variation each."""
    return [
        EngineConfig(False, block_t=64, block_n=128, block_m=128),
        EngineConfig(False, block_t=128, block_n=128, block_m=128),
        EngineConfig(True, block_ty=4, block_n=128, block_m=128),
        EngineConfig(True, block_ty=8, block_n=128, block_m=128),
    ]


def time_one(fn, args, repeats: int) -> float:
    y = fn(*args)
    jax.block_until_ready(y)  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _mesh_shardings(mesh, cfg, mode, input_shape, c_out):
    """In-shardings for a timed fn under a mesh: batch-sharded x, FSDP on the
    weight's N dim + TP on M where they divide (mirroring gan_param_specs'
    rules for the packed layout), AdamW moments following the weight leaf."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import (
        MeshAxes, SpecBuilder, _tp_or_none, named, opt_specs,
    )

    axes = MeshAxes.for_mesh(mesh)
    b = SpecBuilder(mesh, axes)
    tp = _tp_or_none(mesh, axes)
    B, H, W, N = input_shape
    xspec = P(b.dim("x.B", B, axes.batch), None, None, None)
    n_ax, m_ax = b.dim("w.N", N, axes.fsdp), b.dim("w.M", c_out, tp)
    leaf = P(None, n_ax, m_ax) if (cfg is not None and cfg.prepack) else \
        P(None, None, n_ax, m_ax)
    wspec = ops.PackedDeconv(leaf, P(None, None)) if (cfg is not None and cfg.prepack) \
        else leaf
    if mode == "step":
        tree = (xspec, wspec, opt_specs(leaf))
    else:
        tree = (xspec, wspec)
    return named(mesh, tree), b.fallbacks


def make_timed_fn(cfg: Optional[EngineConfig], dims: DeconvDims, mode: str, interpret: bool,
                  mesh=None, input_shape=None, c_out: Optional[int] = None,
                  _shardings=None, grad_compression: Optional[str] = None):
    """Build the callable the sweep times, per mode x variant.

    ``cfg=None`` times the pure-JAX reference path (no Pallas, no packing);
    ``cfg.prepack`` hoists the G-transform + pack out of the timed region.
    Returns (fn, make_args) where make_args(x, w) produces fn's argument
    tuple.  The three variants differ only in the forward callable and which
    leaf of the params the optimizer updates.

    With ``mesh`` (requires ``input_shape`` + ``c_out`` for divisibility),
    the jit is NamedSharding-constrained — batch-sharded input, FSDP/TP
    weight leaf, sharded moments — so the timings (and therefore the block
    choices ``mode='step'`` picks) reflect the sharded layout the multi-
    device GAN train step runs under, not the single-device one.

    ``grad_compression='int8'`` (``mode='step'`` + ``mesh`` only) instead
    times a data-parallel shard_map step whose weight-grad all-reduce goes
    through ``parallel.compression.compressed_psum`` with an error-feedback
    residual threaded through the arguments — the layer-level mirror of the
    compressed whole-model step, so block choices can be tuned under the
    collective pattern they will actually run with.
    """
    if cfg is None:
        from repro.core.winograd_deconv import winograd_deconv2d

        fwd = lambda x, p: winograd_deconv2d(x, p, dims)
        make_params = lambda w: w
        get_leaf = lambda p: p
        set_leaf = lambda p, leaf: leaf
    elif cfg.prepack:
        kw = dict(interpret=interpret, **cfg.kwargs())
        fwd = lambda x, p: ops.winograd_deconv2d_packed(x, p, dims, **kw)
        make_params = lambda w: ops.prepack(w, dims)
        get_leaf = lambda p: p.ww
        set_leaf = lambda p, leaf: ops.PackedDeconv(leaf, p.inv)
    else:
        kw = dict(interpret=interpret, **cfg.kwargs())
        fwd = lambda x, p: ops.winograd_deconv2d_fused(x, p, dims, **kw)
        make_params = lambda w: w
        get_leaf = lambda p: p
        set_leaf = lambda p, leaf: leaf

    def loss(x, p):
        return jnp.sum(fwd(x, p).astype(jnp.float32) ** 2)

    if grad_compression is not None:
        if grad_compression != "int8":
            raise ValueError(f"unknown grad_compression: {grad_compression!r}")
        if mode != "step" or mesh is None:
            raise ValueError("grad_compression requires mode='step' and a mesh")
        if input_shape is None:
            raise ValueError("grad_compression timing needs input_shape")
        from jax.sharding import PartitionSpec as P

        from repro import compat
        from repro.parallel.compression import compressed_psum
        from repro.parallel.sharding import MeshAxes

        axes = MeshAxes.for_mesh(mesh).batch
        rows = 1
        for a in axes:
            rows *= mesh.shape[a]
        if input_shape[0] % rows != 0:
            raise ValueError(
                f"batch {input_shape[0]} not divisible by {rows} shards"
            )

        # DP over the batch axes, replicated weights: the local weight grad
        # is int8-all-reduced with error feedback, residual rides along with
        # a leading shard dim (one row per shard).
        def comm_step(x, p, opt, res):
            _, g = jax.value_and_grad(loss, argnums=1)(x, p)
            red, r2 = compressed_psum(get_leaf(g), res[0], axes, axis_size=rows)
            leaf2, opt2, _ = adamw_update(get_leaf(p), red, opt, lr=1e-3)
            return set_leaf(p, leaf2), opt2, r2[None]

        xspec = P(axes, *([None] * (len(input_shape) - 1)))
        fn = jax.jit(compat.shard_map(
            comm_step, mesh=mesh,
            in_specs=(xspec, P(), P(), P(axes)),
            out_specs=(P(), P(), P(axes)),
            check_vma=False,
        ))

        def make_args(x, w):
            p = make_params(w)
            leaf = get_leaf(p)
            res = jnp.zeros((rows,) + tuple(leaf.shape), jnp.float32)
            return (x, p, adamw_init(leaf), res)

        return fn, make_args

    jit_kw: dict = {}
    if mesh is not None:
        if _shardings is None:
            if input_shape is None or c_out is None:
                raise ValueError("mesh timing needs input_shape and c_out")
            _shardings, _ = _mesh_shardings(mesh, cfg, mode, input_shape, c_out)
        jit_kw["in_shardings"] = _shardings

    if mode == "fwd":
        fn = jax.jit(fwd, **jit_kw)
    elif mode == "grad":
        fn = jax.jit(jax.value_and_grad(loss, argnums=1), **jit_kw)
    elif mode == "step":
        def step(x, p, opt):
            _, g = jax.value_and_grad(loss, argnums=1)(x, p)
            leaf2, opt2, _ = adamw_update(get_leaf(p), get_leaf(g), opt, lr=1e-3)
            return set_leaf(p, leaf2), opt2

        fn = jax.jit(step, **jit_kw)
    else:
        raise ValueError(mode)

    def make_args(x, w):
        p = make_params(w)
        if mode == "step":
            return (x, p, adamw_init(get_leaf(p)))
        return (x, p)

    return fn, make_args


def make_timed_conv_fn(cfg: Optional[EngineConfig], cdims, mode: str, interpret: bool):
    """Conv counterpart of ``make_timed_fn``: builds the timed callable for
    one discriminator conv layer.  ``cfg=None`` times ``lax.conv`` (the
    pre-engine baseline); otherwise the fused Winograd Conv engine, with
    ``cfg.prepack`` hoisting the G-transform + pack out of the timed region
    and ``cfg.epilogue``/``cfg.emit_cells`` selecting the fused finalize's
    output mode (timed through an emit-cells-aware loss so grads flow)."""
    if cfg is None:
        def fwd(x, p):
            return jax.lax.conv_general_dilated(
                x, p, (cdims.stride, cdims.stride),
                [(cdims.padding, cdims.pad_hi), (cdims.padding, cdims.pad_hi)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        make_params = lambda w: w
        get_leaf = lambda p: p
        set_leaf = lambda p, leaf: leaf
    else:
        kw = dict(
            interpret=interpret, block_ty=cfg.block_ty, block_n=cfg.block_n,
            block_m=cfg.block_m, bwd_block_ty=cfg.bwd_block_ty,
            bwd_block_n=cfg.bwd_block_n, bwd_block_m=cfg.bwd_block_m,
            epilogue=cfg.epilogue, emit_cells=cfg.emit_cells,
        )
        if cfg.prepack:
            fwd = lambda x, p: ops.winograd_conv2d_packed(x, p, cdims, **kw)
            make_params = lambda w: ops.prepack_conv(w, cdims)
            get_leaf = lambda p: p.ww
            set_leaf = lambda p, leaf: ops.PackedConv(leaf, p.inv)
        else:
            fwd = lambda x, p: ops.winograd_conv2d(x, p, cdims, **kw)
            make_params = lambda w: w
            get_leaf = lambda p: p
            set_leaf = lambda p, leaf: leaf

    def loss(x, p):
        return jnp.sum(fwd(x, p).astype(jnp.float32) ** 2)

    if mode == "fwd":
        fn = jax.jit(fwd)
    elif mode == "grad":
        fn = jax.jit(jax.value_and_grad(loss, argnums=1))
    elif mode == "step":
        def step(x, p, opt):
            _, g = jax.value_and_grad(loss, argnums=1)(x, p)
            leaf2, opt2, _ = adamw_update(get_leaf(p), get_leaf(g), opt, lr=1e-3)
            return set_leaf(p, leaf2), opt2

        fn = jax.jit(step)
    else:
        raise ValueError(mode)

    def make_args(x, w):
        p = make_params(w)
        if mode == "step":
            return (x, p, adamw_init(get_leaf(p)))
        return (x, p)

    return fn, make_args


def conv1d_candidates(
    block_ty: Sequence[int] = (32, 64, 128),
    *,
    prepack: bool = True,
) -> list[EngineConfig]:
    """Sweep grid for the 1D engine (audio deconv / SSM prefill conv): the
    1D finalize has no tx axis, so the tile-row block is the only spatial
    knob next to the (block_n, block_m) channel tiling."""
    return [
        EngineConfig(True, block_ty=bty, block_n=bn, block_m=bm, prepack=prepack)
        for bty in block_ty
        for bn in (128, 256)
        for bm in (128, 256)
    ]


def make_timed_conv1d_fn(cfg: Optional[EngineConfig], geom, mode: str,
                         interpret: bool):
    """1D counterpart of ``make_timed_conv_fn``.  ``geom`` is either an int
    kernel size (stride-1 causal conv — the SSM prefill shape) or a
    ``DeconvDims`` (the audio decoder's upsampling deconv).  ``cfg=None``
    times the ``lax.conv_general_dilated`` baseline for the same geometry."""
    is_deconv = isinstance(geom, DeconvDims)
    if cfg is None:
        if is_deconv:
            from repro.models.gan import lax_deconv1d

            fwd = lambda x, p: lax_deconv1d(x, p, geom)
        else:
            def fwd(x, p):
                return jax.lax.conv_general_dilated(
                    x, p, (1,), [(geom - 1, 0)],
                    dimension_numbers=("NHC", "HIO", "NHC"),
                )

        make_params = lambda w: w
        get_leaf = lambda p: p
        set_leaf = lambda p, leaf: leaf
    else:
        kw = dict(
            interpret=interpret, block_ty=cfg.block_ty, block_n=cfg.block_n,
            block_m=cfg.block_m, bwd_block_ty=cfg.bwd_block_ty,
            bwd_block_n=cfg.bwd_block_n, bwd_block_m=cfg.bwd_block_m,
        )
        if is_deconv:
            if cfg.prepack:
                fwd = lambda x, p: ops.winograd_deconv1d_packed(x, p, geom, **kw)
                make_params = lambda w: ops.prepack_deconv1d(w, geom)
            else:
                fwd = lambda x, p: ops.winograd_deconv1d(x, p, geom, **kw)
                make_params = lambda w: w
        else:
            if cfg.prepack:
                fwd = lambda x, p: ops.winograd_conv1d_packed(x, p, geom, **kw)
                make_params = lambda w: ops.prepack_conv1d(w, geom)
            else:
                fwd = lambda x, p: ops.winograd_conv1d(x, p, **kw)
                make_params = lambda w: w
        if cfg.prepack:
            get_leaf = lambda p: p.ww
            set_leaf = lambda p, leaf: ops.PackedConv1d(leaf, p.inv)
        else:
            get_leaf = lambda p: p
            set_leaf = lambda p, leaf: leaf

    def loss(x, p):
        return jnp.sum(fwd(x, p).astype(jnp.float32) ** 2)

    if mode == "fwd":
        fn = jax.jit(fwd)
    elif mode == "grad":
        fn = jax.jit(jax.value_and_grad(loss, argnums=1))
    elif mode == "step":
        def step(x, p, opt):
            _, g = jax.value_and_grad(loss, argnums=1)(x, p)
            leaf2, opt2, _ = adamw_update(get_leaf(p), get_leaf(g), opt, lr=1e-3)
            return set_leaf(p, leaf2), opt2

        fn = jax.jit(step)
    else:
        raise ValueError(mode)

    def make_args(x, w):
        p = make_params(w)
        if mode == "step":
            return (x, p, adamw_init(get_leaf(p)))
        return (x, p)

    return fn, make_args


def autotune_conv1d(
    geom,  # int kernel (stride-1 causal conv) | DeconvDims (1D deconv)
    input_shape: tuple[int, int, int],  # (B, L, N)
    c_out: int,
    *,
    dtype=jnp.float32,
    candidates: Iterable[EngineConfig] | None = None,
    interpret: bool | None = None,
    repeats: int = 3,
    seed: int = 0,
    mode: str = "fwd",
) -> list[dict]:
    """Time every candidate 1D engine config for one conv1d/deconv1d layer
    (``mode`` as in ``autotune_deconv``).  Returns rows sorted
    fastest-first; infeasible configs kept with ok=False."""
    if mode not in ("fwd", "grad", "step"):
        raise ValueError(mode)
    if interpret is None:
        interpret = interpret_mode()
    if candidates is None:
        candidates = conv1d_candidates()
    B, L, N = input_shape
    K = geom.kernel if isinstance(geom, DeconvDims) else geom
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, L, N)), dtype)
    w = jnp.asarray(rng.standard_normal((K, N, c_out)), dtype)
    rows: list[dict] = []
    for cfg in candidates:
        try:
            fn, make_args = make_timed_conv1d_fn(cfg, geom, mode, interpret)
            dt = time_one(fn, make_args(x, w), repeats)
            rows.append({"config": cfg, "ms": dt * 1e3, "ok": True, "error": ""})
        except Exception as e:
            rows.append(
                {"config": cfg, "ms": float("inf"), "ok": False,
                 "error": f"{type(e).__name__}: {e}"[:200]}
            )
    rows.sort(key=lambda r: r["ms"])
    return rows


def autotune_conv(
    cdims,
    input_shape: tuple[int, int, int, int],  # (B, H, W, N)
    c_out: int,
    *,
    dtype=jnp.float32,
    candidates: Iterable[EngineConfig] | None = None,
    interpret: bool | None = None,
    repeats: int = 3,
    seed: int = 0,
    mode: str = "fwd",
) -> list[dict]:
    """Time every candidate conv engine config for one discriminator layer
    (``mode`` as in ``autotune_deconv``: fwd / grad / full AdamW step).
    Returns rows sorted fastest-first; infeasible configs kept with
    ok=False."""
    if mode not in ("fwd", "grad", "step"):
        raise ValueError(mode)
    if interpret is None:
        interpret = interpret_mode()
    if candidates is None:
        candidates = conv_candidates()
    B, H, W, N = input_shape
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, H, W, N)), dtype)
    w = jnp.asarray(
        rng.standard_normal((cdims.kernel, cdims.kernel, N, c_out)), dtype
    )
    rows: list[dict] = []
    for cfg in candidates:
        try:
            fn, make_args = make_timed_conv_fn(cfg, cdims, mode, interpret)
            dt = time_one(fn, make_args(x, w), repeats)
            rows.append({"config": cfg, "ms": dt * 1e3, "ok": True, "error": ""})
        except Exception as e:
            rows.append(
                {"config": cfg, "ms": float("inf"), "ok": False,
                 "error": f"{type(e).__name__}: {e}"[:200]}
            )
    rows.sort(key=lambda r: r["ms"])
    return rows


def autotune_deconv(
    dims: DeconvDims,
    input_shape: tuple[int, int, int, int],  # (B, H, W, N)
    c_out: int,
    *,
    dtype=jnp.float32,
    candidates: Iterable[EngineConfig] | None = None,
    interpret: bool | None = None,
    repeats: int = 3,
    seed: int = 0,
    mode: str = "fwd",
    mesh=None,
    grad_compression: Optional[str] = None,
) -> list[dict]:
    """Time every candidate engine config for one deconv layer.

    ``mode='fwd'`` times inference; ``'grad'`` times value_and_grad (the
    Pallas backward engines); ``'step'`` times a full AdamW update.  Returns
    a list of rows {config, ms, ok, error} sorted fastest-first; configs
    that fail to compile/run are kept (ok=False) so sweeps surface
    infeasible corners instead of hiding them.

    ``mesh`` times each candidate under that mesh's sharded layout
    (batch-sharded input, FSDP/TP weights, sharded moments): arXiv
    1903.01811's point that the tile/parallelism design space must be
    re-explored per configuration applies to the mesh layout too, so block
    choices for the sharded train step should come from a sharded sweep.

    ``grad_compression='int8'`` (``mode='step'`` with ``mesh`` only) times
    the data-parallel step whose weight-grad all-reduce is the int8
    error-feedback ``compressed_psum`` — the collective pattern the
    compressed whole-model step runs with.
    """
    if mode not in ("fwd", "grad", "step"):  # fail fast: a bad mode is a
        raise ValueError(mode)  # caller error, not a per-config infeasibility
    if grad_compression is not None and (mode != "step" or mesh is None):
        raise ValueError("grad_compression requires mode='step' and a mesh")
    if interpret is None:
        interpret = interpret_mode()
    if candidates is None:
        candidates = candidate_configs()
    B, H, W, N = input_shape
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, H, W, N)), dtype)
    w = jnp.asarray(
        rng.standard_normal((dims.kernel, dims.kernel, N, c_out)), dtype
    )
    rows: list[dict] = []
    for cfg in candidates:
        row: dict = {"config": cfg}
        shardings = None
        if mesh is not None and grad_compression is None:
            # surface dims that silently fell back to replication — a sweep
            # that claims to measure the sharded layout must say when it
            # actually timed a replicated one (the compressed step is DP:
            # replicated weights by construction, nothing to surface)
            shardings, fb = _mesh_shardings(mesh, cfg, mode, input_shape, c_out)
            row["sharding_fallbacks"] = fb
        try:
            fn, make_args = make_timed_fn(cfg, dims, mode, interpret,
                                          mesh=mesh, input_shape=input_shape,
                                          c_out=c_out, _shardings=shardings,
                                          grad_compression=grad_compression)
            args = make_args(x, w)
            dt = time_one(fn, args, repeats)
            rows.append({**row, "ms": dt * 1e3, "ok": True, "error": ""})
        except Exception as e:  # infeasible block shape, OOM, ...
            rows.append(
                {**row, "ms": float("inf"), "ok": False,
                 "error": f"{type(e).__name__}: {e}"[:200]}
            )
    rows.sort(key=lambda r: r["ms"])
    return rows


def best_config(
    dims: DeconvDims,
    input_shape: tuple[int, int, int, int],
    c_out: int,
    **kw,
) -> EngineConfig:
    rows = autotune_deconv(dims, input_shape, c_out, **kw)
    for r in rows:
        if r["ok"]:
            return r["config"]
    raise RuntimeError(f"no engine config ran for {dims}: {rows[0]['error']}")
