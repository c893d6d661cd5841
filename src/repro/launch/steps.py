"""Lowerable step functions + input_specs for every (arch x shape) cell.

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, zero allocation) for params, optimizer state, batches and caches;
``build_step`` returns the jit-wrapped callable with in/out shardings bound,
ready for ``.lower(**specs).compile()``.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs import get_config
from repro.configs.base import GANConfig, LMConfig, SHAPES, ShapeConfig
from repro.models import lm as LM
from repro.optim import adamw_init, adamw_update
from repro.parallel import sharding as SH

PARAM_DTYPE = jnp.bfloat16


# ------------------------------------------------------------- LM lowering
def _batch_structs(cfg: LMConfig, shape: ShapeConfig):
    B, T = shape.global_batch, shape.seq_len
    b: dict[str, Any] = {}
    if shape.mode == "decode":
        if cfg.frontend == "stub_embeds":
            b["embeds"] = jax.ShapeDtypeStruct((B, 1, cfg.d_model), PARAM_DTYPE)
        else:
            b["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        return b
    if cfg.frontend == "stub_embeds":
        b["embeds"] = jax.ShapeDtypeStruct((B, T, cfg.d_model), PARAM_DTYPE)
    else:
        b["tokens"] = jax.ShapeDtypeStruct((B, T), jnp.int32)
    if shape.mode == "train":
        b["labels"] = jax.ShapeDtypeStruct((B, T), jnp.int32)
    if cfg.mrope_sections is not None:
        b["positions"] = jax.ShapeDtypeStruct((B, T, 3), jnp.int32)
    return b


def _decode_batch_specs(cfg, shape, mesh, axes):
    nb = 1
    for a in axes.batch:
        nb *= mesh.shape[a]
    batch_ax = axes.batch if shape.global_batch % nb == 0 else None
    sp: dict[str, Any] = {}
    if cfg.frontend == "stub_embeds":
        sp["embeds"] = P(batch_ax, None, None)
    else:
        sp["tokens"] = P(batch_ax, None)
    return sp


def lm_input_specs(cfg: LMConfig, shape: ShapeConfig, mesh: Mesh):
    """Returns (arg_structs, in_shardings, out_shardings, meta) for the cell."""
    axes = SH.MeshAxes.for_mesh(mesh)
    pspecs, fallbacks = SH.lm_param_specs(cfg, mesh, axes)
    params_struct = jax.eval_shape(lambda k: LM.lm_init(k, cfg, PARAM_DTYPE),
                                   jax.ShapeDtypeStruct((2,), jnp.uint32))
    meta = {"fallbacks": fallbacks}

    if shape.mode == "train":
        opt_struct = jax.eval_shape(adamw_init, params_struct)
        ospecs = SH.opt_specs(pspecs)
        bstructs = _batch_structs(cfg, shape)
        bspecs = SH.lm_batch_specs(cfg, shape, mesh, axes)
        args = (params_struct, opt_struct, bstructs)
        in_sh = (pspecs, ospecs, bspecs)
        out_sh = (pspecs, ospecs, P())
        return args, in_sh, out_sh, meta

    nb = 1
    for a in axes.batch:
        nb *= mesh.shape[a]
    batch_ax = axes.batch if shape.global_batch % nb == 0 else None
    v_ax = axes.tp if cfg.vocab % mesh.shape[axes.tp] == 0 else None
    logits_spec = P(batch_ax, v_ax)

    if shape.mode == "prefill":
        bstructs = _batch_structs(cfg, shape)
        bspecs = SH.lm_batch_specs(cfg, shape, mesh, axes)
        cspecs = SH.cache_specs(cfg, shape, mesh, axes)
        args = (params_struct, bstructs)
        in_sh = (pspecs, bspecs)
        out_sh = (logits_spec, cspecs)  # (last logits, cache)
        return args, in_sh, out_sh, meta

    # decode
    seq_shard = shape.name == "long_500k"
    cache_struct = jax.eval_shape(
        lambda: LM.init_cache(cfg, shape.global_batch, shape.seq_len, PARAM_DTYPE)
    )
    cspecs = SH.cache_specs(cfg, shape, mesh, axes, seq_shard=seq_shard)
    bstructs = _batch_structs(cfg, shape)
    bspecs = _decode_batch_specs(cfg, shape, mesh, axes)
    cache_len = jax.ShapeDtypeStruct((), jnp.int32)
    args = (params_struct, cache_struct, bstructs, cache_len)
    in_sh = (pspecs, cspecs, bspecs, P())
    out_sh = (logits_spec, cspecs)
    meta["seq_shard"] = seq_shard
    return args, in_sh, out_sh, meta


def build_lm_step(cfg: LMConfig, shape: ShapeConfig, mesh: Mesh):
    """Returns (jit_fn, arg_structs, meta)."""
    args, in_sh, out_sh, meta = lm_input_specs(cfg, shape, mesh)
    named = lambda tree: compat.tree_map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )
    shard_act = None  # activation constraints come from input/param shardings

    if shape.mode == "train":

        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(
                lambda p: LM.train_loss(p, cfg, batch, q_chunk=cfg.q_chunk, loss_chunk=cfg.loss_chunk, mesh=mesh)
            )(params)
            params, opt, _ = adamw_update(params, grads, opt, lr=3e-4, max_grad_norm=1.0)
            return params, opt, loss

        fn = jax.jit(
            train_step, in_shardings=named(in_sh), out_shardings=named(out_sh),
            donate_argnums=(0, 1),
        )
        return fn, args, meta

    if shape.mode == "prefill":

        def prefill_step(params, batch):
            return LM.prefill(params, cfg, batch, q_chunk=cfg.q_chunk, max_len=shape.seq_len + 1, mesh=mesh)

        fn = jax.jit(prefill_step, in_shardings=named(in_sh), out_shardings=named(out_sh))
        return fn, args, meta

    seq_shard = meta.get("seq_shard", False)

    def serve_step(params, cache, batch, cache_len):
        tok = batch.get("tokens", batch.get("embeds"))
        return LM.decode_step(
            params, cfg, cache, tok, cache_len,
            mesh=mesh if seq_shard else None,
            seq_shard_axis="data" if seq_shard else None,
        )

    fn = jax.jit(
        serve_step, in_shardings=named(in_sh), out_shardings=named(out_sh),
        donate_argnums=(1,),
    )
    return fn, args, meta


# ------------------------------------------------------------ GAN lowering
GAN_TRAIN_BATCH = 256


def gan_input_specs(cfg: GANConfig, mesh: Mesh, batch: int = GAN_TRAIN_BATCH):
    """Structs + PartitionSpecs for the GAN train step (divisibility-aware,
    shared with train.trainer's sharded path via parallel.sharding)."""
    from repro.models import gan as G

    gp = jax.eval_shape(lambda k: G.generator_init(k, cfg, PARAM_DTYPE),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
    dp = jax.eval_shape(lambda k: G.discriminator_init(k, cfg, PARAM_DTYPE),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
    gspecs, dspecs, fallbacks = SH.gan_param_specs(cfg, mesh)
    zspec, rspec, bfb = SH.gan_batch_specs(cfg, batch, mesh)
    z = jax.ShapeDtypeStruct((batch, cfg.z_dim), PARAM_DTYPE) if cfg.z_dim else \
        jax.ShapeDtypeStruct((batch, cfg.img_hw, cfg.img_hw, 3), PARAM_DTYPE)
    real = jax.ShapeDtypeStruct((batch, cfg.img_hw, cfg.img_hw, 3), PARAM_DTYPE)
    meta = {"fallbacks": fallbacks + bfb}
    return (gp, dp, z, real), (gspecs, dspecs, zspec, rspec), meta


def build_gan_step(cfg: GANConfig, mesh: Mesh, *, settings=None,
                   overlap=None, grad_compression=None, bucket_bytes=None):
    """GSPMD GAN train step by default; ``settings.overlap`` (or any
    ``settings.grad_compression``) delegates to the explicit-collective
    step from ``parallel.overlap`` (prefetched gathers, bucketed
    backward-order grad reduction, sync-BN, ZeRO block updates).  With
    int8 compression the arg structs gain a ``CommState`` of
    error-feedback residuals between the opt states and the batch.

    ``settings=StepSettings(...)`` carries the build knobs (``mesh`` comes
    from the positional arg here; ``batch`` defaults to
    ``GAN_TRAIN_BATCH``); the individual kwargs are the deprecated
    spelling."""
    from repro.train.trainer import (
        _UNSET, _merge_legacy, check_gspmd_partitionable, gan_losses,
    )

    st = _merge_legacy(settings, dict(
        overlap=overlap if overlap is not None else _UNSET,
        grad_compression=(grad_compression if grad_compression is not None
                          else _UNSET),
        bucket_bytes=bucket_bytes if bucket_bytes is not None else _UNSET,
    ), "build_gan_step")
    cfg = st.apply_to_cfg(cfg)
    batch = st.batch if st.batch is not None else GAN_TRAIN_BATCH

    if st.comm:
        from repro.parallel import overlap as OV

        kw = {} if st.bucket_bytes is None else {"bucket_bytes": st.bucket_bytes}
        fn, meta = OV.build_gan_comm_step(
            cfg, mesh, batch=batch, lr=st.lr, b1=st.b1,
            grad_compression=st.grad_compression, dtype=PARAM_DTYPE, **kw,
        )
        (gp, dp, z, real), _, _ = gan_input_specs(cfg, mesh, batch)
        gopt = jax.eval_shape(adamw_init, gp)
        dopt = jax.eval_shape(adamw_init, dp)
        args = (gp, dp, gopt, dopt) + (
            (meta["comm_struct"],) if meta["comm_struct"] is not None else ()
        ) + (z, real)
        return fn, args, meta

    check_gspmd_partitionable(cfg)
    (gp, dp, z, real), (gspecs, dspecs, zspec, rspec), meta = \
        gan_input_specs(cfg, mesh, batch)
    gopt = jax.eval_shape(adamw_init, gp)
    dopt = jax.eval_shape(adamw_init, dp)
    gosp = SH.opt_specs(gspecs)
    dosp = SH.opt_specs(dspecs)

    def step(gp_, dp_, go_, do_, z_, real_):
        # simultaneous G/D update from one shared forward — mirrors
        # train.trainer.make_gan_step (two vjp pulls, one linearization)
        def both(g, d):
            gl, dl, (gs, ds, _) = gan_losses(g, d, cfg, z_, real_)
            return (gl, dl), (gs, ds)

        (gl, dl), vjp, _ = jax.vjp(both, gp_, dp_, has_aux=True)
        one, zero = jnp.ones_like(gl), jnp.zeros_like(dl)
        ggrads, _ = vjp((one, zero))
        _, dgrads = vjp((zero, one))
        gp2, go2, _ = adamw_update(gp_, ggrads, go_, lr=st.lr, b1=st.b1)
        dp2, do2, _ = adamw_update(dp_, dgrads, do_, lr=st.lr, b1=st.b1)
        return gp2, dp2, go2, do2, gl, dl

    named = lambda tree: compat.tree_map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )
    fn = jax.jit(
        step,
        in_shardings=named((gspecs, dspecs, gosp, dosp, zspec, rspec)),
        out_shardings=named((gspecs, dspecs, gosp, dosp, P(), P())),
        donate_argnums=(0, 1, 2, 3) if st.donate else (),
    )
    return fn, (gp, dp, gopt, dopt, z, real), meta


def build_step(arch: str, shape_name: str, mesh: Mesh):
    cfg = get_config(arch)
    if isinstance(cfg, GANConfig):
        return build_gan_step(cfg, mesh)
    return build_lm_step(cfg, SHAPES[shape_name], mesh)
