"""GAN generators/discriminators built on the Winograd-DeConv core.

The generator's deconv layers dispatch to any of the paper's three method
families (``deconv_impl``): 'ref' / 'pallas' / 'pallas_fused_pre' (this
paper; the latter fuses the pre-PE B-transform into the engine), 'tdc' ([14]),
'zero_padded' ([10-12]), 'lax' (XLA's own conv_transpose) — all numerically
identical, so speed comparisons are apples-to-apples.

``*_prepacked`` impls train and serve *in the Winograd domain*: the
generator's deconv params are the packed (C, N, M) transformed weights
(``kernels.ops.prepack``, run once at init), the forward consumes them
directly, and ``jax.grad`` flows straight out of the Pallas backward
engines into the optimizer — no G-transform, pack, or their transposes
anywhere in the training step.

The discriminator mirrors all of it through ``conv_impl``: its stride-2
convs run as the phase-decomposed Winograd Conv engine ('lax' stays the
XLA baseline), ``*_prepacked`` impls keep packed (C, N, M) conv weights in
params, and the ``pallas_chained`` impls run the whole trunk conv-to-conv
in the cell domain — in training mode too, via the two-pass cell-domain
batchnorm (``_bn_act_cells``), so the FULL adversarial step (G update + D
update, every gradient) stays on the Pallas engines.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import GANConfig
from repro.core import tdc_deconv2d, zero_padded_deconv2d, lax_deconv2d, winograd_deconv2d
from repro.core.tdc import ConvDims, DeconvDims, conv_same_dims
from repro.kernels import ops as kops

from . import layers as L

Params = dict[str, Any]

# deconv_impl -> winograd_deconv2d_packed kwargs for the prepacked variants
# (params hold packed Winograd-domain weights instead of raw K_D x K_D ones).
# The *chained* impls share the per-layer kwargs of the fused-pre engine
# (used for training-mode steps, where batch-stat BN can't fold into the
# epilogue) and additionally run the whole eval-mode generator forward as
# one cell-to-cell pipeline (see generator_apply / _chained_deconv_trunk).
_PREPACKED_KW: dict[str, dict] = {
    "prepacked_ref": dict(backend="ref"),
    "pallas_prepacked": dict(backend="pallas"),
    "pallas_fused_pre_prepacked": dict(backend="pallas", fuse_pre=True),
    "pallas_prepacked_interpret": dict(
        backend="pallas", interpret=True, **kops.INTERPRET_BLOCKS
    ),
    "pallas_fused_pre_prepacked_interpret": dict(
        backend="pallas", fuse_pre=True, interpret=True,
        **kops.INTERPRET_BLOCKS_FUSED,
    ),
    "pallas_chained": dict(backend="pallas", fuse_pre=True),
    "pallas_chained_interpret": dict(
        backend="pallas", fuse_pre=True, interpret=True,
        **kops.INTERPRET_BLOCKS_FUSED,
    ),
    "chained_ref": dict(backend="ref", fuse_pre=True),
}

# chained impls -> winograd_deconv2d_cells kwargs for the pipeline calls
_CHAINED_KW: dict[str, dict] = {
    "pallas_chained": dict(backend="pallas"),
    "pallas_chained_interpret": dict(
        backend="pallas", interpret=True,
        block_ty=kops.INTERPRET_BLOCKS_FUSED["block_ty"],
        block_n=kops.INTERPRET_BLOCKS_FUSED["block_n"],
        block_m=kops.INTERPRET_BLOCKS_FUSED["block_m"],
    ),
    "chained_ref": dict(backend="ref"),
}

# raw-weight impl -> its prepacked equivalent (used by serving to drop the
# per-call G-transform without changing the numerics of the chosen backend).
PREPACKED_EQUIV: dict[str, str] = {
    "ref": "prepacked_ref",
    "pallas": "pallas_prepacked",
    "pallas_fused_pre": "pallas_fused_pre_prepacked",
    "pallas_interpret": "pallas_prepacked_interpret",
    "pallas_fused_pre_interpret": "pallas_fused_pre_prepacked_interpret",
}

# prepacked pallas impl -> the chained pipeline that serves it (the ref
# impls stay per-layer: serving keeps their bit-exact reference numerics).
CHAINED_EQUIV: dict[str, str] = {
    "pallas_prepacked": "pallas_chained",
    "pallas_fused_pre_prepacked": "pallas_chained",
    "pallas_prepacked_interpret": "pallas_chained_interpret",
    "pallas_fused_pre_prepacked_interpret": "pallas_chained_interpret",
}


def uses_prepacked(impl: str) -> bool:
    """True if ``impl`` stores packed Winograd-domain weights in params."""
    return impl in _PREPACKED_KW


def uses_chained(impl: str) -> bool:
    """True if ``impl`` runs the generator as one cell-to-cell chained
    engine pipeline (prepacked param layout, fused epilogues in eval mode,
    two-pass cell-domain batch stats in training mode)."""
    return impl in _CHAINED_KW


def serve_impl(impl: str, *, chained: bool = True) -> str:
    """The serving-time deconv_impl for a training-time ``impl``: prepacked
    (G-transform paid once, off the request path), and — for the pallas
    impls, unless ``chained=False`` — the cell-to-cell chained pipeline.
    Idempotent: already-prepacked / already-chained names pass through."""
    impl = PREPACKED_EQUIV.get(impl, impl)
    if chained:
        impl = CHAINED_EQUIV.get(impl, impl)
    return impl


# ------------------------------------------------- discriminator conv impls
# conv_impl -> winograd_conv2d_packed / winograd_conv2d_cells kwargs.  The
# discriminator mirror of the deconv tables: a stride-2 conv runs as the
# phase-decomposed Winograd Conv engine (kernels.ops.winograd_conv2d_*),
# the *_prepacked impls keep the packed (C, N, M) conv weights in params,
# and the chained impls run the whole trunk conv-to-conv in the cell
# domain.  "lax" (the default) is XLA's own conv — the pre-engine baseline.
_CONV_PREPACKED_KW: dict[str, dict] = {
    "prepacked_ref": dict(backend="ref"),
    "pallas_prepacked": dict(backend="pallas"),
    "pallas_prepacked_interpret": dict(
        backend="pallas", interpret=True, **kops.INTERPRET_BLOCKS_CONV
    ),
    "pallas_chained": dict(backend="pallas"),
    "pallas_chained_interpret": dict(
        backend="pallas", interpret=True, **kops.INTERPRET_BLOCKS_CONV
    ),
    "chained_ref": dict(backend="ref"),
}

# raw-weight conv impl -> per-call engine kwargs (pack per call)
_CONV_RAW_KW: dict[str, dict] = {
    "ref": dict(backend="ref"),
    "pallas": dict(backend="pallas"),
    "pallas_interpret": dict(
        backend="pallas", interpret=True, **kops.INTERPRET_BLOCKS_CONV
    ),
}

CONV_PREPACKED_EQUIV: dict[str, str] = {
    "ref": "prepacked_ref",
    "pallas": "pallas_prepacked",
    "pallas_interpret": "pallas_prepacked_interpret",
}

CONV_CHAINED_EQUIV: dict[str, str] = {
    "pallas_prepacked": "pallas_chained",
    "pallas_prepacked_interpret": "pallas_chained_interpret",
}


def uses_prepacked_conv(impl: str) -> bool:
    """True if ``impl`` stores packed Winograd-domain conv weights in the
    discriminator params."""
    return impl in _CONV_PREPACKED_KW


def uses_chained_conv(impl: str) -> bool:
    """True if ``impl`` runs the discriminator trunk as one conv-to-conv
    chained engine pipeline."""
    return impl in ("pallas_chained", "pallas_chained_interpret", "chained_ref")


# ---------------------------------------------------------- block overrides
# Per-layer engine block choices, keyed by (impl, dims, N, M): the
# autotuner's winning forward AND backward blocks (``bwd_block_*``) land
# here and are merged into that impl's applies, instead of the backward
# engines silently mirroring the forward blocks.  Keying by impl keeps
# TPU-tuned tiles away from interpret-mode impls and fused-engine winners
# away from the unfused variant.  Populated by ``install_tuned_blocks``
# (or manually via ``set_deconv_blocks``).
DECONV_BLOCKS: dict[tuple, dict] = {}

_BLOCK_KEYS = (
    "block_t", "block_ty", "block_n", "block_m",
    "bwd_block_t", "bwd_block_ty", "bwd_block_n", "bwd_block_m",
)


def set_deconv_blocks(impl: str, dims: DeconvDims, n_in: int, m_out: int,
                      **blocks) -> None:
    """Register engine block overrides for ``impl`` on every deconv layer
    with this (geometry, N, M) signature; None values are dropped
    (mirror-forward)."""
    bad = set(blocks) - set(_BLOCK_KEYS)
    if bad:
        raise ValueError(f"unknown block keys {sorted(bad)}")
    DECONV_BLOCKS[(impl, dims, n_in, m_out)] = {
        k: v for k, v in blocks.items() if v is not None
    }


def clear_deconv_blocks() -> None:
    DECONV_BLOCKS.clear()


def install_tuned_blocks(cfg: GANConfig, *, mode: str = "grad", batch: int = 1,
                         candidates=None, **autotune_kw) -> list[dict]:
    """Run ``kernels.autotune.autotune_deconv`` per generator layer and wire
    each layer's winning config — including its *backward* blocks — into the
    impl table (the ROADMAP item: stop mirroring forward blocks in the
    backward engines).  Returns the per-layer winner rows for logging.

    The default candidate grid is restricted to the engine variant
    ``cfg.deconv_impl`` actually runs (fused-pre vs unfused, prepacked), and
    winners from a different variant are skipped — numbers measured on a
    code path the model never executes must not land in the table."""
    from repro.kernels.autotune import autotune_deconv, candidate_configs

    impl = cfg.deconv_impl
    fused = _PREPACKED_KW.get(impl, {}).get("fuse_pre", False)
    if candidates is None:
        candidates = candidate_configs(
            include_fused=fused, include_unfused=not fused,
            prepack=uses_prepacked(impl),
        )
    installed = []
    h = cfg.seed_hw
    for li, d in enumerate(cfg.deconvs):
        rows = autotune_deconv(
            d.dims, (batch, h, h, d.c_in), d.c_out, mode=mode,
            candidates=candidates, **autotune_kw,
        )
        won = next(
            (r for r in rows if r["ok"] and r["config"].fuse_pre == fused),
            None,
        )
        if won is not None:
            c = won["config"]
            set_deconv_blocks(
                impl, d.dims, d.c_in, d.c_out,
                **{k: getattr(c, k) for k in _BLOCK_KEYS},
            )
            installed.append({"layer": li, "ms": won["ms"], "config": c})
        else:
            installed.append({"layer": li, "error": rows[0]["error"]})
        h = d.dims.out_size(h)
    return installed


def _packed_of(wd: Params, dims: DeconvDims) -> kops.PackedDeconv:
    """Rehydrate a PackedDeconv from the trainable ``ww`` leaf (the static
    inverse-transform rows come from the cached layout, so they never enter
    the param tree and the optimizer never touches them)."""
    inv_np = kops.packed_layout(dims)[2]
    return kops.PackedDeconv(wd["ww"], jnp.asarray(inv_np))


def _deconv_apply(impl: str, x, wd: Params, dims: DeconvDims):
    """Apply one deconv layer; ``wd`` is the layer's param dict ({"w": raw}
    or {"ww": packed} for the prepacked impls)."""
    if impl in _PREPACKED_KW:
        kw = dict(_PREPACKED_KW[impl])
        if kw.get("backend") == "pallas":
            ww = wd["ww"]
            kw.update(DECONV_BLOCKS.get((impl, dims, ww.shape[1], ww.shape[2]), {}))
        return kops.winograd_deconv2d_packed(
            x, _packed_of(wd, dims), dims, **kw
        )
    w = wd["w"]
    if impl == "ref":
        return winograd_deconv2d(x, w, dims)
    if impl == "ref_bf16":
        return winograd_deconv2d(x, w, dims, bf16=True)
    if impl == "ref_dense":
        return winograd_deconv2d(x, w, dims, dense=True, bf16=True)
    if impl == "pallas":
        return kops.winograd_deconv2d_fused(x, w, dims)
    if impl == "pallas_fused_pre":
        return kops.winograd_deconv2d_fused(x, w, dims, fuse_pre=True)
    if impl == "pallas_interpret":
        return kops.winograd_deconv2d_fused(x, w, dims, interpret=True,
                                            **kops.INTERPRET_BLOCKS)
    if impl == "pallas_fused_pre_interpret":
        return kops.winograd_deconv2d_fused(x, w, dims, fuse_pre=True, interpret=True,
                                            **kops.INTERPRET_BLOCKS_FUSED)
    if impl == "tdc":
        return tdc_deconv2d(x, w, dims)
    if impl == "zero_padded":
        return zero_padded_deconv2d(x, w, dims)
    if impl == "lax":
        return lax_deconv2d(x, w, dims)
    raise ValueError(impl)


def prepack_generator(params: Params, cfg: GANConfig, mesh=None) -> Params:
    """One-time conversion of raw-weight generator params to the packed
    Winograd-domain layout (for use with a ``*_prepacked`` deconv_impl).

    Already-packed ``{"ww": ...}`` leaves pass through untouched, so sharded
    packed params from a mesh training run can be fed directly.  With
    ``mesh``, the converted tree is placed per ``parallel.sharding``'s
    ``gan_param_specs`` — the packed (C, N, M) weights come out already
    FSDP/TP-sharded, ready for the sharded train step or serve engine.
    """
    out = dict(params)
    for i, d in enumerate(cfg.deconvs):
        wd = params[f"deconv{i}"]
        if "w" in wd:
            out[f"deconv{i}"] = {"ww": kops.prepack(wd["w"], d.dims).ww}
    if mesh is not None:
        from repro.parallel import sharding as SH

        # spec layout only depends on packed-vs-raw leaves, so any prepacked
        # impl names the right tree
        impl = PREPACKED_EQUIV.get(cfg.deconv_impl, "prepacked_ref")
        cfg_p = cfg if uses_prepacked(cfg.deconv_impl) else dataclasses.replace(
            cfg, deconv_impl=impl
        )
        gsp, _, _ = SH.gan_param_specs(cfg_p, mesh)
        out = jax.device_put(out, SH.named(mesh, gsp))
    return out


# ------------------------------------------------- per-arch prepack registry
@dataclasses.dataclass(frozen=True)
class PrepackedGenerator:
    """A serve-ready resident generator: arch id, config with the serving
    impl already substituted (``serve_impl``), and packed (C, N, M) weights
    — the G-transform is paid when this entry is built, never on a request
    path.  ``GanServeEngine(models=...)`` accepts these directly (or plain
    arch-id strings resolved through ``get_prepacked_generator``)."""

    arch_id: str
    cfg: GANConfig
    params: Params


_SERVE_REGISTRY: dict[str, PrepackedGenerator] = {}


def register_prepacked_generator(arch_id: str, params: Params, cfg: GANConfig,
                                 *, mesh=None,
                                 chained: bool = True) -> PrepackedGenerator:
    """Prepack ``params`` for serving and register them under ``arch_id``,
    so several processes' worth of wiring (launch scripts, benchmarks, the
    serve engine) can share one resident copy per arch.  Re-registering an
    arch replaces its entry."""
    impl = serve_impl(cfg.deconv_impl, chained=chained)
    cfg_s = dataclasses.replace(cfg, deconv_impl=impl)
    packed = prepack_generator(params, cfg, mesh=mesh) if uses_prepacked(impl) \
        else params
    entry = PrepackedGenerator(arch_id=arch_id, cfg=cfg_s, params=packed)
    _SERVE_REGISTRY[arch_id] = entry
    return entry


def get_prepacked_generator(arch_id: str) -> PrepackedGenerator:
    """The registered serve-ready generator for ``arch_id``."""
    try:
        return _SERVE_REGISTRY[arch_id]
    except KeyError:
        raise KeyError(
            f"no prepacked generator registered for {arch_id!r} "
            f"(registered: {sorted(_SERVE_REGISTRY)})"
        ) from None


def registered_archs() -> tuple[str, ...]:
    return tuple(sorted(_SERVE_REGISTRY))


def clear_prepacked_generators() -> None:
    _SERVE_REGISTRY.clear()


# ------------------------------------------------------ resident health hooks
def params_finite(params: Params) -> bool:
    """True iff every floating-point leaf of ``params`` is fully finite.

    The serve engine's half-open circuit-breaker probe calls this before
    re-admitting a quarantined resident: weights poisoned by NaN/Inf (a
    corrupted restore, an overflowed update) can never produce a good
    batch, so the probe refuses to close the breaker on them."""
    for leaf in jax.tree_util.tree_leaves(params):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            if not bool(jnp.all(jnp.isfinite(leaf))):
                return False
    return True


def generator_health(params: Params, cfg: Optional[GANConfig] = None) -> dict:
    """Diagnostic health row for a (possibly prepacked) generator: leaf
    count, parameter count, and whether every weight is finite — the
    engine-side mirror of the train loop's checkpoint-integrity check."""
    leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(params)
        if hasattr(leaf, "shape")
    ]
    return {
        "finite": params_finite(params),
        "n_leaves": len(leaves),
        "n_params": int(sum(int(leaf.size) for leaf in leaves)),
        "prepacked": cfg is not None and uses_prepacked(cfg.deconv_impl),
    }


def unpack_generator(params: Params, cfg: GANConfig) -> Params:
    """Checkpoint-export inverse of ``prepack_generator``: packed
    Winograd-domain generator params -> raw K_D x K_D deconv weights, via
    least squares through the G-transform + pack
    (``kernels.ops.unpack_weights``).  A packed-trained model exports to
    the standard deconv format; raw ``{"w": ...}`` leaves pass through
    untouched, so prepack -> unpack round-trips."""
    out = dict(params)
    for i, d in enumerate(cfg.deconvs):
        wd = params[f"deconv{i}"]
        if "ww" in wd:
            out[f"deconv{i}"] = {"w": kops.unpack_weights(wd["ww"], d.dims)}
    return out


def prepack_discriminator(params: Params, cfg: GANConfig, mesh=None) -> Params:
    """One-time conversion of raw-weight discriminator params to the packed
    Winograd-domain conv layout (for use with a prepacked ``conv_impl``).
    Already-packed leaves pass through; with ``mesh`` the tree is placed per
    ``parallel.sharding.gan_param_specs`` (the disc half)."""
    out = dict(params)
    for i, cd in enumerate(disc_conv_dims(cfg)):
        wd = params.get(f"conv{i}")
        if wd is not None and "w" in wd:
            out[f"conv{i}"] = {
                "ww": kops.prepack_conv(wd["w"], cd).ww, "b": wd["b"]
            }
    if mesh is not None:
        from repro.parallel import sharding as SH

        impl = CONV_PREPACKED_EQUIV.get(cfg.conv_impl, "prepacked_ref")
        cfg_p = cfg if uses_prepacked_conv(cfg.conv_impl) else \
            dataclasses.replace(cfg, conv_impl=impl)
        _, dsp, _ = SH.gan_param_specs(cfg_p, mesh)
        out = jax.device_put(out, SH.named(mesh, dsp))
    return out


# ---------------------------------------------------------------- generator
def generator_init(key: jax.Array, cfg: GANConfig, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 2 + len(cfg.encoder) + len(cfg.deconvs))
    p: Params = {}
    ki = 0
    if cfg.z_dim:  # latent stem
        p["stem"] = L.linear_init(keys[ki], cfg.z_dim, cfg.seed_hw**2 * cfg.stem_ch, dtype)
        p["stem_bn"] = L.batchnorm_init(cfg.stem_ch, dtype)
        ki += 1
    for i, e in enumerate(cfg.encoder):
        p[f"enc{i}"] = L.conv2d_init(keys[ki], e.kernel, e.c_in, e.c_out, dtype)
        if e.norm == "batch":
            p[f"enc{i}_bn"] = L.batchnorm_init(e.c_out, dtype)
        ki += 1
    for i, d in enumerate(cfg.deconvs):
        w = L.normal_init(keys[ki], (d.dims.kernel, d.dims.kernel, d.c_in, d.c_out), 0.02, dtype)
        if uses_prepacked(cfg.deconv_impl):
            # Winograd-domain params: the G-transform runs here, once, and
            # never again — training updates the packed weights directly.
            p[f"deconv{i}"] = {"ww": kops.prepack(w, d.dims).ww}
        else:
            p[f"deconv{i}"] = {"w": w}
        if d.norm == "batch":
            p[f"deconv{i}_bn"] = L.batchnorm_init(d.c_out, dtype)
        ki += 1
    return p


def _bn_eval_affine(bn: Params, eps: float = 1e-5):
    """Fold eval-mode batchnorm (running stats) into a per-channel affine
    (a, b) with y = a*x + b — the epilogue the chained engine fuses."""
    a = bn["scale"].astype(jnp.float32) * jax.lax.rsqrt(bn["var"] + eps)
    b = bn["bias"].astype(jnp.float32) - bn["mean"] * a
    return a, b


def _cells_to_image(c: jax.Array, out_hw: tuple[int, int], padding: int = 0) -> jax.Array:
    """Emitted cell layout (B, R, Cc, m*m, M) -> the cropped NHWC image
    (pure relayout; the inverse of the engines' emit_cells layout)."""
    B, R, Cc, m2, M = c.shape
    m = int(round(m2 ** 0.5))
    img = jnp.transpose(
        c.reshape(B, R, Cc, m, m, M), (0, 1, 3, 2, 4, 5)
    ).reshape(B, R * m, Cc * m, M)
    return img[:, padding : padding + out_hw[0], padding : padding + out_hw[1]]


def _bn_act_cells(
    bn: Params,
    emitted: jax.Array,  # raw emit_cells output (B, R, Cc, m*m, >=M)
    out_hw: tuple[int, int],
    *,
    act: str,
    padding: int = 0,
    momentum: float = 0.9,
    eps: float = 1e-5,
):
    """Training-mode batchnorm + activation IN THE CELL DOMAIN — the second
    pass of the two-pass chained-BN scheme.  The emitted cells are a pure
    relayout of the layer's output pixels with everything outside the crop
    window already zeroed, so the batch statistics come from plain masked
    sums over the resident cell tensor (sum / count with count = the window
    pixel count; zeros outside the window contribute nothing), the affine +
    activation run as one fused XLA pointwise pass over the same tensor,
    and the crop mask re-zeroes out-of-window cells so the next chained
    engine call consumes the result directly.  Numerically equal to
    ``layers.batchnorm`` + activation on the NHWC image, without ever
    leaving the cell layout.  Returns (cells, new_running_stats)."""
    M = bn["scale"].shape[0]
    c = emitted[..., :M].astype(jnp.float32)
    B, R, Cc, m2, _ = c.shape
    m = int(round(m2 ** 0.5))
    count = B * out_hw[0] * out_hw[1]
    mean = c.sum(axis=(0, 1, 2, 3)) / count
    ex2 = (c * c).sum(axis=(0, 1, 2, 3)) / count
    # sync-BN: inside a `L.bn_sync_axis` context (sharded train step) the
    # moments pmean across the data shards — same global stats as the
    # single-device step (equal-sized shards)
    mean, ex2 = L.bn_sync_moments(mean, ex2)
    # one-pass E[x^2] - mean^2 can dip (slightly) negative under fp32
    # cancellation when |mean| >> std — clamp so rsqrt(var + eps) cannot
    # NaN a diverging run the per-layer two-pass var would survive
    var = jnp.maximum(ex2 - mean * mean, 0.0)
    y = (c - mean) * jax.lax.rsqrt(var + eps)
    y = y * bn["scale"].astype(jnp.float32) + bn["bias"].astype(jnp.float32)
    y = L.ACTIVATIONS[act](y)
    mask = kops.cells_window_mask(R, Cc, m, padding, out_hw[0], out_hw[1])
    new = {
        "mean": momentum * bn["mean"] + (1 - momentum) * mean,
        "var": momentum * bn["var"] + (1 - momentum) * var,
    }
    return (y * mask).astype(emitted.dtype), new


def _chained_deconv_trunk(
    p: Params, cfg: GANConfig, h: jax.Array, *, training: bool = False
) -> tuple[jax.Array, Params]:
    """Deconv trunk as ONE engine-domain pipeline, eval AND training mode.

    Eval (and BN-free layers in either mode): every layer runs the
    epilogue-fused engine (BN folded to scale/bias + activation applied in
    VMEM) and — where the cell layouts line up (``ops.chain_aligned``) —
    emits the next layer's cell layout directly, so consecutive layers
    chain with zero XLA relayout between them.

    Training-mode batch-stat BN layers use the two-pass scheme instead of
    falling back to per-layer NHWC steps: the engine emits the raw cell
    layout (no epilogue), ``_bn_act_cells`` computes the batch statistics
    and applies BN + activation on the resident cell tensor, and the chain
    continues — the trunk never materializes an intermediate NHWC image.
    Misaligned hops (ArtGAN's trailing K4S2 -> K3S1) fall back to NHWC out
    + a cells re-layout.  Returns (image, new_bn_stats)."""
    kw = _CHAINED_KW[cfg.deconv_impl]
    new_stats: Params = {}
    hw = (h.shape[1], h.shape[2])
    cells = kops.cells_from_image(h, cfg.deconvs[0].dims)
    img = None
    for i, d in enumerate(cfg.deconvs):
        with jax.named_scope(f"g.deconv{i}"):
            packed = _packed_of(p[f"deconv{i}"], d.dims)
            has_bn = d.norm == "batch"
            nxt = cfg.deconvs[i + 1].dims if i + 1 < len(cfg.deconvs) else None
            out_hw = (d.dims.out_size(hw[0]), d.dims.out_size(hw[1]))
            aligned = nxt is not None and kops.chain_aligned(d.dims, nxt)
            if training and has_bn:
                if aligned:
                    emitted = kops.winograd_deconv2d_cells(
                        cells, packed, d.dims, hw, emit_cells=True, **kw,
                    )
                    y_cells, stats = _bn_act_cells(
                        p[f"deconv{i}_bn"], emitted, out_hw, act=d.act,
                        padding=d.dims.padding,
                    )
                    cells = kops.cells_to_next(y_cells, d.dims, nxt, out_hw)
                else:  # misaligned hop (or BN on the last layer): NHWC fallback
                    img = kops.winograd_deconv2d_cells(cells, packed, d.dims, hw, **kw)
                    img, stats = L.batchnorm(p[f"deconv{i}_bn"], img, training=True)
                    img = L.ACTIVATIONS[d.act](img)
                    if nxt is not None:
                        cells = kops.cells_from_image(img, nxt)
                new_stats[f"deconv{i}_bn"] = stats
            else:
                scale, bias = (
                    _bn_eval_affine(p[f"deconv{i}_bn"]) if has_bn else (None, None)
                )
                if has_bn:
                    new_stats[f"deconv{i}_bn"] = {
                        "mean": p[f"deconv{i}_bn"]["mean"],
                        "var": p[f"deconv{i}_bn"]["var"],
                    }
                if aligned:
                    emitted = kops.winograd_deconv2d_cells(
                        cells, packed, d.dims, hw,
                        epilogue=d.act, scale=scale, bias=bias, emit_cells=True, **kw,
                    )
                    cells = kops.cells_to_next(emitted, d.dims, nxt, out_hw)
                else:
                    img = kops.winograd_deconv2d_cells(
                        cells, packed, d.dims, hw,
                        epilogue=d.act, scale=scale, bias=bias, **kw,
                    )
                    if nxt is not None:
                        cells = kops.cells_from_image(img, nxt)
            hw = out_hw
    return img, new_stats


def generator_apply(
    p: Params, cfg: GANConfig, inp: jax.Array, *, training: bool = True
) -> tuple[jax.Array, Params]:
    """inp: (B, z_dim) latent or (B, H, W, 3) image (image-to-image).
    Returns (image, new_bn_stats).

    A chained ``deconv_impl`` runs the whole deconv trunk inside the engine
    domain (``_chained_deconv_trunk``) in BOTH modes: eval folds BN into the
    fused epilogue; training uses the two-pass cell-domain BN (batch stats
    computed on the resident cell tensor), so neither mode falls back to
    per-layer NHWC steps.  Grads flow via the Pallas backward engines."""
    new_stats: Params = {}
    if cfg.z_dim:
        h = L.linear(p["stem"], inp)
        h = h.reshape(inp.shape[0], cfg.seed_hw, cfg.seed_hw, cfg.stem_ch)
        h, s = L.batchnorm(p["stem_bn"], h, training=training)
        new_stats["stem_bn"] = s
        h = jax.nn.relu(h)
    else:
        h = inp
        for i, e in enumerate(cfg.encoder):
            h = L.conv2d(p[f"enc{i}"], h, stride=e.stride)
            if e.norm == "batch":
                h, s = L.batchnorm(p[f"enc{i}_bn"], h, training=training)
                new_stats[f"enc{i}_bn"] = s
            h = L.ACTIVATIONS[e.act](h)
    if uses_chained(cfg.deconv_impl):
        img, trunk_stats = _chained_deconv_trunk(p, cfg, h, training=training)
        return img, {**new_stats, **trunk_stats}
    for i, d in enumerate(cfg.deconvs):
        with jax.named_scope(f"g.deconv{i}"):
            h = _deconv_apply(cfg.deconv_impl, h, p[f"deconv{i}"], d.dims)
            if d.norm == "batch":
                h, s = L.batchnorm(p[f"deconv{i}_bn"], h, training=training)
                new_stats[f"deconv{i}_bn"] = s
            h = L.ACTIVATIONS[d.act](h)
    return h, new_stats


# ------------------------------------------------------------ discriminator
# Default trunk widths; parallel.sharding.gan_param_specs mirrors this
# layout via disc_channels(cfg), so the two must change together.
DISC_CHANNELS: tuple[int, ...] = (64, 128, 256, 512)

DISC_KERNEL, DISC_STRIDE = 4, 2


def disc_channels(cfg: GANConfig) -> tuple[int, ...]:
    """Trunk widths of the discriminator for this config."""
    return tuple(getattr(cfg, "disc_channels", DISC_CHANNELS))


def disc_conv_dims(cfg: GANConfig) -> tuple[ConvDims, ...]:
    """Per-layer ConvDims of the discriminator trunk (K4S2, lax-SAME pads
    per input extent — identical geometry to ``layers.conv2d(stride=2)``)."""
    h, out = cfg.img_hw, []
    for _ in disc_channels(cfg):
        cd = conv_same_dims(DISC_KERNEL, DISC_STRIDE, h)
        out.append(cd)
        h = cd.out_size(h)
    return tuple(out)


def _packed_conv_of(wd: Params, cdims: ConvDims) -> kops.PackedConv:
    """Rehydrate a PackedConv from the trainable ``ww`` leaf (static inverse
    rows come from the cached layout — never in the param tree)."""
    inv_np = kops.conv_packed_layout(cdims)[1]
    return kops.PackedConv(wd["ww"], jnp.asarray(inv_np))


def discriminator_init(key: jax.Array, cfg: GANConfig, dtype=jnp.float32) -> Params:
    chans = [cfg.img_ch, *disc_channels(cfg)]
    keys = jax.random.split(key, len(chans))
    dims = disc_conv_dims(cfg)
    p: Params = {}
    for i in range(len(chans) - 1):
        wd = L.conv2d_init(keys[i], DISC_KERNEL, chans[i], chans[i + 1], dtype)
        if uses_prepacked_conv(cfg.conv_impl):
            # Winograd-domain conv params: G-transform + pack once, here
            wd = {"ww": kops.prepack_conv(wd["w"], dims[i]).ww, "b": wd["b"]}
        p[f"conv{i}"] = wd
        if i > 0:
            p[f"conv{i}_bn"] = L.batchnorm_init(chans[i + 1], dtype)
    final_hw = cfg.img_hw // 2 ** (len(chans) - 1)
    p["head"] = L.linear_init(keys[-1], final_hw**2 * chans[-1], 1, dtype)
    return p


def _disc_conv_apply(impl: str, x, wd: Params, cdims: ConvDims):
    """One per-layer discriminator conv (bias fused into the engine
    epilogue for the winograd impls)."""
    if impl == "lax":
        return L.conv2d(wd, x, stride=DISC_STRIDE)
    if impl in _CONV_RAW_KW:
        return kops.winograd_conv2d(
            x, wd["w"], cdims, bias=wd["b"].astype(jnp.float32),
            **_CONV_RAW_KW[impl],
        )
    if impl in _CONV_PREPACKED_KW:
        kw = dict(_CONV_PREPACKED_KW[impl])
        if kw.get("backend") == "pallas":
            ww = wd["ww"]
            kw.update(DECONV_BLOCKS.get((impl, cdims, ww.shape[1], ww.shape[2]), {}))
        return kops.winograd_conv2d_packed(
            x, _packed_conv_of(wd, cdims), cdims,
            bias=wd["b"].astype(jnp.float32), **kw,
        )
    raise ValueError(impl)


def _chained_conv_trunk(
    p: Params, cfg: GANConfig, img: jax.Array, *, training: bool = True
) -> tuple[jax.Array, Params]:
    """Discriminator trunk as ONE conv-to-conv engine pipeline — every
    stride-2 layer runs the fused Winograd Conv engine and hands the next
    layer its phase-major cell layout via ``ops.conv_cells_to_next`` (with
    m = S = 2, each output cell IS one phase pair of the next layer, so the
    hop is a static cell-level gather, never an NHWC materialize).

    Eval mode folds conv bias + running-stat BN into the fused epilogue;
    training mode uses the two-pass cell-domain BN (conv bias still fused,
    batch stats + BN + leaky_relu on the resident cell tensor).  The final
    layer materializes pixels only for the dense head."""
    base_kw = _CONV_PREPACKED_KW[cfg.conv_impl]
    dims = disc_conv_dims(cfg)
    new_stats: Params = {}
    hw = (img.shape[1], img.shape[2])
    cells = kops.conv_cells_from_image(img, dims[0])
    h_img = None
    n_layers = len(dims)
    for i, cd in enumerate(dims):
        with jax.named_scope(f"d.conv{i}"):
            wd = p[f"conv{i}"]
            kw = dict(base_kw)
            if kw.get("backend") == "pallas" and "ww" in wd:
                kw.update(DECONV_BLOCKS.get(
                    (cfg.conv_impl, cd, wd["ww"].shape[1], wd["ww"].shape[2]), {}
                ))
            packed = _packed_conv_of(wd, cd)
            b = wd["b"].astype(jnp.float32)
            has_bn = f"conv{i}_bn" in p
            last = i + 1 >= n_layers
            out_hw = (cd.out_size(hw[0]), cd.out_size(hw[1]))
            aligned = not last and kops.conv_chain_aligned(cd, dims[i + 1])
            if training and has_bn:
                emitted = kops.winograd_conv2d_cells(
                    cells, packed, cd, hw, bias=b, emit_cells=True, **kw,
                )
                y_cells, stats = _bn_act_cells(
                    p[f"conv{i}_bn"], emitted, out_hw, act="leaky_relu",
                )
                new_stats[f"conv{i}_bn"] = stats
                if aligned:
                    cells = kops.conv_cells_to_next(y_cells, cd, dims[i + 1], out_hw)
                else:
                    h_img = _cells_to_image(y_cells, out_hw)
                    if not last:
                        cells = kops.conv_cells_from_image(h_img, dims[i + 1])
            else:
                if has_bn:
                    a, bb = _bn_eval_affine(p[f"conv{i}_bn"])
                    scale, bias = a, a * b + bb
                    new_stats[f"conv{i}_bn"] = {
                        "mean": p[f"conv{i}_bn"]["mean"],
                        "var": p[f"conv{i}_bn"]["var"],
                    }
                else:
                    scale, bias = None, b
                if aligned:
                    emitted = kops.winograd_conv2d_cells(
                        cells, packed, cd, hw, epilogue="leaky_relu",
                        scale=scale, bias=bias, emit_cells=True, **kw,
                    )
                    cells = kops.conv_cells_to_next(emitted, cd, dims[i + 1], out_hw)
                else:
                    h_img = kops.winograd_conv2d_cells(
                        cells, packed, cd, hw, epilogue="leaky_relu",
                        scale=scale, bias=bias, **kw,
                    )
                    if not last:
                        cells = kops.conv_cells_from_image(h_img, dims[i + 1])
            hw = out_hw
    return L.linear(p["head"], h_img.reshape(h_img.shape[0], -1)), new_stats


def discriminator_apply(
    p: Params, cfg: GANConfig, img: jax.Array, *, training: bool = True
) -> tuple[jax.Array, Params]:
    """``cfg.conv_impl`` selects the trunk: 'lax' (XLA conv, the baseline),
    per-layer Winograd Conv engine impls, or the chained conv-to-conv
    pipeline — all numerically identical, so the adversarial train step's
    D-half (and the grad-through-D path that updates G) runs in whichever
    domain the benchmark compares."""
    impl = getattr(cfg, "conv_impl", "lax")
    if uses_chained_conv(impl):
        return _chained_conv_trunk(p, cfg, img, training=training)
    dims = disc_conv_dims(cfg)
    h, new_stats = img, {}
    i = 0
    while f"conv{i}" in p:
        with jax.named_scope(f"d.conv{i}"):
            h = _disc_conv_apply(impl, h, p[f"conv{i}"], dims[i])
            if f"conv{i}_bn" in p:
                h, s = L.batchnorm(p[f"conv{i}_bn"], h, training=training)
                new_stats[f"conv{i}_bn"] = s
            h = L.leaky_relu(h)
            i += 1
    return L.linear(p["head"], h.reshape(h.shape[0], -1)), new_stats


def merge_bn_stats(params: Params, stats: Params) -> Params:
    """Fold updated running BN stats back into the param tree."""
    out = dict(params)
    for k, s in stats.items():
        out[k] = {**params[k], **s}
    return out


# ------------------------------------------------------------ audio decoder
# MusicGen/EnCodec-style waveform head: a stack of 1D K4S2 TDC deconv
# layers (configs.musicgen_medium.audio_decoder) running on the 1D engine.
# The engine call is linear — bias + activation run in XLA after it, so
# jax.grad differentiates the epilogue for free and the custom VJP only
# handles the Winograd-domain cotangents.

_AUDIO_ACTS = {
    "relu": jax.nn.relu,
    "leaky_relu": L.leaky_relu,
    "tanh": jnp.tanh,
    "none": lambda x: x,
}


def lax_deconv1d(x: jax.Array, w: jax.Array, dims: DeconvDims) -> jax.Array:
    """XLA baseline for the 1D TDC deconv: lhs-dilated correlation with the
    flipped kernel; x (B, L, N), w (K_D, N, M) -> (B, L_O, M)."""
    K, P = dims.kernel, dims.padding
    return jax.lax.conv_general_dilated(
        x, jnp.flip(w, 0),
        window_strides=(1,),
        padding=[(K - 1 - P, K - 1 - P + dims.output_padding)],
        lhs_dilation=(dims.stride,),
        dimension_numbers=("NHC", "HIO", "NHC"),
    )


def audio_decoder_init(key: jax.Array, specs, dtype=jnp.float32) -> Params:
    """Params for a ``Deconv1dSpec`` stack: raw (K_D, N, M) deconv taps plus
    a per-channel bias per layer (no batchnorm — audio decoders normalize
    upstream of the waveform head)."""
    keys = jax.random.split(key, max(1, len(specs)))
    p: Params = {}
    for i, s in enumerate(specs):
        p[f"deconv{i}"] = {
            "w": L.normal_init(keys[i], (s.dims.kernel, s.c_in, s.c_out), 0.02, dtype),
            "b": jnp.zeros((s.c_out,), dtype),
        }
    return p


def _audio_deconv_apply(impl: str, x, w, dims: DeconvDims):
    if impl == "lax":
        return lax_deconv1d(x, w, dims)
    if impl == "tdc":
        from repro.core.tdc import tdc_deconv1d

        return tdc_deconv1d(x, w, dims)
    if impl == "ref":
        return kops.winograd_deconv1d(x, w, dims, backend="ref")
    if impl == "pallas":
        return kops.winograd_deconv1d(x, w, dims)
    if impl == "pallas_interpret":
        return kops.winograd_deconv1d(
            x, w, dims, interpret=True, **kops.INTERPRET_BLOCKS_1D
        )
    raise ValueError(impl)


def audio_decoder_apply(
    params: Params, specs, x: jax.Array, *, impl: str = "pallas"
) -> jax.Array:
    """Run the deconv decoder stack: latent (B, L, c_in) -> waveform
    (B, L * prod(strides), c_out).  ``impl`` picks the layer backend: 'lax'
    (XLA lhs-dilated conv, the baseline), 'tdc' (sub-correlation oracle),
    'ref' / 'pallas' / 'pallas_interpret' (the 1D Winograd engine) — all
    numerically identical."""
    for i, s in enumerate(specs):
        wd = params[f"deconv{i}"]
        x = _audio_deconv_apply(impl, x, wd["w"], s.dims)
        x = _AUDIO_ACTS[s.act](x + wd["b"])
    return x
