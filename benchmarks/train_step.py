"""Train-step benchmark: fwd, bwd (value_and_grad) and full-AdamW-step wall
time for the Winograd-DeConv layer families, emitting BENCH_train_step.json
so the perf trajectory of the training path is tracked PR over PR.

Variants per layer (all numerically identical forward):
  ref                        pure-JAX winograd path (XLA fwd + XLA bwd)
  pallas                     unfused Pallas engine, Pallas backward engines
  pallas_fused_pre           fused pre-PE engine, fused Pallas backward
  pallas_prepacked           pallas + weights prepacked once (Winograd-domain
                             step: no G-transform/pack anywhere in the step)
  pallas_fused_pre_prepacked fused + prepacked

Usage:
  PYTHONPATH=src python -m benchmarks.train_step                  # full layers
  PYTHONPATH=src python -m benchmarks.train_step --smoke          # CI: tiny
  PYTHONPATH=src python -m benchmarks.train_step --arch dcgan --out f.json
  PYTHONPATH=src python -m benchmarks.train_step --smoke --devices 8
                                                  # + sharded GAN step times

Beyond the per-layer sweep the report carries an end-to-end ``generator``
section (chained vs per-layer engine pipeline), a ``discriminator`` section
(lax / pure-JAX Winograd conv reference / per-call-pack engine / packed +
chained engine forward) and an ``adversarial`` section — the FULL GAN train
step with the engine generator and the discriminator backend varying, so
the all-engine step (G + D, both grads in the Pallas domain) is tracked PR
over PR.

On CPU the Pallas variants run in interpret mode: timings order host-loop
overheads rather than MXU work (the prepacked-vs-unpacked delta — the
per-step G-transform + pack — is real on both, and the gated geomeans are
engine-family ratios for exactly that reason).  On a TPU backend the same
driver measures the production numbers.

``--devices N`` additionally times the full sharded GAN train step (the
donated, NamedSharding-constrained ``make_gan_step(mesh=...)``) at every
power-of-two device count up to N, recording a per-device-count table in
the report.  On a CPU host the flag forces N host-platform devices — this
only works when the module is the process entry point, because the XLA flag
must be set before jax initializes.

With ``--devices`` the report also gains a ``weak_scaling`` section: the
communication-efficient step from ``parallel.overlap`` (prefetched FSDP
gathers, bucketed backward-order grad reduction, sync-BN, ZeRO block
updates, int8 error-feedback compression by default) timed at constant
per-device batch, with the per-step grad-reduction wire bytes recorded.
``--profile`` attributes every sharded/weak-scaling point from the lowered
HLO (collective counts, wire bytes, flops — ``launch.hlo_costs``) and drops
jax profiler traces under ``--profile-dir``.

``--train-chaos`` runs the train-side chaos drill (``bench_train_chaos``):
the resilient ``train_gan`` loop under injected NaN gradients, a persistent
raising step, on-disk checkpoint corruption and simulated preemption.  The
``"train_chaos"`` section records invariants, not timings — run terminates,
final metrics finite, fault accounting reconciles, preempt-resume metrics
parity — and ``compare_bench`` gates them baseline-free (the twin of fig8's
``serve_chaos`` section).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "../src"))


def _force_host_device_count(argv: list[str]) -> None:
    """--devices N on CPU needs xla_force_host_platform_device_count set
    before first jax init; a no-op on TPU hosts (the flag only affects the
    host platform) and when jax is already imported (library use)."""
    n = 0
    for i, a in enumerate(argv):
        try:
            if a == "--devices":
                n = int(argv[i + 1])
            elif a.startswith("--devices="):
                n = int(a.split("=", 1)[1])
        except (ValueError, IndexError):
            return
    if n > 1 and "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()


if __name__ == "__main__":
    _force_host_device_count(sys.argv)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tdc import DeconvDims
from repro.device import enable_compile_cache, interpret_mode
from repro.kernels.autotune import EngineConfig, make_timed_fn, time_one

from .workloads import GAN_LAYERS

MODES = ("fwd", "grad", "step")


def _variants(interpret: bool) -> list[tuple[str, EngineConfig | None]]:
    """(name, EngineConfig) rows; None marks the pure-JAX reference."""
    if interpret:  # CPU-feasible block sizes, shared with the model impls
        from repro.kernels.ops import INTERPRET_BLOCKS, INTERPRET_BLOCKS_FUSED

        fwd_kw, fused_kw = INTERPRET_BLOCKS, INTERPRET_BLOCKS_FUSED
    else:
        fwd_kw, fused_kw = {}, {}
    return [
        ("ref", None),
        ("pallas", EngineConfig(False, **fwd_kw)),
        ("pallas_fused_pre", EngineConfig(True, **fused_kw)),
        ("pallas_prepacked", EngineConfig(False, prepack=True, **fwd_kw)),
        ("pallas_fused_pre_prepacked", EngineConfig(True, prepack=True, **fused_kw)),
    ]


def bench_layer(
    dims: DeconvDims,
    input_shape: tuple[int, int, int, int],
    c_out: int,
    *,
    interpret: bool,
    repeats: int = 3,
    seed: int = 0,
) -> list[dict]:
    rng = np.random.default_rng(seed)
    B, H, W, N = input_shape
    x = jnp.asarray(rng.standard_normal((B, H, W, N)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((dims.kernel, dims.kernel, N, c_out)), jnp.float32)
    rows = []
    for name, cfg in _variants(interpret):
        row = {"variant": name}
        for mode in MODES:
            try:
                fn, make_args = make_timed_fn(cfg, dims, mode, interpret)
                row[f"{mode}_ms"] = time_one(fn, make_args(x, w), repeats) * 1e3
            except Exception as e:
                row[f"{mode}_ms"] = None
                row[f"{mode}_error"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)
    return rows


def _shrunk_gan_cfg(cfg, max_ch: int = 8):
    """Smoke-scale a gan_zoo config: cap every channel width — generator
    AND discriminator trunk (spatial dims and layer structure stay, so the
    chained pipelines still exercise every geometry hop, including ArtGAN's
    misaligned K4S2 -> K3S1 fallback)."""
    import dataclasses

    return dataclasses.replace(
        cfg,
        stem_ch=min(cfg.stem_ch, max_ch) if cfg.stem_ch else cfg.stem_ch,
        encoder=tuple(
            dataclasses.replace(
                e, c_in=min(e.c_in, max_ch) if i else e.c_in,
                c_out=min(e.c_out, max_ch),
            )
            for i, e in enumerate(cfg.encoder)
        ),
        deconvs=tuple(
            dataclasses.replace(d, c_in=min(d.c_in, max_ch), c_out=min(d.c_out, max_ch))
            for d in cfg.deconvs
        ),
        disc_channels=tuple(min(c, max_ch) for c in cfg.disc_channels),
    )


def _interleaved_times(fns: dict, args_of, *, repeats: int, warm: int = 2):
    """min-of-rounds wall times with the variants interleaved per round, so
    shared-runner noise phases hit every variant equally (the ratio is the
    headline, not the absolutes).  ``args_of(name)`` supplies each
    variant's argument tuple; failures record an error string instead."""
    import time as _time

    best: dict = {}
    errors: dict = {}
    live = {}
    for name, fn in fns.items():
        try:
            jax.block_until_ready(fn(*args_of(name)))  # compile + warm
            live[name] = fn
            best[name] = float("inf")
        except Exception as e:
            errors[name] = f"{type(e).__name__}: {e}"[:200]
    for rnd in range(max(4 * repeats, 12) + warm):
        for name, fn in live.items():
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*args_of(name)))
            if rnd >= warm:
                best[name] = min(best[name], _time.perf_counter() - t0)
    return {n: v * 1e3 for n, v in best.items()}, errors


def bench_discriminator(
    archs: list[str], *, interpret: bool, smoke: bool, repeats: int = 3
) -> dict:
    """Discriminator forward (eval mode) per arch: the lax baseline, the
    pure-JAX Winograd conv reference (chained_ref), the engine with
    per-call packing, and the packed + chained engine.  The gated headline
    geomean — packed/chained vs per-call-pack engine, a same-machine
    same-family ratio — gates in CI via compare_bench; the engine-vs-ref
    ratio is recorded alongside (on CPU it reports emulation overhead, on a
    TPU backend the real engine win)."""
    import dataclasses

    from repro.configs.gan_zoo import GANS
    from repro.models import gan as G

    suffix = "_interpret" if interpret else ""
    engine_impl = f"pallas_chained{suffix}"
    B = 2 if smoke else 8
    # lax = the pre-engine baseline; ref = the pure-JAX Winograd conv
    # reference; pallas_raw = the engine with per-call G-transform + pack;
    # pallas = the packed + chained engine (the production path)
    variants = {
        "lax": "lax", "ref": "chained_ref",
        "pallas_raw": f"pallas{suffix}", "pallas": engine_impl,
    }
    rows = []
    for arch in archs:
        cfg = GANS[arch]
        if smoke:
            cfg = _shrunk_gan_cfg(cfg)
        dp = G.discriminator_init(jax.random.PRNGKey(0), cfg)
        dp_packed = G.prepack_discriminator(dp, cfg)
        img = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.img_hw, cfg.img_hw, 3))
        fns, params = {}, {}
        for name, impl in variants.items():
            c = dataclasses.replace(cfg, conv_impl=impl)
            params[name] = dp_packed if G.uses_prepacked_conv(impl) else dp
            fns[name] = jax.jit(
                lambda p, x, c=c: G.discriminator_apply(p, c, x, training=False)[0]
            )
        best, errors = _interleaved_times(
            fns, lambda name: (params[name], img), repeats=repeats
        )
        row = {"arch": arch, "batch": B}
        for name in variants:
            if name in best:
                row[f"{name}_ms"] = best[name]
            else:
                row[f"{name}_ms"] = None
                row[f"{name}_error"] = errors[name]
        if row.get("pallas_raw_ms") and row.get("pallas_ms"):
            row["speedup"] = row["pallas_raw_ms"] / row["pallas_ms"]
        if row.get("ref_ms") and row.get("pallas_ms"):
            row["vs_ref"] = row["ref_ms"] / row["pallas_ms"]
        rows.append(row)
        cells = ",".join(
            f"{k}={row[k]:.2f}" if isinstance(row.get(k), float) else f"{k}=FAIL"
            for k in ("lax_ms", "ref_ms", "pallas_raw_ms", "pallas_ms")
        )
        sp = f",speedup={row['speedup']:.3f}" if "speedup" in row else ""
        print(f"train_step,discriminator,{arch},{cells}{sp}")
    out: dict = {"impl_engine": engine_impl, "rows": rows}
    sps = [r["speedup"] for r in rows if r.get("speedup")]
    if sps:
        # the gated headline: what prepacking + conv-to-conv chaining buys
        # WITHIN the engine family (the PR 2/PR 4 convention — interpret-mode
        # absolutes vs compiled XLA are emulation artifacts; the family
        # ratio is machine- and emulation-independent)
        out["packed_chained_speedup_geomean"] = float(np.exp(np.mean(np.log(sps))))
        print(
            "train_step,summary,discriminator_packed_chained_speedup_geomean="
            f"{out['packed_chained_speedup_geomean']:.3f}"
        )
    vs = [r["vs_ref"] for r in rows if r.get("vs_ref")]
    if vs:
        out["engine_vs_ref_geomean"] = float(np.exp(np.mean(np.log(vs))))
        print(
            "train_step,summary,discriminator_engine_vs_ref_geomean="
            f"{out['engine_vs_ref_geomean']:.3f}"
        )
    return out


def bench_adversarial(
    archs: list[str], *, interpret: bool, smoke: bool, repeats: int = 3
) -> dict:
    """FULL adversarial train step (G update + D update, both grads) per
    arch, with the engine generator throughout and the discriminator
    backend varying: 'lax' (XLA conv), 'ref' (pure-JAX Winograd conv
    reference), 'pallas_raw' (engine D with per-step G-transform + pack)
    and 'pallas' (packed + chained engine D — the whole step in the engine
    domain).  Gated headline geomean: the packed + chained engine step vs
    the per-step-packing engine step (the PR 2 convention); the
    engine-vs-ref step ratio is recorded alongside."""
    import dataclasses

    from repro import data as D
    from repro.configs.gan_zoo import GANS
    from repro.models import gan as G
    from repro.optim import adamw_init
    from repro.train.trainer import make_gan_step

    suffix = "_interpret" if interpret else ""
    gen_impl = f"pallas_chained{suffix}"
    engine_impl = f"pallas_chained{suffix}"
    B = 2 if smoke else 8
    variants = {
        "lax": "lax", "ref": "chained_ref",
        "pallas_raw": f"pallas{suffix}", "pallas": engine_impl,
    }
    rows = []
    for arch in archs:
        base = GANS[arch]
        if smoke:
            base = _shrunk_gan_cfg(base)
        base = dataclasses.replace(base, deconv_impl=gen_impl)
        kg, kd = jax.random.split(jax.random.PRNGKey(0))
        fns, args = {}, {}
        for name, impl in variants.items():
            cfg = dataclasses.replace(base, conv_impl=impl)
            gp = G.generator_init(kg, cfg)
            dp = G.discriminator_init(kd, cfg)
            z = (
                D.latent_batch(0, 0, B, cfg.z_dim) if cfg.z_dim
                else D.gan_batch(0, 0, B, cfg.img_hw)
            )
            real = D.gan_batch(0, 1, B, cfg.img_hw)
            args[name] = (gp, dp, adamw_init(gp), adamw_init(dp), z, real)
            fns[name] = make_gan_step(cfg)
        best, errors = _interleaved_times(
            fns, lambda name: args[name], repeats=repeats
        )
        row = {"arch": arch, "batch": B, "gen_impl": gen_impl}
        for name in variants:
            if name in best:
                row[f"{name}_ms"] = best[name]
            else:
                row[f"{name}_ms"] = None
                row[f"{name}_error"] = errors[name]
        if row.get("pallas_raw_ms") and row.get("pallas_ms"):
            row["speedup"] = row["pallas_raw_ms"] / row["pallas_ms"]
        if row.get("ref_ms") and row.get("pallas_ms"):
            row["vs_ref"] = row["ref_ms"] / row["pallas_ms"]
        rows.append(row)
        cells = ",".join(
            f"{k}={row[k]:.2f}" if isinstance(row.get(k), float) else f"{k}=FAIL"
            for k in ("lax_ms", "ref_ms", "pallas_raw_ms", "pallas_ms")
        )
        sp = f",speedup={row['speedup']:.3f}" if "speedup" in row else ""
        print(f"train_step,adversarial,{arch},{cells}{sp}")
    out: dict = {"impl_gen": gen_impl, "impl_engine": engine_impl, "rows": rows}
    sps = [r["speedup"] for r in rows if r.get("speedup")]
    if sps:
        out["packed_chained_step_speedup_geomean"] = float(
            np.exp(np.mean(np.log(sps)))
        )
        print(
            "train_step,summary,adversarial_packed_chained_step_speedup_geomean="
            f"{out['packed_chained_step_speedup_geomean']:.3f}"
        )
    vs = [r["vs_ref"] for r in rows if r.get("vs_ref")]
    if vs:
        out["engine_vs_ref_geomean"] = float(np.exp(np.mean(np.log(vs))))
        print(
            "train_step,summary,adversarial_engine_vs_ref_geomean="
            f"{out['engine_vs_ref_geomean']:.3f}"
        )
    return out


def bench_generator(
    archs: list[str], *, interpret: bool, smoke: bool, repeats: int = 3
) -> dict:
    """End-to-end generator forward (the serve path): the per-layer
    fused-pre prepacked engine vs the cell-to-cell chained pipeline
    (epilogue-fused finalize, BN folded, zero XLA relayout between aligned
    layers).  Per arch one eval-mode jitted generator_apply each, identical
    params; the headline geomean gates in CI via compare_bench."""
    import dataclasses

    import numpy as np

    from repro import data as D
    from repro.configs.gan_zoo import GANS
    from repro.models import gan as G

    suffix = "_interpret" if interpret else ""
    per_layer_impl = f"pallas_fused_pre_prepacked{suffix}"
    chained_impl = f"pallas_chained{suffix}"
    B = 2 if smoke else 8
    rows = []
    for arch in archs:
        cfg = GANS[arch]
        if smoke:
            cfg = _shrunk_gan_cfg(cfg)
        cfg_pl = dataclasses.replace(cfg, deconv_impl=per_layer_impl)
        cfg_ch = dataclasses.replace(cfg, deconv_impl=chained_impl)
        params = G.generator_init(jax.random.PRNGKey(0), cfg_pl)
        inp = (
            D.latent_batch(0, 0, B, cfg.z_dim) if cfg.z_dim
            else D.gan_batch(0, 0, B, cfg.img_hw)
        )
        row = {"arch": arch, "batch": B}
        fns, failed = {}, False
        for name, c in (("per_layer", cfg_pl), ("chained", cfg_ch)):
            fn = jax.jit(
                lambda p, z, c=c: G.generator_apply(p, c, z, training=False)[0]
            )
            try:
                jax.block_until_ready(fn(params, inp))  # compile + warm
                fns[name] = fn
            except Exception as e:
                row[f"{name}_ms"] = None
                row[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
                failed = True
        if not failed:
            import time as _time

            # interleave the repeats so shared-runner noise phases hit both
            # variants equally — the ratio is the headline, not the
            # absolutes — and take min over many rounds: per-round jitter on
            # shared CI runners is several percent, larger than the effect
            # being tracked, and these forwards are milliseconds each
            best = {name: float("inf") for name in fns}
            for rnd in range(max(4 * repeats, 12) + 2):
                for name, fn in fns.items():
                    t0 = _time.perf_counter()
                    jax.block_until_ready(fn(params, inp))
                    if rnd >= 2:  # first rounds warm caches, not timings
                        best[name] = min(best[name], _time.perf_counter() - t0)
            for name, dt in best.items():
                row[f"{name}_ms"] = dt * 1e3
        a, b = row.get("per_layer_ms"), row.get("chained_ms")
        if a and b:
            row["speedup"] = a / b
        rows.append(row)
        cells = ",".join(
            f"{k}={row[k]:.2f}" if isinstance(row.get(k), float) else f"{k}=FAIL"
            for k in ("per_layer_ms", "chained_ms")
        )
        sp = f",speedup={row['speedup']:.3f}" if "speedup" in row else ""
        print(f"train_step,generator,{arch},{cells}{sp}")
    out: dict = {"impl_per_layer": per_layer_impl, "impl_chained": chained_impl,
                 "rows": rows}
    sps = [r["speedup"] for r in rows if r.get("speedup")]
    if sps:
        out["chained_speedup_geomean"] = float(np.exp(np.mean(np.log(sps))))
        print(
            "train_step,summary,generator_chained_speedup_geomean="
            f"{out['chained_speedup_geomean']:.3f}"
        )
    return out


def bench_conv1d(*, interpret: bool, smoke: bool, repeats: int = 3) -> dict:
    """The 1D engine's two consumers, engine vs the XLA baseline: the SSM
    prefill causal conv (dense K=4 stride-1 — the Mamba ``d_conv`` shape)
    and one audio-decoder K4S2 deconv layer.  Variants per case: ``lax``
    (XLA conv), ``ref`` (pure-JAX 1D engine oracle), ``pallas`` (the 1D
    Pallas engine; interpret mode on CPU).  Timed via the interleaved-rounds
    harness so runner noise hits every variant equally."""
    from repro.core.tdc import DeconvDims
    from repro.kernels import ops
    from repro.models.gan import lax_deconv1d

    kw = dict(ops.INTERPRET_BLOCKS_1D, interpret=True) if interpret else {}
    rng = np.random.default_rng(0)
    if smoke:  # seconds-scale on CPU interpret
        conv_shape, conv_out = (1, 64, 8), 8
        dec_shape, dec_out = (1, 32, 8), 8
    else:
        conv_shape, conv_out = (8, 2048, 256), 256
        dec_shape, dec_out = (8, 1024, 128), 64
    K = 4
    dims = DeconvDims(kernel=4, stride=2, padding=1)
    out = {"interpret": interpret, "smoke": smoke, "cases": []}

    def one_case(name, shape, fns, args_of):
        times, errors = _interleaved_times(fns, args_of, repeats=repeats)
        row = {"name": name, "shape": list(shape)}
        for v in fns:
            if v in times:
                row[f"{v}_ms"] = times[v]
            else:
                row[f"{v}_error"] = errors[v]
        if "lax" in times and "pallas" in times:
            row["engine_vs_lax"] = times["lax"] / times["pallas"]
        out["cases"].append(row)
        cells = ",".join(
            f"{v}={row[f'{v}_ms']:.2f}" if f"{v}_ms" in row else f"{v}=FAIL"
            for v in fns
        )
        print(f"train_step,conv1d,{name},{cells}")

    # SSM prefill conv: dense channels so engine and lax do the same flops
    x = jnp.asarray(rng.standard_normal(conv_shape), jnp.float32)
    w = jnp.asarray(
        rng.standard_normal((K, conv_shape[2], conv_out)), jnp.float32
    )
    pk = ops.prepack_conv1d(w, K)
    one_case(
        "ssm_prefill_conv_k4", conv_shape,
        {
            "lax": jax.jit(lambda x: jax.lax.conv_general_dilated(
                x, w, (1,), [(K - 1, 0)],
                dimension_numbers=("NHC", "HIO", "NHC"))),
            "ref": lambda x: ops.winograd_conv1d_packed(x, pk, K, backend="ref"),
            "pallas": lambda x: ops.winograd_conv1d_packed(x, pk, K, **kw),
        },
        lambda n: (x,),
    )

    # audio decoder upsampling layer: 1D TDC deconv, L -> 2L
    xd = jnp.asarray(rng.standard_normal(dec_shape), jnp.float32)
    wd = jnp.asarray(
        rng.standard_normal((dims.kernel, dec_shape[2], dec_out)), jnp.float32
    )
    pkd = ops.prepack_deconv1d(wd, dims)
    one_case(
        "audio_deconv_k4s2", dec_shape,
        {
            "lax": jax.jit(lambda x: lax_deconv1d(x, wd, dims)),
            "ref": lambda x: ops.winograd_deconv1d_packed(x, pkd, dims, backend="ref"),
            "pallas": lambda x: ops.winograd_deconv1d_packed(x, pkd, dims, **kw),
        },
        lambda n: (xd,),
    )
    return out


def profile_step(step_fn, args, n_devices: int, *, trace_dir=None, tag=""):
    """Attribute a jitted step: static collective-vs-compute breakdown from
    the lowered HLO via ``launch.hlo_costs.analyze_text`` (flops, HBM bytes,
    collective wire bytes, per-op collective counts), plus an optional jax
    profiler trace under ``trace_dir`` for timeline inspection.  This is
    what turns the sharded slowdown curve from a guess into an attribution:
    the per-device-count records show exactly how many collectives each
    step issues and what they move."""
    rec: dict = {}
    try:
        from repro.launch.hlo_costs import analyze_text

        txt = step_fn.lower(*args).compile().as_text()
        c = analyze_text(txt, n_devices)
        rec.update(c)
        comm = c.get("collective_wire_bytes_per_device") or 0
        hbm = c.get("hbm_bytes_per_device") or 0
        if comm + hbm:
            rec["collective_bytes_fraction"] = comm / (comm + hbm)
    except Exception as e:  # keep the bench alive on analyzer drift
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    if trace_dir:
        d = os.path.join(trace_dir, tag)
        os.makedirs(d, exist_ok=True)
        with jax.profiler.trace(d):
            for _ in range(3):
                jax.block_until_ready(step_fn(*args))
        rec["trace_dir"] = d
    return rec


def bench_sharded(
    requested: int, *, interpret: bool, smoke: bool, repeats: int = 3,
    profile: bool = False, profile_dir=None,
) -> dict:
    """Per-device-count wall times of the full sharded GAN train step.

    One process, one forced host-device pool: meshes over 1, 2, 4, ...
    devices are sub-pools of the same ``jax.devices()``, so the scaling
    numbers are comparable run to run.
    """
    import dataclasses

    from repro import data as D
    from repro.configs.gan_zoo import DCGAN, tiny_dcgan
    from repro.launch.mesh import make_mesh
    from repro.models import gan as G
    from repro.optim import adamw_init
    from repro.train.trainer import make_gan_step

    avail = len(jax.devices())
    if avail < requested:
        raise RuntimeError(
            f"--devices {requested} asked for, but JAX has {avail} "
            f"{jax.default_backend()} device(s)"
        )
    counts, d = [], 1
    while d <= min(requested, avail):
        counts.append(d)
        d *= 2
    impl = "prepacked_ref" if interpret else "pallas_fused_pre_prepacked"
    # smoke: the tiny trunk the parity tests measure; keeps CPU runs in seconds
    cfg = dataclasses.replace(tiny_dcgan(impl) if smoke else DCGAN, deconv_impl=impl)
    B = max(8, counts[-1] if counts else 1)
    out = {
        "requested_devices": requested,
        "available_devices": avail,
        "arch": cfg.arch_id,
        "impl": impl,
        "batch": B,
        "step_ms": {},
    }
    for d in counts:
        mesh = make_mesh((d,), ("data",))
        # donate=False: time_one re-feeds the same buffers every repeat
        step = make_gan_step(cfg, mesh=mesh, batch=B, donate=False)
        kg, kd = jax.random.split(jax.random.PRNGKey(0))
        gp, dp = G.generator_init(kg, cfg), G.discriminator_init(kd, cfg)
        go, do = adamw_init(gp), adamw_init(dp)
        z = D.latent_batch(0, 0, B, cfg.z_dim)
        real = D.gan_batch(0, 0, B, cfg.img_hw)
        ms = time_one(step, (gp, dp, go, do, z, real), repeats) * 1e3
        out["step_ms"][str(d)] = ms
        print(f"train_step,sharded,{cfg.arch_id},devices={d},step={ms:.2f}")
        if profile:
            rec = profile_step(
                step, (gp, dp, go, do, z, real), d,
                trace_dir=profile_dir, tag=f"sharded_d{d}",
            )
            out.setdefault("profile", {})[str(d)] = rec
            colls = rec.get("collectives_by_op")
            print(f"train_step,sharded,profile,devices={d},collectives={colls}")
    return out


def bench_weak_scaling(
    requested: int, *, interpret: bool, smoke: bool, repeats: int = 3,
    per_device_batch: int = 1, grad_compression="int8",
    profile: bool = False, profile_dir=None,
) -> dict:
    """Weak scaling of the communication-efficient sharded GAN step: the
    global batch grows with the device count (``per_device_batch`` per
    device), so per-device work is constant and a flat curve means the
    collectives scale.

    The step is ``parallel.overlap.build_gan_comm_step`` — prefetched FSDP
    gathers, bucketed backward-order grad reduction, sync-BN, ZeRO block
    updates — with int8 error-feedback compression on by default (pass
    ``grad_compression=None`` for the uncompressed bucketed step).

    On forced host devices every device's compute serializes onto the host
    cores, so raw wall time grows ~linearly with the device count by
    construction; ``per_device_norm_ms`` (step_ms / devices) is the number
    a real parallel machine would see per device, and the one the flatness
    gate reads.  The d=8 raw point still does the same total work as the
    committed strong-scaling table's 8-device point (global batch 8), so
    the two step_ms values are directly comparable.
    """
    import dataclasses

    from repro import data as D
    from repro.configs.gan_zoo import DCGAN, tiny_dcgan
    from repro.launch.mesh import make_mesh
    from repro.models import gan as G
    from repro.optim import adamw_init
    from repro.parallel import overlap as OV

    avail = len(jax.devices())
    if avail < requested:
        raise RuntimeError(
            f"--devices {requested} asked for, but JAX has {avail} "
            f"{jax.default_backend()} device(s)"
        )
    counts, d = [], 1
    while d <= min(requested, avail):
        counts.append(d)
        d *= 2
    impl = "prepacked_ref" if interpret else "pallas_fused_pre_prepacked"
    cfg = dataclasses.replace(tiny_dcgan(impl) if smoke else DCGAN, deconv_impl=impl)
    out: dict = {
        "requested_devices": requested,
        "available_devices": avail,
        "arch": cfg.arch_id,
        "impl": impl,
        "per_device_batch": per_device_batch,
        "grad_compression": grad_compression,
        "step_ms": {},
        "per_device_norm_ms": {},
    }
    for d in counts:
        B = per_device_batch * d
        mesh = make_mesh((d,), ("data",))
        # donate=False: time_one re-feeds the same buffers every repeat
        step, meta = OV.build_gan_comm_step(
            cfg, mesh, batch=B, grad_compression=grad_compression,
            donate=False,
        )
        kg, kd = jax.random.split(jax.random.PRNGKey(0))
        gp, dp = G.generator_init(kg, cfg), G.discriminator_init(kd, cfg)
        go, do = adamw_init(gp), adamw_init(dp)
        z = D.latent_batch(0, 0, B, cfg.z_dim)
        real = D.gan_batch(0, 0, B, cfg.img_hw)
        if grad_compression:
            comm = OV.init_comm_state(gp, dp, mesh)
            args = (gp, dp, go, do, comm, z, real)
        else:
            args = (gp, dp, go, do, z, real)
        ms = time_one(step, args, repeats) * 1e3
        out["step_ms"][str(d)] = ms
        out["per_device_norm_ms"][str(d)] = ms / d
        if "wire" not in out:
            out["wire"] = meta["wire"]  # per-step grad-reduction bytes
            out["buckets"] = {
                "generator": len(meta["g_plan"].buckets),
                "discriminator": len(meta["d_plan"].buckets),
            }
        print(f"train_step,weak_scaling,{cfg.arch_id},devices={d},"
              f"batch={B},step={ms:.2f},per_dev={ms / d:.2f}")
        if profile:
            rec = profile_step(
                step, args, d, trace_dir=profile_dir, tag=f"weak_d{d}",
            )
            out.setdefault("profile", {})[str(d)] = rec
            colls = rec.get("collectives_by_op")
            print(f"train_step,weak_scaling,profile,devices={d},"
                  f"collectives={colls}")
    return out


def bench_train_chaos(*, smoke: bool, seed: int = 0) -> dict:
    """Train-side chaos drill (the twin of fig8's ``serve_chaos``): run the
    resilient ``train_gan`` loop under injected faults and record the
    invariants ``compare_bench`` gates baseline-free — no timings, only
    contract checks:

      * **recovery** — NaN grads + a persistent raising step + one on-disk
        checkpoint corruption, all in one run: it must terminate (no
        infinite replay), end with finite metrics, and the injected vs
        handled fault accounting must reconcile;
      * **escalation** — an uncapped persistent fault must escalate into a
        carried ``TrainFaultError`` within the policy's per-step budget
        (the bounded-crashloop regression guard);
      * **resume_parity** — a chaos-preempted run relaunched from its
        final checkpoint must reproduce an uninterrupted run's metrics
        exactly (loop state, comm residuals and params all round-trip).
    """
    import math
    import tempfile

    from repro.configs.gan_zoo import tiny_dcgan
    from repro.train import resilience as R
    from repro.train.trainer import train_gan

    cfg = tiny_dcgan()
    steps = 10 if smoke else 20
    out: dict = {"arch": cfg.arch_id, "steps": steps, "smoke": smoke}

    with tempfile.TemporaryDirectory() as td:
        # -------- recovery: the acceptance-criteria chaos cocktail
        plans = [
            R.TrainFaultPlan(kind="nan_grad", at_step=3, max_faults=1),
            R.TrainFaultPlan(kind="corrupt_ckpt", at_step=5, max_faults=1),
            R.TrainFaultPlan(kind="raise", at_step=7, persistent=True,
                             max_faults=2),
        ]
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("ignore", RuntimeWarning)
            res = train_gan(
                cfg, steps=steps, batch=2, seed=seed, log_every=1,
                ckpt_every=4, ckpt_dir=os.path.join(td, "recovery"),
                fault_plan=plans, handle_signals=False,
            )
        cnt, inj = res["counters"], res["faults_injected"]
        handled = cnt["injected_handled"]
        finite = bool(res["metrics"]) and all(
            math.isfinite(v) for e in res["metrics"] for v in e.values()
        )
        detail = {
            "raise_handled_eq_injected":
                handled.get("raise", 0) == inj.get("raise", 0),
            "nan_grad_handled_eq_injected":
                handled.get("nan_grad", 0) == inj.get("nan_grad", 0),
            "corrupt_ckpt_le_fallbacks":
                inj.get("corrupt_ckpt", 0) <= cnt["ckpt_fallbacks"],
            "metrics_steps_unique": len({e["step"] for e in res["metrics"]})
                == len(res["metrics"]),
        }
        out["recovery"] = {
            "terminated": res["final_step"] == steps,
            "final_metrics_finite": finite,
            "counters": cnt,
            "injected": inj,
            "accounting": {"reconciles": all(detail.values()), **detail},
        }
        print(f"train_step,train_chaos,recovery,terminated="
              f"{out['recovery']['terminated']},finite={finite},"
              f"reconciles={all(detail.values())},injected={inj},"
              f"handled={handled}")

        # -------- escalation: persistent fault must NOT replay forever
        esc: dict = {"raised": False, "bounded": False}
        try:
            train_gan(
                cfg, steps=6, batch=2, seed=seed, log_every=1,
                ckpt_every=2, ckpt_dir=os.path.join(td, "escalation"),
                fault_plan=R.TrainFaultPlan(kind="raise", at_step=2,
                                            persistent=True),
                policy=R.FaultPolicy(max_restores_per_step=2),
                handle_signals=False,
            )
        except R.TrainFaultError as e:
            esc = {
                "raised": True, "kind": e.kind, "step": e.step,
                "attempts": e.attempts,
                "bounded": e.attempts <= 2 + 1,  # budget + escalating try
            }
        out["escalation"] = esc
        print(f"train_step,train_chaos,escalation,raised={esc['raised']},"
              f"attempts={esc.get('attempts')},bounded={esc['bounded']}")

        # -------- resume parity: preempt mid-run, relaunch, compare exact
        kw = dict(steps=6, batch=2, seed=seed, log_every=1, ckpt_every=3,
                  handle_signals=False)
        clean = train_gan(cfg, ckpt_dir=os.path.join(td, "clean"), **kw)
        pre = train_gan(
            cfg, ckpt_dir=os.path.join(td, "pre"),
            fault_plan=R.TrainFaultPlan(kind="preempt", at_step=4,
                                        max_faults=1),
            **kw,
        )
        resumed = train_gan(cfg, ckpt_dir=os.path.join(td, "pre"), **kw)
        diffs = [
            abs(a[k] - b[k])
            for a, b in zip(clean["metrics"], resumed["metrics"])
            for k in a
        ] if len(clean["metrics"]) == len(resumed["metrics"]) else [float("inf")]
        out["resume_parity"] = {
            "preempted": pre["preempted"],
            "match": clean["metrics"] == resumed["metrics"],
            "max_abs_diff": max(diffs) if diffs else float("inf"),
            "compared_entries": len(clean["metrics"]),
        }
        print(f"train_step,train_chaos,resume_parity,"
              f"match={out['resume_parity']['match']},"
              f"max_abs_diff={out['resume_parity']['max_abs_diff']:.3e}")
    return out


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one gan_zoo arch (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + first layer per arch (CI-sized)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="BENCH_train_step.json")
    ap.add_argument("--devices", type=int, default=0,
                    help="also time the sharded GAN step on meshes of "
                         "1..N devices (forces N host devices on CPU when "
                         "run as the entry point)")
    ap.add_argument("--devices-only", action="store_true",
                    help="skip the per-layer sweep and emit only the "
                         "sharded per-device-count table (the multi-device "
                         "CI job: the tests job already gates the layers)")
    ap.add_argument("--profile", action="store_true",
                    help="attribute each sharded/weak-scaling point: "
                         "collective-vs-compute breakdown from the lowered "
                         "HLO (launch.hlo_costs) + a jax profiler trace "
                         "under --profile-dir")
    ap.add_argument("--profile-dir", default="artifacts/profile",
                    help="where --profile writes jax profiler traces")
    ap.add_argument("--per-device-batch", type=int, default=1,
                    help="weak-scaling batch per device (global batch = "
                         "devices * this)")
    ap.add_argument("--grad-compression", default="int8",
                    choices=("int8", "none"),
                    help="gradient compression for the weak-scaling step")
    ap.add_argument("--train-chaos", action="store_true",
                    help="run the train-side chaos drill (injected NaN "
                         "grads, persistent raising step, checkpoint "
                         "corruption, preemption) and record its "
                         "invariants as the gated 'train_chaos' section")
    args = ap.parse_args(argv)
    if args.devices_only and not args.devices:
        ap.error("--devices-only requires --devices N")
    enable_compile_cache()

    interpret = interpret_mode()
    archs = [] if args.devices_only else (
        [args.arch] if args.arch else sorted(GAN_LAYERS)
    )
    report = {
        "backend": jax.default_backend(),
        "interpret": interpret,
        "smoke": args.smoke,
        "modes": list(MODES),
        "layers": [],
    }
    for arch in archs:
        layers = GAN_LAYERS[arch]
        if args.smoke:
            layers = layers[:1]
        for li, l in enumerate(layers):
            if args.smoke:  # shrink to seconds-scale on CPU interpret
                # 32 channels keeps the per-step G-transform + pack delta
                # (the thing prepacking removes) above the CPU timing noise
                shape = (1, min(l.h_in, 4), min(l.w_in, 4), min(l.n_in, 32))
                c_out = min(l.m_out, 32)
            else:
                shape = (l.batch, l.h_in, l.w_in, l.n_in)
                c_out = l.m_out
            rows = bench_layer(
                l.dims, shape, c_out, interpret=interpret, repeats=args.repeats
            )
            entry = {
                "arch": arch, "layer": li,
                "dims": {"kernel": l.dims.kernel, "stride": l.dims.stride,
                         "padding": l.dims.padding, "output_padding": l.dims.output_padding},
                "input": list(shape), "c_out": c_out,
                "variants": rows,
            }
            report["layers"].append(entry)
            for r in rows:
                cells = ",".join(
                    f"{m}={r[f'{m}_ms']:.2f}" if r[f"{m}_ms"] is not None else f"{m}=FAIL"
                    for m in MODES
                )
                print(f"train_step,{arch},layer{li},{r['variant']},{cells}")

    # headline: does the prepacked fused path beat the unpacked one end-to-end?
    speedups = []
    for entry in report["layers"]:
        v = {r["variant"]: r for r in entry["variants"]}
        a = v.get("pallas_fused_pre", {}).get("step_ms")
        b = v.get("pallas_fused_pre_prepacked", {}).get("step_ms")
        if a and b:
            speedups.append(a / b)
    if speedups:
        report["prepacked_step_speedup_geomean"] = float(
            np.exp(np.mean(np.log(speedups)))
        )
        print(
            "train_step,summary,prepacked_fused_step_speedup_geomean="
            f"{report['prepacked_step_speedup_geomean']:.3f}"
        )
    if archs:
        report["generator"] = bench_generator(
            archs, interpret=interpret, smoke=args.smoke, repeats=args.repeats
        )
        report["discriminator"] = bench_discriminator(
            archs, interpret=interpret, smoke=args.smoke, repeats=args.repeats
        )
        report["adversarial"] = bench_adversarial(
            archs, interpret=interpret, smoke=args.smoke, repeats=args.repeats
        )
        report["conv1d"] = bench_conv1d(
            interpret=interpret, smoke=args.smoke, repeats=args.repeats
        )
    if args.devices:
        report["sharded"] = bench_sharded(
            args.devices, interpret=interpret, smoke=args.smoke,
            repeats=args.repeats, profile=args.profile,
            profile_dir=args.profile_dir,
        )
        report["weak_scaling"] = bench_weak_scaling(
            args.devices, interpret=interpret, smoke=args.smoke,
            repeats=args.repeats, per_device_batch=args.per_device_batch,
            grad_compression=(
                None if args.grad_compression == "none" else args.grad_compression
            ),
            profile=args.profile, profile_dir=args.profile_dir,
        )
    if args.train_chaos:
        report["train_chaos"] = bench_train_chaos(smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"train_step,wrote,{args.out}")
    return report


if __name__ == "__main__":
    main()
