"""Run the main path once on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: train, then serve
    python chip_smoke.py --chips 4   # the sharded train step on a 2x2 mesh,
                                     # against the same step on one chip

Phases, all in this one process (a chip belongs to one process):

  device  JAX's first device must be a TPU; otherwise exit 1 before any
          phase, printing no result.
  train   ``train_gan`` on DCGAN at its published widths and batch (128),
          both nets on the ``pallas_chained`` engines, a few steps.  Every
          loss and grad norm must be finite.  Step 0's losses and the
          generator's eval output are checked against the ``lax`` impls at
          ``highest`` matmul precision, and step 0's grad norms against the
          ``chained_ref`` impls (the same packed-weight parameterization in
          plain jnp) at ``highest``.
  serve   ``GanServeEngine`` with DCGAN and ArtGAN resident at full width
          (impl ``pallas``, served as ``pallas_chained``) behind
          ``AsyncGanServer``; 16 requests of sizes 1-8.  Every future must
          resolve as served and match the ``ref`` generator.

Tolerances.  On the TPU an f32 matmul at DEFAULT precision (the engines'
``_com_pe``, and XLA's own dots in the stem, heads and batchnorm) is one
bf16 pass: each operand is rounded to 8 mantissa bits (relative error
<= 2^-9) and products accumulate in f32.  A dot's relative error is then
about 2^-9 ~ 2e-3 whatever its length, the Winograd transforms sum a few
such terms (a factor ~2), and a generator stacks five matmul stages, so
the image's relative L2 error should be near 5e-3 and its worst pixel a
few times that.  The checks allow 4x: relative L2 <= 2e-2 and max error
<= 5e-2 of the image's largest magnitude.  A wrong tap, tile or halo
moves pixels by O(1) of that scale, far outside either bound.  Losses are
means of a BCE whose slope is below 1, so their error is below the logits'
(~1e-3 at init): |d loss| <= 1e-2.  Grad norms: relative 5e-2.  The
four-chip step runs the same kernels on the same samples with the same
semantics (on CPU in f32 the two agree to ~1e-7), but each device's
dots have other shapes, so the bf16-pass rounding and the f32 reduction
order (sync-BN moments, gradient means) differ: its step-0 metrics must
match the one-chip step within a relative 2e-3, about two bf16 passes.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase raises, and the process exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import re
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
BATCH = 128  # DCGAN's published batch
TRAIN_STEPS = 5
SHARDED_STEPS = 3
IMG_REL_L2 = 2e-2
IMG_REL_MAX = 5e-2
LOSS_ABS = 1e-2
GRAD_NORM_REL = 5e-2
MESH_REL = 2e-3
SERVE_SIZES = (1, 3, 8, 2, 5, 7, 4, 6, 8, 1, 2, 3, 4, 5, 6, 7)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def image_errors(out, ref) -> tuple[float, float]:
    """(relative L2, max abs error over max |ref|), computed in f64 on host."""
    import numpy as np

    a = np.asarray(out, np.float64)
    b = np.asarray(ref, np.float64)
    check(a.shape == b.shape, f"shape {a.shape} != reference {b.shape}")
    check(bool(np.isfinite(a).all()), "non-finite values in output")
    scale = float(np.abs(b).max())
    return (float(np.linalg.norm(a - b) / np.linalg.norm(b)),
            float(np.abs(a - b).max() / scale))


def check_image(name: str, out, ref) -> None:
    rel_l2, rel_max = image_errors(out, ref)
    log(f"{name}: rel_l2={rel_l2!r} (<= {IMG_REL_L2}) "
        f"max_err/max_ref={rel_max!r} (<= {IMG_REL_MAX})")
    check(rel_l2 <= IMG_REL_L2 and rel_max <= IMG_REL_MAX,
          f"{name}: outside tolerance")


def dcgan(deconv_impl: str, conv_impl: str):
    from repro.configs.gan_zoo import DCGAN

    return dataclasses.replace(DCGAN, deconv_impl=deconv_impl, conv_impl=conv_impl)


def step0_batch(cfg):
    from repro import data as D

    return (D.latent_batch(SEED, 0, BATCH, cfg.z_dim),
            D.gan_batch(SEED, 0, BATCH, cfg.img_hw))


def init_params(cfg):
    """The init ``train_gan(seed=SEED)`` makes for ``cfg``."""
    import jax

    from repro.models import gan as G

    kg, kd = jax.random.split(jax.random.PRNGKey(SEED))
    return G.generator_init(kg, cfg), G.discriminator_init(kd, cfg)


def run_train(cfg, settings, steps: int) -> tuple[list[dict], list[float]]:
    """``train_gan`` for ``steps`` steps; per-step metrics and the host time
    at which each step's metrics were read (the loop blocks on them)."""
    from repro.train.trainer import TrainHooks, train_gan

    stamps: list[float] = []
    t0 = time.perf_counter()
    out = train_gan(
        cfg, steps=steps, batch=BATCH, seed=SEED, log_every=1,
        settings=settings,
        hooks=TrainHooks(on_step=lambda s, m: stamps.append(time.perf_counter())),
    )
    check(out["final_step"] == steps, f"train stopped at step {out['final_step']}")
    check(not out["fault_log"], f"train recovered from faults: {out['fault_log']}")
    metrics = out["metrics"]
    check(len(metrics) == steps, f"{len(metrics)} metric rows for {steps} steps")
    for m in metrics:
        log(f"  step {m['step']}: " + " ".join(
            f"{k}={m[k]!r}" for k in ("g_loss", "d_loss", "g_grad_norm", "d_grad_norm")))
        check(all(math.isfinite(m[k]) for k in
                  ("g_loss", "d_loss", "g_grad_norm", "d_grad_norm")),
              f"non-finite metrics at step {m['step']}")
        check(m["nonfinite"] == 0.0, f"sentinel flagged step {m['step']}")
    return metrics, [t - t0 for t in stamps]


def report_times(label: str, times: list[float]) -> None:
    """First step = compile + one step; the rest are steady steps."""
    steps = [b - a for a, b in zip(times, times[1:])]
    med = statistics.median(steps)
    log(f"{label}: first step (compile + run) {times[0]!r} s, "
        f"median step {med!r} s over {len(steps)} steps, "
        f"compile ~{times[0] - med!r} s (information only)")


def phase_train() -> None:
    import jax

    from repro.models import gan as G
    from repro.optim import adamw_init
    from repro.train.trainer import StepSettings, gan_losses, make_gan_step

    cfg = dcgan("pallas_chained", "pallas_chained")
    log(f"[train] DCGAN gen {cfg.stem_ch}->"
        + "->".join(str(d.c_out) for d in cfg.deconvs)
        + f", disc {G.disc_channels(cfg)}, batch {BATCH}, "
        f"impls {cfg.deconv_impl}/{cfg.conv_impl}")
    metrics, times = run_train(cfg, StepSettings(), TRAIN_STEPS)
    report_times("[train] timing", times)

    z, real = step0_batch(cfg)
    with jax.default_matmul_precision("highest"):
        cfg_lax = dcgan("lax", "lax")
        gp, dp = init_params(cfg_lax)
        g_ref, d_ref, _ = jax.jit(
            lambda gp, dp, z, real: gan_losses(gp, dp, cfg_lax, z, real)
        )(gp, dp, z, real)
        img_ref = jax.jit(
            lambda p, z: G.generator_apply(p, cfg_lax, z, training=False)[0]
        )(gp, z)
        cfg_cr = dcgan("chained_ref", "chained_ref")
        gp_c, dp_c = init_params(cfg_cr)
        step_ref = make_gan_step(cfg_cr, settings=StepSettings(donate=False))
        m_ref = step_ref(gp_c, dp_c, adamw_init(gp_c), adamw_init(dp_c), z, real)[-1]
    m0 = metrics[0]
    for k, ref in (("g_loss", g_ref), ("d_loss", d_ref)):
        err = abs(m0[k] - float(ref))
        log(f"[train] step-0 {k}: engine {m0[k]!r} lax/highest {float(ref)!r} "
            f"|diff| {err!r} (<= {LOSS_ABS})")
        check(err <= LOSS_ABS, f"step-0 {k} outside tolerance")
    for k in ("g_grad_norm", "d_grad_norm"):
        ref = float(m_ref[k])
        rel = abs(m0[k] - ref) / ref
        log(f"[train] step-0 {k}: engine {m0[k]!r} chained_ref/highest {ref!r} "
            f"rel {rel!r} (<= {GRAD_NORM_REL})")
        check(rel <= GRAD_NORM_REL, f"step-0 {k} outside tolerance")

    gp_e, _ = init_params(cfg)
    img = jax.jit(lambda p, z: G.generator_apply(p, cfg, z, training=False)[0])(gp_e, z)
    check_image("[train] eval generator vs lax/highest", img, img_ref)


def phase_serve() -> None:
    import jax
    import numpy as np

    from repro.configs.gan_zoo import ARTGAN, DCGAN
    from repro.models import gan as G
    from repro.serve.engine import GanServeEngine
    from repro.serve.loop import AsyncGanServer

    cfgs = {c.arch_id: dataclasses.replace(c, deconv_impl="pallas")
            for c in (DCGAN, ARTGAN)}
    params = {a: G.generator_init(jax.random.PRNGKey(SEED + i), c)
              for i, (a, c) in enumerate(cfgs.items())}
    engine = GanServeEngine(
        models={a: (params[a], c) for a, c in cfgs.items()},
        batch=8, max_retries=0,
    )
    served_impls = {a: r.cfg.deconv_impl for a, r in engine.archs.items()}
    log(f"[serve] residents {served_impls}, buckets {engine.buckets}")
    check(set(served_impls.values()) == {"pallas_chained"},
          f"serving impls {served_impls}")

    archs = list(cfgs)
    rng = np.random.default_rng(SEED)
    reqs = [(archs[i % 2], rng.standard_normal((n, DCGAN.z_dim)).astype(np.float32))
            for i, n in enumerate(SERVE_SIZES)]
    t0 = time.perf_counter()
    with AsyncGanServer(engine) as server:
        futures = [server.submit(jax.numpy.asarray(z), arch=a) for a, z in reqs]
        outs = [f.result(timeout=900) for f in futures]
    log(f"[serve] {len(outs)} of {len(reqs)} requests served in "
        f"{time.perf_counter() - t0!r} s (compiles included)")
    health = engine.health()
    for a, h in health.items():
        log(f"[serve] {a}: failures={h['failures']} retries={h['retries']} "
            f"buckets={engine.archs[a].bucket_counts}")
        check(h["failures"] == 0 and h["retries"] == 0, f"{a}: dispatch failures")

    with jax.default_matmul_precision("highest"):
        for a in archs:
            cfg_ref = dataclasses.replace(cfgs[a], deconv_impl="ref")
            zs = np.concatenate([z for arch, z in reqs if arch == a])
            ref = np.asarray(jax.jit(
                lambda p, z: G.generator_apply(p, cfg_ref, z, training=False)[0]
            )(params[a], zs))
            row = 0
            for (arch, z), out in zip(reqs, outs):
                if arch != a:
                    continue
                check_image(f"[serve] {a} request of {len(z)}", out,
                            ref[row:row + len(z)])
                row += len(z)


def phase_sharded(n_chips: int) -> None:
    """The sharded (shard_map) train step on all chips vs one chip."""
    import jax

    from repro.compat import make_mesh
    from repro.train.trainer import StepSettings, make_gan_step

    devices = jax.devices()
    check(len(devices) == n_chips, f"{len(devices)} devices, asked for {n_chips}")
    cfg = dcgan("pallas_chained", "pallas_chained")
    mesh = make_mesh((2, n_chips // 2), ("data", "model"))
    log(f"[sharded] mesh {dict(mesh.shape)} over {len(mesh.devices.flat)} devices")
    settings = StepSettings(mesh=mesh, batch=BATCH, overlap=True)

    # the compiled SPMD program: one partition per device, kernels inside
    from repro.optim import adamw_init
    from repro.parallel import sharding as SH

    gp, dp = init_params(cfg)
    gsp, dsp, _ = SH.gan_param_specs(cfg, mesh)
    zsp, rsp, _ = SH.gan_batch_specs(cfg, BATCH, mesh)
    args = jax.eval_shape(lambda: (gp, dp, adamw_init(gp), adamw_init(dp), *step0_batch(cfg)))
    specs = (gsp, dsp, SH.opt_specs(gsp), SH.opt_specs(dsp), zsp, rsp)
    args = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        args, SH.named(mesh, specs))
    hlo = make_gan_step(cfg, settings=settings).lower(*args).compile().as_text()
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    parts = re.findall(r"num_partitions=(\d+)", hlo.split("\n", 1)[0])
    log(f"[sharded] compiled step: {kernels} tpu_custom_calls in an SPMD program "
        f"of num_partitions={parts}")
    check(kernels > 0 and parts == [str(n_chips)],
          "the sharded step does not run the kernels on every device")

    one, t_one = run_train(cfg, StepSettings(), 1)
    log(f"[sharded] one-chip first step (compile + run) {t_one[0]!r} s")
    many, t_many = run_train(cfg, settings, SHARDED_STEPS)
    report_times(f"[sharded] {n_chips}-chip timing", t_many)
    for k in ("g_loss", "d_loss", "g_grad_norm", "d_grad_norm"):
        rel = abs(many[0][k] - one[0][k]) / abs(one[0][k])
        log(f"[sharded] step-0 {k}: {n_chips} chips {many[0][k]!r} "
            f"1 chip {one[0][k]!r} rel {rel!r} (<= {MESH_REL})")
        check(rel <= MESH_REL, f"step-0 {k}: sharded step differs from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded train step on a 2x2 mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {dev.platform!r})", file=sys.stderr)
        return 1
    log(f"[device] {dev.device_kind}, {len(devices)} device(s)")

    from repro.device import enable_compile_cache

    hits = {"requests": 0, "hits": 0}

    def _on_event(event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            hits["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1

    jax.monitoring.register_event_listener(_on_event)
    log(f"[device] compile cache: {enable_compile_cache()}")

    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_train()
        phase_serve()
    log(f"[device] compile cache hits {hits['hits']} of {hits['requests']} lookups")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
