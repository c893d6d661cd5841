"""Training loops: GAN (the paper's workload) and LM (assigned archs).

Fault-tolerance contract (see ``train/resilience.py`` for the pieces):
  * every N steps the full (params, opt_state, comm residuals) tree plus
    the loop state (metrics history, lr scale, counters) is checkpointed
    atomically and fsync-durably;
  * a step failure (device error, injected fault, straggler deadline)
    triggers restore-from-latest and replay — the data pipeline is a pure
    function of (seed, step) so replay is exact — under a **bounded**
    ``FaultPolicy`` budget: a fault that re-fires deterministically at the
    same step escalates into a carried ``TrainFaultError`` after
    ``max_restores_per_step`` restores instead of replaying forever;
  * a step **sentinel** (in-jit finiteness flag + host-side windowed
    divergence detector) catches NaN losses and blown-up trajectories the
    step they happen; the policy decides skip / rollback (with an
    lr-scale knob) / abort;
  * SIGTERM/SIGINT request **preemption-safe exit**: one final atomic
    checkpoint (including the loop state), then a clean return with
    ``"preempted": True`` — resume is bit-exact vs an uninterrupted run;
  * async dispatch: with the sentinel off the loop never blocks on
    metrics except at log boundaries; with it on (the default) it reads
    five device scalars per step — one small transfer;
  * inside a profiler session ``train_gan`` marks its host work with
    ``repro.obs`` spans: ``gan.train.data`` (the batch), ``gan.train.step``
    (the step's dispatch, and any retrace) and ``gan.train.sync`` (the
    metrics fetch, where the loop waits for the device).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import data as D
from repro import obs
from repro.configs.base import GANConfig
from repro.models import gan as G
from repro.optim import adamw_init, adamw_update
from repro.train import checkpoint as C
from repro.train import resilience as R

#: every step variant (single-device, GSPMD, overlapped) emits these
METRIC_SPEC_KEYS = ("g_loss", "d_loss", "g_grad_norm", "d_grad_norm", "nonfinite")


@dataclasses.dataclass
class TrainHooks:
    """Injection points used by tests (fault injection) and launchers."""

    on_step: Optional[Callable[[int, dict], None]] = None
    inject_fault_at: Optional[int] = None  # raise once at this step (test hook)
    step_deadline_s: float = 0.0  # 0 = no watchdog


@dataclasses.dataclass(frozen=True)
class StepSettings:
    """Every knob that shapes how a GAN train step is BUILT, in one bundle.

    ``make_gan_step``, ``train_gan`` and ``launch.steps.build_gan_step``
    all accept ``settings=StepSettings(...)``; the historical per-function
    kwarg sprawl (``mesh``, ``overlap``, ``grad_compression``,
    ``bucket_bytes``, ``deconv_impl``, ``conv_impl``, ``donate``, ...) is
    deprecated but still accepted — legacy kwargs are mapped onto a
    ``StepSettings`` (overriding any ``settings=`` also passed) with a
    ``DeprecationWarning``.

    Fields:
      lr, b1            AdamW learning rate / beta1
      mesh              device mesh: NamedSharding-constrained step, ZeRO
                        moments (``parallel.sharding.gan_param_specs``)
      batch             global batch size (required with mesh, for the
                        divisibility check)
      donate            donate param/opt buffers into the jit (off for
                        benchmarks that re-time one argument set)
      overlap           explicit-collective step from ``parallel.overlap``
                        (prefetched gathers, bucketed backward-order grad
                        reduction, sync-BN, ZeRO block updates)
      grad_compression  "int8" threads error-feedback CommState through
                        the step (implies the overlap step)
      bucket_bytes      grad-reduction bucket target for the overlap step
      deconv_impl       generator backend override (None = cfg's)
      conv_impl         discriminator backend override (None = cfg's)
    """

    lr: float = 2e-4
    b1: float = 0.5
    mesh: Any = None
    batch: Optional[int] = None
    donate: bool = True
    overlap: bool = False
    grad_compression: Optional[str] = None
    bucket_bytes: Optional[int] = None
    deconv_impl: Optional[str] = None
    conv_impl: Optional[str] = None

    @property
    def comm(self) -> bool:
        """True when the explicit-collective (overlap) step is selected."""
        return self.overlap or self.grad_compression is not None

    def apply_to_cfg(self, cfg: GANConfig) -> GANConfig:
        """cfg with the impl overrides substituted."""
        if self.deconv_impl is not None:
            cfg = dataclasses.replace(cfg, deconv_impl=self.deconv_impl)
        if self.conv_impl is not None:
            cfg = dataclasses.replace(cfg, conv_impl=self.conv_impl)
        return cfg


_UNSET = object()  # distinguishes "legacy kwarg not passed" from None/False


def _merge_legacy(settings: Optional[StepSettings], legacy: dict,
                  where: str) -> StepSettings:
    """Fold explicitly-passed legacy kwargs over ``settings`` (or defaults),
    with the deprecation note the redesign promised."""
    given = {k: v for k, v in legacy.items() if v is not _UNSET}
    base = settings if settings is not None else StepSettings()
    if not given:
        return base
    warnings.warn(
        f"{where}: kwargs {sorted(given)} are deprecated; pass "
        "settings=StepSettings(...) instead",
        DeprecationWarning, stacklevel=3,
    )
    return dataclasses.replace(base, **given)


# --------------------------------------------------------------- GAN loop
def check_gspmd_partitionable(cfg: GANConfig) -> None:
    """Raise if ``cfg`` runs compiled Pallas kernels: the GSPMD partitioner
    cannot split a Mosaic kernel, so a meshed step with them must be the
    shard_map one (``StepSettings(overlap=True)``), where each device runs
    the kernels on its own shard."""
    mosaic = [i for i in (cfg.deconv_impl, cfg.conv_impl)
              if i.startswith("pallas") and not i.endswith("_interpret")]
    if mosaic:
        raise ValueError(
            f"impls {mosaic} run Pallas TPU kernels, which GSPMD cannot "
            "partition; build the meshed step with StepSettings(overlap=True)"
        )


def gan_losses(gp, dp, cfg: GANConfig, z, real, *, training=True):
    fake, g_stats = G.generator_apply(gp, cfg, z, training=training)
    d_fake, _ = G.discriminator_apply(dp, cfg, fake, training=training)
    d_real, d_stats = G.discriminator_apply(dp, cfg, real, training=training)
    bce = lambda logit, target: jnp.mean(
        jnp.maximum(logit, 0) - logit * target + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    )
    g_loss = bce(d_fake, jnp.ones_like(d_fake))  # non-saturating
    d_loss = 0.5 * (bce(d_real, jnp.ones_like(d_real)) + bce(d_fake, jnp.zeros_like(d_fake)))
    return g_loss, d_loss, (g_stats, d_stats, fake)


def make_gan_step(cfg: GANConfig, lr=_UNSET, b1=_UNSET, *,
                  settings: Optional[StepSettings] = None, mesh=_UNSET,
                  batch=_UNSET, donate=_UNSET, overlap=_UNSET,
                  grad_compression=_UNSET, bucket_bytes=_UNSET):
    """Returns the jit'd GAN train step: simultaneous G/D update from one
    shared forward (two vjp pulls on a single linearization — one generator
    forward per step, and no updated param is re-consumed within the step,
    so the sharded variants need no mid-step re-gather).

    How the step is built is configured by ``settings=StepSettings(...)``
    (the individual kwargs are a deprecated spelling of the same fields).

    With ``settings.mesh``, the step is NamedSharding-constrained
    end-to-end: params and AdamW moments follow
    ``parallel.sharding.gan_param_specs`` / ``opt_specs`` (FSDP over the
    packed N dim + TP over M where it divides, ZeRO-sharded moments), the
    (z, real) batch shards over the ("pod","data") axes, and the param/opt
    buffers are donated.  ``settings.batch`` (the global batch size) is
    required then, for the divisibility check; ``donate=False`` opts out
    of donation for callers that re-time the step on one argument set
    (benchmarks).

    ``settings.overlap`` (or any ``settings.grad_compression``) swaps the
    GSPMD step for the explicit-collective one from ``parallel.overlap``:
    prefetched FSDP gathers, bucketed grad reduction in backward order
    (``settings.bucket_bytes`` sets the target), ZeRO block updates,
    sync-BN.  With ``grad_compression="int8"`` the step additionally
    takes/returns a ``parallel.overlap.CommState`` (error-feedback
    residuals) between the opt-state and batch arguments — init via
    ``overlap.init_comm_state``.
    """
    st = _merge_legacy(settings, dict(
        lr=lr, b1=b1, mesh=mesh, batch=batch, donate=donate, overlap=overlap,
        grad_compression=grad_compression, bucket_bytes=bucket_bytes,
    ), "make_gan_step")
    cfg = st.apply_to_cfg(cfg)
    lr, b1, mesh, batch, donate = st.lr, st.b1, st.mesh, st.batch, st.donate
    if st.comm:
        if mesh is None or batch is None:
            raise ValueError("overlap/grad_compression require mesh and batch")
        from repro.parallel import overlap as OV

        kw = {} if st.bucket_bytes is None else {"bucket_bytes": st.bucket_bytes}
        fn, _ = OV.build_gan_comm_step(
            cfg, mesh, batch=batch, lr=lr, b1=b1,
            grad_compression=st.grad_compression, donate=donate, **kw,
        )
        return fn

    def step(gp, dp, g_opt, d_opt, z, real):
        # Simultaneous G/D update from ONE shared forward: both objectives
        # come out of a single gan_losses evaluation, and the two gradient
        # trees are two vjp calls on the same linearization.  One generator
        # forward per step (the alternating form ran it twice), and the
        # d-side cotangent through the generator is dead code XLA removes.
        # Sharded, this is the comm win: no mid-step re-gather exists
        # because no updated param is consumed again within the step.
        def both(gp_, dp_):
            gl, dl, (g_stats, d_stats, _) = gan_losses(gp_, dp_, cfg, z, real)
            return (gl, dl), (g_stats, d_stats)

        (g_loss, d_loss), vjp, (g_stats, d_stats) = jax.vjp(
            both, gp, dp, has_aux=True
        )
        one, zero = jnp.ones_like(g_loss), jnp.zeros_like(d_loss)
        g_grads, _ = vjp((one, zero))
        _, d_grads = vjp((zero, one))
        gp2, g_opt2, gm = adamw_update(gp, g_grads, g_opt, lr=lr, b1=b1)
        gp2 = G.merge_bn_stats(gp2, g_stats)
        dp2, d_opt2, dm = adamw_update(dp, d_grads, d_opt, lr=lr, b1=b1)
        dp2 = G.merge_bn_stats(dp2, d_stats)
        metrics = {
            "g_loss": g_loss,
            "d_loss": d_loss,
            "g_grad_norm": gm["grad_norm"],
            "d_grad_norm": dm["grad_norm"],
        }
        # in-jit sentinel bit: one fused isfinite reduction over the four
        # scalars above, read by the host as part of the metrics fetch
        metrics["nonfinite"] = R.nonfinite_flag(metrics)
        return gp2, dp2, g_opt2, d_opt2, metrics

    if mesh is None:
        return jax.jit(step)
    if batch is None:
        raise ValueError("batch (global batch size) is required with mesh")
    check_gspmd_partitionable(cfg)
    from repro.parallel import sharding as SH

    gsp, dsp, _ = SH.gan_param_specs(cfg, mesh)
    zspec, rspec, _ = SH.gan_batch_specs(cfg, batch, mesh)
    mspec = {k: P() for k in METRIC_SPEC_KEYS}
    named = lambda t: SH.named(mesh, t)
    return jax.jit(
        step,
        in_shardings=named(
            (gsp, dsp, SH.opt_specs(gsp), SH.opt_specs(dsp), zspec, rspec)
        ),
        out_shardings=named((gsp, dsp, SH.opt_specs(gsp), SH.opt_specs(dsp), mspec)),
        donate_argnums=(0, 1, 2, 3) if donate else (),
    )


def train_gan(
    cfg: GANConfig,
    *,
    steps: int = 200,
    batch: int = 16,
    seed: int = 0,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    log_every: int = 10,
    hooks: TrainHooks = TrainHooks(),
    dtype=jnp.float32,
    settings: Optional[StepSettings] = None,
    policy: Optional[R.FaultPolicy] = None,
    fault_plan=None,
    handle_signals: bool = True,
    deconv_impl=_UNSET,
    conv_impl=_UNSET,
    mesh=_UNSET,
    overlap=_UNSET,
    grad_compression=_UNSET,
    bucket_bytes=_UNSET,
) -> dict:
    """End-to-end GAN training on synthetic data; restartable.

    Step construction is configured by ``settings=StepSettings(...)``
    (the individual ``deconv_impl``/``conv_impl``/``mesh``/``overlap``/
    ``grad_compression``/``bucket_bytes`` kwargs are a deprecated spelling
    of the same fields); ``batch`` here is the training loop's global batch
    and overrides ``settings.batch`` for the step build.

    ``deconv_impl`` overrides ``cfg.deconv_impl``; with a ``*_prepacked``
    impl the generator trains in the Winograd domain — params hold the
    packed transformed weights (G-transform runs once at init), the forward
    consumes them directly, and the backward is the Pallas engines, so no
    step ever re-runs the weight transform or pack.  ``conv_impl``
    likewise overrides the discriminator backend: a prepacked/chained conv
    impl puts the FULL adversarial step — both nets, both grads — in the
    engine domain.

    ``mesh`` runs the same loop multi-device: params/opt state are placed
    per ``parallel.sharding.gan_param_specs`` (FSDP + TP with ZeRO-sharded
    moments) and every step is the donated, NamedSharding-constrained jit
    from ``make_gan_step(mesh=...)``.  ``batch`` must divide the mesh's
    ("pod","data") extent for the inputs to shard (otherwise they replicate,
    recorded in the spec fallback log).

    ``overlap``/``grad_compression``/``bucket_bytes`` select the
    communication-efficient step (see ``make_gan_step``); with int8
    compression the error-feedback residuals (``CommState``) are part of
    the checkpoint tree, so fault-restore and resume replay bit-exact;
    pre-existing checkpoints without a comm subtree restore with zeroed
    residuals (one step of bounded extra quantization error).

    Resilience (see ``train/resilience.py``): ``policy`` is the
    ``FaultPolicy`` bounding fault-restores (per-step crashloop budget,
    run-wide budget, capped exponential backoff) and deciding what a
    sentinel-flagged divergent step does (``skip``/``rollback``/``abort``
    with an optional per-rollback lr scale).  ``fault_plan`` installs one
    ``TrainFaultPlan`` (or a sequence) for chaos injection.  With
    ``handle_signals`` (default), SIGTERM/SIGINT trigger a final atomic
    checkpoint (params + loop state) and a clean return with
    ``"preempted": True``; relaunching with the same ``ckpt_dir`` resumes
    to metrics bit-identical to an uninterrupted run.  The result dict
    carries ``counters``/``fault_log``/``faults_injected`` so a chaos
    harness can reconcile injected vs handled faults.
    """
    st = _merge_legacy(settings, dict(
        deconv_impl=deconv_impl, conv_impl=conv_impl, mesh=mesh,
        overlap=overlap, grad_compression=grad_compression,
        bucket_bytes=bucket_bytes,
    ), "train_gan")
    st = dataclasses.replace(st, batch=batch)  # the loop batch is the global batch
    cfg = st.apply_to_cfg(cfg)
    mesh = st.mesh
    pol = policy if policy is not None else R.FaultPolicy()
    plans = () if fault_plan is None else (
        tuple(fault_plan) if isinstance(fault_plan, (list, tuple)) else (fault_plan,)
    )
    skip_mode = pol.on_divergence == "skip"
    if skip_mode and st.donate:
        # "skip" reverts to the pre-step buffers, so they must stay alive
        st = dataclasses.replace(st, donate=False)
    detector = R.DivergenceDetector(pol) if pol.sentinel else None

    counters: dict = {
        "restores": 0, "rollbacks": 0, "skips": 0, "sentinel_trips": 0,
        "ckpt_fallbacks": 0, "injected_handled": {},
    }
    fault_log: list[dict] = []

    def _warn_corrupt(step_, err):
        counters["ckpt_fallbacks"] += 1
        warnings.warn(
            f"checkpoint step {step_} failed integrity verification "
            f"({err}); falling back to the next-older checkpoint",
            RuntimeWarning, stacklevel=2,
        )

    k = jax.random.PRNGKey(seed)
    kg, kd = jax.random.split(k)
    gp = G.generator_init(kg, cfg, dtype)
    dp = G.discriminator_init(kd, cfg, dtype)
    g_opt, d_opt = adamw_init(gp), adamw_init(dp)
    _like = lambda: {"gp": gp, "dp": dp, "g_opt": g_opt, "d_opt": d_opt}

    start = 0
    restored_ls = None
    if ckpt_dir:
        last, tree = C.restore_latest_valid(ckpt_dir, _like(), on_skip=_warn_corrupt)
        if last is not None:
            gp, dp, g_opt, d_opt = tree["gp"], tree["dp"], tree["g_opt"], tree["d_opt"]
            start = last
            restored_ls = C.load_loop_state(ckpt_dir, last)

    def _build_step(scale: float):
        s2 = st if scale == 1.0 else dataclasses.replace(st, lr=st.lr * scale)
        if mesh is not None:
            return make_gan_step(cfg, settings=s2)
        return make_gan_step(cfg, settings=dataclasses.replace(s2, batch=None))

    def _restore_comm(step_, template):
        """Comm residuals from the checkpoint; zero template for pre-comm
        checkpoints (back-compat: one step of bounded quantization error)."""
        try:
            host = C.restore_checkpoint(ckpt_dir, step_, {"comm": template})
        except KeyError:
            return template
        return jax.tree.map(
            lambda a, t: jax.device_put(np.asarray(a), t.sharding),
            host["comm"], template,
        )

    comm = None
    if mesh is not None:
        from repro.parallel import sharding as SH

        gsp, dsp, _ = SH.gan_param_specs(cfg, mesh)
        gp = jax.device_put(gp, SH.named(mesh, gsp))
        dp = jax.device_put(dp, SH.named(mesh, dsp))
        g_opt = jax.device_put(g_opt, SH.named(mesh, SH.opt_specs(gsp)))
        d_opt = jax.device_put(d_opt, SH.named(mesh, SH.opt_specs(dsp)))
        step_fn = _build_step(1.0)
        if st.grad_compression is not None:
            from repro.parallel import overlap as OV

            ckw = {} if st.bucket_bytes is None else {"bucket_bytes": st.bucket_bytes}
            comm = OV.init_comm_state(gp, dp, mesh, **ckw)
            if ckpt_dir and start:
                comm = _restore_comm(start, comm)
    elif st.comm:
        raise ValueError("overlap/grad_compression require mesh")
    else:
        step_fn = _build_step(1.0)

    metrics_hist: list[dict] = []
    lr_scale = 1.0
    if restored_ls:
        metrics_hist = [
            e for e in restored_ls.get("metrics_hist", [])
            if e.get("step", 0) <= start
        ]
        lr_scale = float(restored_ls.get("lr_scale", 1.0))
        if lr_scale != 1.0:
            step_fn = _build_step(lr_scale)

    def _append_metrics(entry: dict) -> None:
        # replayed log boundaries replace, never double-append
        metrics_hist[:] = [e for e in metrics_hist if e["step"] != entry["step"]]
        metrics_hist.append(entry)

    def _save(step_) -> None:
        tree = _like()
        if comm is not None:
            tree["comm"] = comm
        C.save_checkpoint(ckpt_dir, step_, tree, loop_state={
            "step": step_, "lr_scale": lr_scale,
            "metrics_hist": metrics_hist, "counters": counters,
        })

    faulted = False
    preempted = False
    attempts_at: dict[int, int] = {}
    s = start

    def _restore_to_latest() -> None:
        nonlocal gp, dp, g_opt, d_opt, comm, s, metrics_hist
        last, tree = C.restore_latest_valid(ckpt_dir, _like(), on_skip=_warn_corrupt)
        if last is None:
            # no (valid) checkpoint yet: restart from init — including the
            # metrics history, which belongs to the discarded trajectory
            kg2, kd2 = jax.random.split(jax.random.PRNGKey(seed))
            gp, dp = G.generator_init(kg2, cfg, dtype), G.discriminator_init(kd2, cfg, dtype)
            g_opt, d_opt = adamw_init(gp), adamw_init(dp)
            s = 0
            metrics_hist = []
        else:
            gp, dp, g_opt, d_opt = tree["gp"], tree["dp"], tree["g_opt"], tree["d_opt"]
            s = last
            ls = C.load_loop_state(ckpt_dir, last)
            src = ls.get("metrics_hist", metrics_hist) if ls else metrics_hist
            # replayed steps must not keep stale post-checkpoint entries
            metrics_hist = [e for e in src if e.get("step", 0) <= last]
        if comm is not None:
            if last is None:
                from repro.parallel import overlap as OV

                ckw = {} if st.bucket_bytes is None else {"bucket_bytes": st.bucket_bytes}
                comm = OV.init_comm_state(gp, dp, mesh, **ckw)
            else:
                comm = _restore_comm(last, comm)
        if detector is not None:
            detector.reset()

    def _bounded_restore(cause, *, verdict=None, injected=False) -> None:
        """One budgeted restore-and-replay: crashloop detection (same step
        failing repeatedly), run-wide budget, capped exponential backoff,
        then the actual restore.  Past the budget the fault is carried out
        of the loop as a ``TrainFaultError`` instead of replayed forever."""
        nonlocal lr_scale, step_fn
        attempt = attempts_at.get(s, 0) + 1
        attempts_at[s] = attempt
        total = counters["restores"] + counters["rollbacks"]
        if attempt > pol.max_restores_per_step or total >= pol.max_total_restores:
            why = (
                f"step {s} failed {attempt} time(s) "
                f"(budget: {pol.max_restores_per_step}/step, "
                f"{pol.max_total_restores}/run)"
            )
            if verdict is not None:
                raise R.TrainDivergenceError(
                    why, verdict=verdict, step=s, attempts=attempt, cause=cause,
                )
            raise R.TrainFaultError(
                why, step=s, kind="crashloop", attempts=attempt, cause=cause,
            ) from cause
        if verdict is not None:
            counters["rollbacks"] += 1
        else:
            counters["restores"] += 1
        if injected and verdict is None:
            # injected nan_grad divergences were already counted by the
            # sentinel path; only injected raises are accounted here
            ih = counters["injected_handled"]
            ih["raise"] = ih.get("raise", 0) + 1
        fault_log.append({
            "step": s, "attempt": attempt, "injected": injected,
            "kind": "divergence" if verdict is not None else "exception",
            "verdict": verdict,
            "action": "rollback" if verdict is not None else "restore",
            "error": None if cause is None else f"{type(cause).__name__}: {cause}",
        })
        wait = pol.backoff(attempt - 1)
        if wait:
            time.sleep(wait)
        _restore_to_latest()
        if verdict is not None and pol.lr_scale != 1.0:
            lr_scale *= pol.lr_scale
            step_fn = _build_step(lr_scale)

    with R.PreemptionGuard(install=handle_signals) as guard:
        while s < steps:
            if guard.requested:
                # preemption-safe exit: one final atomic checkpoint with the
                # loop state, then a clean return — resume is bit-exact
                preempted = True
                if ckpt_dir:
                    _save(s)
                break
            t0 = time.monotonic()
            prev = None
            inj: list = []
            try:
                if hooks.inject_fault_at == s and not faulted:
                    faulted = True
                    raise RuntimeError(f"injected fault at step {s}")
                inj = [
                    kind for kind in (
                        p.draw(step=s, attempt=attempts_at.get(s, 0)) for p in plans
                    ) if kind
                ]
                if "preempt" in inj:
                    guard.request()  # honored at the next step boundary
                if "corrupt_ckpt" in inj and ckpt_dir:
                    R.corrupt_latest_checkpoint(ckpt_dir)
                if "raise" in inj:
                    raise R.InjectedTrainFault(f"injected raise at step {s}")
                with obs.span("gan.train.data", step=s):
                    z = D.latent_batch(seed, s, batch, cfg.z_dim) if cfg.z_dim else D.gan_batch(
                        seed, 1_000_000 + s, batch, cfg.img_hw
                    )
                    real = D.gan_batch(seed, s, batch, cfg.img_hw)
                if "nan_grad" in inj:
                    # NaN in the batch -> NaN losses/grads -> NaN update:
                    # the same poisoning a broken kernel or fp overflow does
                    z = z * jnp.float32(np.nan)
                if skip_mode:
                    prev = (gp, dp, g_opt, d_opt, comm)
                with obs.span("gan.train.step", step=s):
                    if comm is not None:
                        gp, dp, g_opt, d_opt, comm, m = step_fn(
                            gp, dp, g_opt, d_opt, comm, z, real
                        )
                    else:
                        gp, dp, g_opt, d_opt, m = step_fn(gp, dp, g_opt, d_opt, z, real)
                if hooks.step_deadline_s and time.monotonic() - t0 > hooks.step_deadline_s:
                    raise TimeoutError(f"step {s} exceeded deadline (straggler)")
            except (RuntimeError, TimeoutError) as e:
                if isinstance(e, R.TrainFaultError):
                    raise  # already carried past a budget: do not re-wrap
                # fault path: restore the newest VALID checkpoint and replay
                # (a corrupt latest falls back to the next-older one) —
                # bounded by the policy's restore budget
                if not ckpt_dir:
                    raise
                _bounded_restore(e, injected=isinstance(e, R.InjectedTrainFault))
                continue
            host_m = None
            if detector is not None:
                with obs.span("gan.train.sync", step=s):
                    host_m = {k2: float(v) for k2, v in m.items()}
                verdict = detector.observe(s, host_m)
                if verdict is not None:
                    counters["sentinel_trips"] += 1
                    if "nan_grad" in inj and verdict.startswith("nonfinite"):
                        ih = counters["injected_handled"]
                        ih["nan_grad"] = ih.get("nan_grad", 0) + 1
                    if pol.on_divergence == "abort":
                        raise R.TrainDivergenceError(
                            f"sentinel flagged step {s}: {verdict}",
                            verdict=verdict, step=s,
                        )
                    if skip_mode:
                        counters["skips"] += 1
                        fault_log.append({
                            "step": s, "kind": "divergence", "verdict": verdict,
                            "action": "skip", "injected": "nan_grad" in inj,
                            "attempt": 0, "error": None,
                        })
                        if counters["skips"] > pol.max_skips:
                            raise R.TrainDivergenceError(
                                f"step {s}: skip budget ({pol.max_skips}) "
                                f"exhausted; last verdict: {verdict}",
                                verdict=verdict, step=s,
                                attempts=counters["skips"],
                            )
                        # discard the update: revert to the pre-step buffers
                        gp, dp, g_opt, d_opt, comm = prev
                        s += 1
                        continue
                    # rollback
                    if not ckpt_dir:
                        raise R.TrainDivergenceError(
                            f"sentinel flagged step {s} ({verdict}) and the "
                            "policy says rollback, but there is no ckpt_dir "
                            "to roll back to",
                            verdict=verdict, step=s,
                        )
                    _bounded_restore(None, verdict=verdict,
                                     injected="nan_grad" in inj)
                    continue
            if (s + 1) % log_every == 0 or s + 1 == steps:
                hm = host_m
                if hm is None:
                    with obs.span("gan.train.sync", step=s):
                        hm = {k2: float(v) for k2, v in m.items()}
                _append_metrics({"step": s + 1, **hm})
                if hooks.on_step:
                    hooks.on_step(s + 1, hm)
            if ckpt_dir and (s + 1) % ckpt_every == 0:
                _save(s + 1)
            s += 1
    return {
        "params": {"gp": gp, "dp": dp},
        "metrics": metrics_hist,
        "final_step": s,
        "preempted": preempted,
        "counters": counters,
        "fault_log": fault_log,
        "faults_injected": R.plan_totals(plans),
        "lr_scale": lr_scale,
    }
