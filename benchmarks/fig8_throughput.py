"""Paper Fig. 8: DeConv throughput comparison — plus the serving load test.

Three views:
  (a) the paper's own DSE timing model (eqs. 5-9) with its FPGA constants
      (100 MHz, 4 GB/s), reproducing the reported speedup ordering;
  (b) measured wall-time of the three numerically-identical implementations
      on this host (CPU XLA), small batch;
  (c) an open-loop load test of the async multi-tenant serve engine
      (``serve.AsyncGanServer`` over ``GanServeEngine``): several gan_zoo
      archs resident in one engine process, driven by Poisson and bursty
      arrival processes at a fixed offered rate, reporting delivered
      throughput and p50/p95/p99 end-to-end latency per arch and per
      arrival pattern — the paper's sustained-images/sec figure recast as
      a serving benchmark.  ``--smoke --update BENCH.json`` merges the
      table into the committed report as the ``"serve"`` section, gated by
      ``benchmarks.compare_bench --serve-rel-tol``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gan_zoo import GANS
from repro.device import enable_compile_cache
from repro.core import tdc_deconv2d, winograd_deconv2d, zero_padded_deconv2d
from repro.core.complexity import dse_model, mults_tdc, mults_winograd, mults_zero_padded
from repro.models import gan as G
from repro.serve import (
    AsyncGanServer,
    FaultPlan,
    GanServeEngine,
    GanServeError,
    GanServeRejected,
)
from repro.serve import metrics as SM

from .workloads import GAN_LAYERS


def paper_model() -> list[dict]:
    rows = []
    for model, layers in GAN_LAYERS.items():
        # eq. (9) computational roof per layer, aggregated as total ops / total time
        total_ops = 0.0
        t_wino = 0.0
        for l in layers:
            m = dse_model(l)
            ops = 2 * mults_winograd(l)
            total_ops += ops
            t_wino += ops / m["computational_roof_ops"]
        # zero-padded / tdc modeled via mult ratio at the same DSP throughput
        mult_zp = sum(mults_zero_padded(l) for l in layers)
        mult_tdc = sum(mults_tdc(l) for l in layers)
        mult_w = sum(mults_winograd(l) for l in layers)
        rows.append(
            {
                "model": model,
                "t_winograd_s": t_wino,
                "t_tdc_s": t_wino * mult_tdc / mult_w,
                "t_zero_padded_s": t_wino * mult_zp / mult_w,
                "speedup_vs_zp": round(mult_zp / mult_w, 2),
                "speedup_vs_tdc": round(mult_tdc / mult_w, 2),
            }
        )
    return rows


def _time(fn, *args, n=3) -> float:
    # one warmup evaluation (the old isinstance-on-a-fresh-call spelling ran
    # fn twice, double-counting warmup work and skewing short measurements)
    r = fn(*args)
    (r[0] if isinstance(r, tuple) else r).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
        (r[0] if isinstance(r, tuple) else r).block_until_ready()
    return (time.perf_counter() - t0) / n


def measured(batch=2, scale=4) -> list[dict]:
    """Wall-time on this host; channels scaled down by ``scale`` to keep CPU
    times sane — ratios are what matter."""
    rng = np.random.default_rng(0)
    rows = []
    for model, layers in GAN_LAYERS.items():
        t = {"zero_padded": 0.0, "tdc": 0.0, "winograd": 0.0}
        for l in layers:
            n_in = max(4, l.n_in // scale)
            m_out = max(4, l.m_out // scale)
            x = jnp.asarray(rng.standard_normal((batch, l.h_in, l.w_in, n_in)), jnp.float32)
            w = jnp.asarray(
                rng.standard_normal((l.dims.kernel, l.dims.kernel, n_in, m_out)), jnp.float32
            )
            zp = jax.jit(lambda x, w, d=l.dims: zero_padded_deconv2d(x, w, d))
            td = jax.jit(lambda x, w, d=l.dims: tdc_deconv2d(x, w, d))
            wi = jax.jit(lambda x, w, d=l.dims: winograd_deconv2d(x, w, d))
            t["zero_padded"] += _time(zp, x, w)
            t["tdc"] += _time(td, x, w)
            t["winograd"] += _time(wi, x, w)
        rows.append(
            {
                "model": model,
                "t_zero_padded_us": round(t["zero_padded"] * 1e6, 1),
                "t_tdc_us": round(t["tdc"] * 1e6, 1),
                "t_winograd_us": round(t["winograd"] * 1e6, 1),
                "speedup_vs_zp": round(t["zero_padded"] / t["winograd"], 2),
                "speedup_vs_tdc": round(t["tdc"] / t["winograd"], 2),
            }
        )
    return rows


# ----------------------------------------------------- serving load test
SMOKE_ARCHS = ("dcgan", "artgan")  # latent-input archs; both resident at once


def build_serve_engine(archs=SMOKE_ARCHS, *, impl: str = "ref", batch: int = 8,
                       max_ch: int = 8, seed: int = 0,
                       **engine_kw) -> GanServeEngine:
    """One engine process with every arch in ``archs`` resident (its own
    prepacked weights + jit cache, shared request queue).  ``max_ch`` caps
    channel widths (train_step's smoke scaling) so CPU runs stay
    seconds-scale; 0 keeps the full models.  ``engine_kw`` passes through
    to ``GanServeEngine`` (retry budget, breaker knobs, nan_guard, ...)."""
    from .train_step import _shrunk_gan_cfg

    models = {}
    for i, name in enumerate(archs):
        cfg = dataclasses.replace(GANS[name], deconv_impl=impl)
        if max_ch:
            cfg = _shrunk_gan_cfg(cfg, max_ch)
        gp = G.generator_init(jax.random.PRNGKey(seed + i), cfg, jnp.float32)
        models[name] = (gp, cfg)
    return GanServeEngine(models=models, batch=batch, **engine_kw)


def poisson_arrivals(rate_rps: float, duration_s: float, rng) -> list[float]:
    """Open-loop Poisson process: exponential inter-arrivals at the offered
    rate, independent of service times."""
    t, out = 0.0, []
    while True:
        t += rng.exponential(1.0 / rate_rps)
        if t >= duration_s:
            return out
        out.append(t)


def bursty_arrivals(rate_rps: float, duration_s: float, rng, *,
                    burst: int = 4) -> list[float]:
    """Same offered rate as the Poisson process, but arrivals land in
    back-to-back bursts of ``burst`` — the batching window's best case and
    the admission queue's worst."""
    gap = burst / rate_rps
    out, t = [], 0.0
    while t < duration_s:
        out.extend([t] * burst)
        t += gap
    return [x for x in out if x < duration_s]


ARRIVALS = {"poisson": poisson_arrivals, "bursty": bursty_arrivals}


def _latent(cfg, n: int, rng) -> jax.Array:
    if cfg.z_dim:
        return jnp.asarray(rng.standard_normal((n, cfg.z_dim)), jnp.float32)
    return jnp.asarray(
        rng.standard_normal((n, cfg.img_hw, cfg.img_hw, 3)), jnp.float32
    )


def _warmup_engine(engine: GanServeEngine) -> None:
    """Compile every (arch, bucket) executable off the clock — coalesced
    batches can land on any bucket, and a mid-run jit compile would read as
    seconds of tail latency."""
    rng = np.random.default_rng(0)
    for arch, res in engine.archs.items():
        for k in engine.buckets:
            jax.block_until_ready(
                engine.generate(_latent(res.cfg, k, rng), arch=arch)
            )
    for res in engine.archs.values():
        res.bucket_counts.clear()


def run_load(engine: GanServeEngine, *, pattern: str, rate_rps: float,
             duration_s: float, deadline_ms: float = 25.0,
             max_queue: int = 256, seed: int = 0,
             fault_plan: FaultPlan | None = None) -> tuple[dict, dict]:
    """Drive the engine open-loop through an ``AsyncGanServer`` with the
    named arrival pattern, round-robining requests across the resident
    archs.  Returns ``(summary, accounting)``: the
    ``serve.metrics.summarize`` table (per-arch and ``_all`` rows:
    throughput + p50/p95/p99 e2e latency + SLO components + error
    counters), and a reconciliation dict — ``submitted`` must equal
    ``delivered + failed + rejected`` with ``hung == 0``, the serve
    stack's no-hang invariant.  ``fault_plan`` installs chaos injection on
    the engine for the duration of the run."""
    rng = np.random.default_rng(seed)
    times = ARRIVALS[pattern](rate_rps, duration_s, rng)
    archs = sorted(engine.archs)
    zs = {a: _latent(engine.archs[a].cfg, 1, rng) for a in archs}
    reqs = []
    engine.fault_plan = fault_plan
    try:
        with AsyncGanServer(engine, max_queue=max_queue) as srv:
            t0 = time.monotonic()
            for i, t_s in enumerate(times):
                dt = t0 + t_s - time.monotonic()
                if dt > 0:
                    time.sleep(dt)
                arch = archs[i % len(archs)]
                reqs.append(
                    srv.submit(zs[arch], arch=arch,
                               deadline_ms=deadline_ms).request
                )
    finally:
        engine.fault_plan = None
    # context exit drains: every request has resolved (done/failed/rejected)
    delivered = sum(1 for r in reqs if r.done)
    failed = sum(1 for r in reqs if r.failed and not r.done)
    rejected = sum(1 for r in reqs if r.rejected and not r.done and not r.failed)
    accounting = {
        "submitted": len(reqs), "delivered": delivered, "failed": failed,
        "rejected": rejected,
        "hung": sum(1 for r in reqs if not r.resolved),
    }
    counters = engine.health() if fault_plan is not None else None
    return SM.summarize(reqs, counters=counters), accounting


def load_test(*, archs=SMOKE_ARCHS, rate_rps: float = 30.0,
              duration_s: float = 2.0, batch: int = 8, max_ch: int = 8,
              impl: str = "ref", deadline_ms: float = 25.0, seed: int = 0,
              patterns=("poisson", "bursty"), smoke: bool = False) -> dict:
    """The Fig. 8 serving benchmark: one multi-tenant engine, both arrival
    patterns, flat row table ready for the committed report JSON."""
    engine = build_serve_engine(archs, impl=impl, batch=batch, max_ch=max_ch,
                                seed=seed)
    _warmup_engine(engine)
    rows = []
    for pattern in patterns:
        summary, _ = run_load(engine, pattern=pattern, rate_rps=rate_rps,
                              duration_s=duration_s, deadline_ms=deadline_ms,
                              seed=seed)
        for arch_key in sorted(summary):
            r = {k: (round(v, 3) if isinstance(v, float) else v)
                 for k, v in summary[arch_key].items()}
            rows.append({"pattern": pattern, "arch": arch_key,
                         "offered_rps": rate_rps, **r})
    return {
        "smoke": smoke, "archs": list(archs), "impl": impl, "batch": batch,
        "max_ch": max_ch, "deadline_ms": deadline_ms, "rows": rows,
    }


# ----------------------------------------------------------- chaos harness
def quarantine_drill(engine: GanServeEngine, arch: str) -> dict:
    """Exercise the full circuit-breaker cycle on one resident arch:
    persistent injected faults trip the breaker (``tripped``), a submit
    against the open breaker fast-rejects (``fast_rejected``), and after
    the cooldown a half-open probe through the now-healthy arch re-closes
    it (``recovered``).  Synchronous — futures self-drive the engine."""
    rng = np.random.default_rng(1)
    res = engine.archs[arch]
    z = _latent(res.cfg, 1, rng)
    out = {"tripped": False, "fast_rejected": False, "recovered": False}
    engine.fault_plan = FaultPlan(kind="raise", arch=arch, rate=1.0,
                                  persistent=True)
    try:
        trips = 0
        for _ in range(res.breaker.threshold):
            try:
                engine.submit(z, arch=arch).result(timeout=30.0)
            except GanServeError:
                trips += 1
        out["tripped"] = res.breaker.state == "open" and \
            trips == res.breaker.threshold
        try:
            engine.submit(z, arch=arch)
        except GanServeRejected:
            out["fast_rejected"] = True
    finally:
        engine.fault_plan = None
    time.sleep(res.breaker.cooldown_ms / 1e3 + 0.05)
    try:
        engine.submit(z, arch=arch).result(timeout=30.0)  # half-open probe
        out["recovered"] = res.breaker.state == "closed"
    except (GanServeError, GanServeRejected):
        pass
    return out


def chaos_test(*, archs=SMOKE_ARCHS, fault_rate: float = 0.1,
               fault_kind: str = "mix", rate_rps: float = 30.0,
               duration_s: float = 2.0, batch: int = 8, max_ch: int = 8,
               impl: str = "ref", deadline_ms: float = 100.0,
               seed: int = 0, smoke: bool = False) -> dict:
    """The chaos-harness benchmark: the Fig. 8 serving load test under an
    i.i.d. injected fault rate (``fault_kind`` "raise"/"nan"/"delay" or
    "mix"), followed by a quarantine drill.  The section's ``ok`` flag
    asserts the failure-semantics contract: every submitted request
    resolved (zero hung futures), accounting reconciles (submitted =
    delivered + failed + rejected), and the drilled arch tripped,
    fast-rejected, and recovered through its half-open probe."""
    engine = build_serve_engine(
        archs, impl=impl, batch=batch, max_ch=max_ch, seed=seed,
        nan_guard=True, max_retries=2, breaker_threshold=3,
        breaker_cooldown_ms=150.0,
    )
    _warmup_engine(engine)
    plan = FaultPlan(kind=fault_kind, rate=fault_rate, seed=seed,
                     delay_ms=10.0)
    summary, accounting = run_load(
        engine, pattern="poisson", rate_rps=rate_rps, duration_s=duration_s,
        deadline_ms=deadline_ms, seed=seed, fault_plan=plan,
    )
    drill = quarantine_drill(engine, archs[0])
    rows = []
    for arch_key in sorted(summary):
        r = {k: (round(v, 3) if isinstance(v, float) else v)
             for k, v in summary[arch_key].items()}
        rows.append({"pattern": "poisson", "arch": arch_key,
                     "offered_rps": rate_rps, **r})
    acct_ok = (
        accounting["hung"] == 0
        and accounting["submitted"]
        == accounting["delivered"] + accounting["failed"]
        + accounting["rejected"]
    )
    return {
        "smoke": smoke, "archs": list(archs), "impl": impl, "batch": batch,
        "max_ch": max_ch, "deadline_ms": deadline_ms,
        "fault_rate": fault_rate, "fault_kind": fault_kind,
        "faults_fired": plan.fired, "faults_by_kind": dict(plan.fired_by_kind),
        "accounting": accounting, "drill": drill, "rows": rows,
        "ok": bool(acct_ok and all(drill.values())),
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-scaled load test (shrunk channels, ~2s/pattern)")
    ap.add_argument("--load-only", action="store_true",
                    help="skip the DSE-model and per-layer measured tables")
    ap.add_argument("--skip-load", action="store_true",
                    help="only the DSE-model and per-layer measured tables")
    ap.add_argument("--rate", type=float, default=None, help="offered rps")
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds per arrival pattern")
    ap.add_argument("--batch", type=int, default=8, help="engine row pool")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos harness: i.i.d. injected-fault probability "
                         "per dispatch attempt (> 0 switches the load test "
                         "to the 'serve_chaos' section and gates on the "
                         "no-hang / accounting / quarantine-recovery "
                         "contract)")
    ap.add_argument("--fault-kind", default="mix",
                    choices=("raise", "nan", "delay", "mix"),
                    help="chaos harness: which fault to inject")
    ap.add_argument("--update", default=None, metavar="REPORT.json",
                    help="merge the load-test table into this report as "
                         "its 'serve' section ('serve_chaos' with "
                         "--fault-rate > 0)")
    args = ap.parse_args()

    if not args.load_only:
        for r in paper_model():
            print(
                f"fig8_model,{r['model']},speedup_vs_zp={r['speedup_vs_zp']},"
                f"speedup_vs_tdc={r['speedup_vs_tdc']}"
            )
        for r in measured():
            print(
                f"fig8_measured,{r['model']},wino_us={r['t_winograd_us']},"
                f"speedup_vs_zp={r['speedup_vs_zp']},speedup_vs_tdc={r['speedup_vs_tdc']}"
            )
    if args.skip_load:
        return

    rate = args.rate if args.rate is not None else (30.0 if args.smoke else 50.0)
    duration = args.duration if args.duration is not None else \
        (2.0 if args.smoke else 5.0)

    if args.fault_rate > 0:
        # chaos path: writes its own section, never touches the healthy
        # "serve" baseline, and gates on the failure-semantics contract
        chaos = chaos_test(fault_rate=args.fault_rate,
                           fault_kind=args.fault_kind, rate_rps=rate,
                           duration_s=duration, batch=args.batch,
                           max_ch=8 if args.smoke else 16, smoke=args.smoke)
        acct, drill = chaos["accounting"], chaos["drill"]
        print(
            f"fig8_chaos,accounting,submitted={acct['submitted']},"
            f"delivered={acct['delivered']},failed={acct['failed']},"
            f"rejected={acct['rejected']},hung={acct['hung']},"
            f"faults_fired={chaos['faults_fired']}"
        )
        print(
            f"fig8_chaos,drill,tripped={drill['tripped']},"
            f"fast_rejected={drill['fast_rejected']},"
            f"recovered={drill['recovered']}"
        )
        for row in chaos["rows"]:
            print(
                f"fig8_chaos,{row['pattern']},{row['arch']},"
                f"thpt={row.get('throughput_rps')},p95={row.get('p95_ms')},"
                f"failed={row.get('failed')},rej={row.get('rejected')}"
            )
        if args.update:
            report = {}
            if os.path.exists(args.update):
                with open(args.update) as f:
                    report = json.load(f)
            report["serve_chaos"] = chaos
            with open(args.update, "w") as f:
                json.dump(report, f, indent=1)
            print(f"updated {args.update} (serve_chaos section)")
        if not chaos["ok"]:
            raise SystemExit(
                "chaos harness FAILED: accounting does not reconcile, a "
                "future hung, or the quarantine drill did not recover "
                f"(accounting={acct}, drill={drill})"
            )
        return

    serve = load_test(rate_rps=rate, duration_s=duration, batch=args.batch,
                      max_ch=8 if args.smoke else 16, smoke=args.smoke)
    for row in serve["rows"]:
        print(
            f"fig8_serve,{row['pattern']},{row['arch']},"
            f"offered={row['offered_rps']},thpt={row.get('throughput_rps')},"
            f"p50={row.get('p50_ms')},p95={row.get('p95_ms')},"
            f"p99={row.get('p99_ms')},rej={row.get('rejected')}"
        )
    if args.update:
        report = {}
        if os.path.exists(args.update):
            with open(args.update) as f:
                report = json.load(f)
        report["serve"] = serve
        with open(args.update, "w") as f:
            json.dump(report, f, indent=1)
        print(f"updated {args.update} (serve section)")


if __name__ == "__main__":
    main()
