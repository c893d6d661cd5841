"""Window driver for serving cells: ``GanServeEngine`` behind ``AsyncGanServer``.

Set-up makes the generator's raw weights on the device from the seed (one
jitted call, f32 as served), builds the engine with that one resident, and
warms, for every request size the traffic sends, each count of such
requests that fits one dispatch, by driving the server with that many
simultaneous requests: the engine pads to the bucket and concatenates and
slices per request with eager ops, each of which compiles per shape.  A
dispatch that mixes sizes concatenates a sequence of shapes that no warm-up
can cover, so ``compiles_in_window`` is noted in every run.

Traffic (``params``):

* ``arrivals: poisson`` — open loop: ``round(rate_rps * seconds)``
  requests at seed-drawn times in the window, each timed from when it was
  due to when its result was in hand;
* ``arrivals: closed`` — ``clients`` callers, each with one request
  outstanding, resubmitting as soon as its result is in hand, until the
  window closes.

A request asks for ``size_min`` to ``size_max`` images: every seed sends the
same sizes, an even share of each, in its own order.  Latents are drawn on
the host from the seed and handed to the server as host arrays, with
``deadline_ms`` of batching delay.  After the window (and a drain of up to a minute) a
seed-drawn sample of the served requests, ``per_bucket`` from every bucket
the window used where there are that many, is compared with the reference
generator at HIGHEST precision; a request that failed, was rejected or
never came makes the run incorrect.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops as F
from bench import reference as R
from bench import system
from bench.device import memory_peak_bytes
from bench import traffic as TR

DRAIN_S = 60.0


class _Compiles:
    n = 0
    listening = False

    @staticmethod
    def on_event(ev: str, duration: float, **kw) -> None:
        if ev.endswith("backend_compile_duration"):
            _Compiles.n += 1


def compiles() -> int:
    """Programs the backend has compiled since this was first called."""
    if not _Compiles.listening:
        jax.monitoring.register_event_duration_secs_listener(_Compiles.on_event)
        _Compiles.listening = True
    return _Compiles.n


def _weights(cfg: dict, seed: int):
    """The generator's raw weights, made on the device in one call; the key
    is an argument, so one compiled program serves every seed."""
    return jax.jit(lambda k: R.generator_init(k, cfg, packed=False))(jax.random.PRNGKey(seed))


def build(ctx):
    system.import_program()
    from repro.serve.engine import GanServeEngine
    from repro.serve.loop import AsyncGanServer

    p, cfg = ctx.params, ctx.cfg
    pc = system.gan_config(cfg)
    weights = _weights(cfg, ctx.seed)
    engine = GanServeEngine(models={cfg["name"]: (weights, pc)}, batch=p["batch"])
    impl = engine.archs[cfg["name"]].cfg.deconv_impl
    if impl != cfg["deconv_impl"]:
        raise RuntimeError(f"the engine serves {impl!r}, the configuration states "
                           f"{cfg['deconv_impl']!r}")
    server = AsyncGanServer(engine, max_queue=p["max_queue"]).start()
    return weights, engine, server


def warm(ctx, engine, server) -> None:
    p, cfg = ctx.params, ctx.cfg
    rng = np.random.default_rng(0)
    for size in range(p["size_min"], p["size_max"] + 1):
        for k in range(1, engine.batch // size + 1):  # requests per dispatch
            zs = [rng.standard_normal((size, cfg["z_dim"]), np.float32) for _ in range(k)]
            futs = [server.submit(z, arch=cfg["name"], deadline_ms=p["deadline_ms"]) for z in zs]
            for f in futs:
                jax.block_until_ready(f.result(timeout=900))
    res = engine.archs[cfg["name"]]
    res.bucket_counts.clear()
    engine.dispatch_log.clear()


class Sent:
    """One request of the window: when it was due, sent and in hand, and, for
    the requests kept for the comparison, its images."""

    __slots__ = ("index", "due", "sent", "done", "z_index", "future", "rid", "size", "out")

    def __init__(self, index: int, due: float, z_index: int, future):
        self.index, self.due, self.z_index, self.future = index, due, z_index, future
        self.sent = time.perf_counter()
        self.rid, self.size = future.request.rid, future.request.size
        self.done = self.out = None


class Window:
    """The latents a cell's requests draw from, and the served requests whose
    images are kept for the comparison: a uniform sample of ``4 * sample``
    drawn from the seed as results come (reservoir sampling), so that the
    other results are dropped at once."""

    def __init__(self, ctx, n: int):
        self.ctx = ctx
        self.sizes = TR.request_sizes(ctx.params, n, np.random.default_rng([ctx.seed, 4]))
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])
        self.z = np.random.default_rng([ctx.seed, 1]).standard_normal(
            (int(self.offsets[-1]), ctx.cfg["z_dim"]), np.float32)
        self.rng = np.random.default_rng([ctx.seed, 2])
        self.kept: list[Sent] = []
        self.served = 0
        self.sent: list[Sent] = []

    def latents(self, z_index: int) -> np.ndarray:
        return self.z[self.offsets[z_index]:self.offsets[z_index + 1]]

    def submit(self, server, due: float) -> Sent:
        i = len(self.sent)
        j = i % len(self.sizes)
        with jax.profiler.TraceAnnotation("bench.serve.submit"):
            fut = server.submit(self.latents(j), arch=self.ctx.cfg["name"],
                                deadline_ms=self.ctx.params["deadline_ms"])
        s = Sent(i, due, j, fut)
        self.sent.append(s)
        return s

    def in_hand(self, s: Sent, timeout: float) -> None:
        """Wait for a request's result, stamp when it was in hand, and drop
        the result unless it is kept."""
        with jax.profiler.TraceAnnotation("bench.serve.wait"):
            ok = s.future.request.event.wait(timeout)
        req = s.future.request
        if ok and req.done:
            s.done = time.perf_counter()
            cap = 4 * self.ctx.params["sample"]
            if len(self.kept) < cap:
                self.kept.append(s)
                s.out = req.out
            else:
                j = int(self.rng.integers(0, self.served + 1))
                if j < cap:
                    self.kept[j].out = None
                    self.kept[j] = s
                    s.out = req.out
            self.served += 1
        s.future = None


def open_loop(ctx, server, t0: float) -> Window:
    """Send at the scheduled times.  Requests are served FIFO, so the sender
    stamps results in order between sends, and the rest after the last send."""
    due = TR.schedule(ctx.params, ctx.seconds, np.random.default_rng([ctx.seed, 0]))
    w = Window(ctx, len(due))
    nxt = 0  # oldest request not yet in hand
    for d in due:
        while True:
            now = time.perf_counter()
            if now >= t0 + d:
                break
            while nxt < len(w.sent) and w.sent[nxt].future.request.resolved:
                w.in_hand(w.sent[nxt], 0)
                nxt += 1
            with jax.profiler.TraceAnnotation("bench.serve.sleep"):
                time.sleep(min(t0 + d - now, 5e-4))
        w.submit(server, t0 + d)
    end = time.perf_counter() + DRAIN_S
    for s in w.sent[nxt:]:
        w.in_hand(s, max(0.0, end - time.perf_counter()))
    return w


def closed_loop(ctx, server, t0: float) -> Window:
    """``clients`` callers with one request each outstanding, until the window
    closes; completions are FIFO, so the oldest outstanding request is the
    next to come back."""
    w = Window(ctx, max(1, int(ctx.params["max_rate_rps"] * ctx.seconds)))
    live = [w.submit(server, time.perf_counter()) for _ in range(ctx.params["clients"])]
    t_end = t0 + ctx.seconds
    while live:
        s = live.pop(0)
        w.in_hand(s, DRAIN_S)
        now = time.perf_counter()
        if s.done is not None and now < t_end:
            live.append(w.submit(server, now))
    return w


def _sample(ctx, w: Window, engine) -> list[Sent]:
    """The kept requests the comparison reads: from every bucket the window
    used up to ``per_bucket`` each, then the rest in seed order, ``sample``
    in all."""
    p = ctx.params
    kept = sorted(w.kept, key=lambda s: s.index)
    size = {s.rid: s.size for s in w.sent}
    bucket = {}
    for rids in engine.dispatch_log:
        b = engine.bucket_for(sum(size.get(r, 0) for r in rids))
        bucket.update({r: b for r in rids})
    picked: list[Sent] = []
    for b in sorted({bucket[s.rid] for s in kept}):
        picked += [s for s in kept if bucket[s.rid] == b][: p["per_bucket"]]
    picked += [s for s in kept if s not in picked]
    return picked[: p["sample"]]


def reference_images(cfg: dict, weights, zs: np.ndarray, *, precision=R.HIGHEST,
                     dtype=jnp.float32) -> np.ndarray:
    """The reference generator over a fixed-shape stack of latents."""
    f = jax.jit(lambda w, z: R.generator_eval_raw(
        jax.tree.map(lambda a: a.astype(dtype), w), cfg, z.astype(dtype), precision))
    return np.asarray(f(weights, jnp.asarray(zs)).astype(jnp.float32))


def image_gaps(out: np.ndarray, ref: np.ndarray) -> tuple[float, float]:
    """(relative L2, worst pixel over the reference's largest magnitude), in f64."""
    a, b = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    if a.shape != b.shape or not np.isfinite(a).all():
        return math.inf, math.inf
    return (float(np.linalg.norm(a - b) / np.linalg.norm(b)),
            float(np.abs(a - b).max() / np.abs(b).max()))


def compare(ctx, w: Window, picked: list[Sent], weights) -> dict:
    """Image gaps of the picked requests against the reference, run once
    over a fixed shape (``sample`` requests of ``size_max`` rows, the unused
    rows zero), and the count of picked results not stored in float32, the
    precision the configuration states."""
    p = ctx.params
    zs = np.zeros((p["sample"], p["size_max"], ctx.cfg["z_dim"]), np.float32)
    for j, s in enumerate(picked):
        zs[j, :s.size] = w.latents(s.z_index)
    ref = reference_images(ctx.cfg, weights, zs.reshape(-1, ctx.cfg["z_dim"]))
    ref = ref.reshape(p["sample"], p["size_max"], *ref.shape[1:])
    if not picked:
        return {"img_rel_l2": math.inf, "img_rel_max": math.inf, "images_not_f32": math.inf}
    gaps = [image_gaps(s.out, ref[j, :s.size]) for j, s in enumerate(picked)]
    return {"img_rel_l2": max(g[0] for g in gaps), "img_rel_max": max(g[1] for g in gaps),
            "images_not_f32": float(sum(s.out.dtype != np.float32 for s in picked))}


def run(ctx) -> dict:
    p, cfg = ctx.params, ctx.cfg
    weights, engine, server = build(ctx)
    warm(ctx, engine, server)
    compiled = compiles()
    ctx.tracer.start()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process
    ctx.tracer.window_begin()
    w = (closed_loop if p["arrivals"] == "closed" else open_loop)(ctx, server, t0)
    ctx.tracer.window_end()
    ctx.tracer.stop()
    n_compiles = compiles() - compiled
    server.stop(drain=True)
    peak = memory_peak_bytes()
    dispatches = len(engine.dispatch_log)
    picked = _sample(ctx, w, engine)
    for s in picked:
        s.out = np.asarray(s.out)
    for s in w.sent:
        if s not in picked:
            s.out = None
    del engine, server
    gc.collect()

    sent = w.sent
    ok = [s for s in sent if s.done is not None]
    in_window = [s for s in ok if s.done <= t0 + ctx.seconds]
    nums = compare(ctx, w, picked, weights)
    nums["unserved"] = float(len(sent) - len(ok))
    checks = {k: (v, ctx.limits[k]) for k, v in nums.items()}
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    gen_flops = F.total_flops(F.generate_passes(cfg, 1))
    images = sum(s.size for s in ok)
    images_in_window = sum(s.size for s in in_window)
    lat = TR.latencies_ms([s.due for s in sent], [s.done for s in sent])
    return {
        "correct": correct, "attempted": len(sent), "failed": len(sent) - len(ok),
        "checks": checks, "memory_peak_bytes": peak, "setup_s": setup_s,
        "window_s": ctx.seconds,
        "e2e": {"setup_s": setup_s,
                "serve_p95_ms": TR.percentile(lat, 95),
                "serve_images_per_s": images_in_window / ctx.seconds},
        "dispatches": dispatches, "images": images,
        "work": {"model_flops": images_in_window * gen_flops,
                 "engine_passes": F.generate_passes(cfg, images)},
        "trace": ctx.tracer.reduce() if ctx.trace else None,
        "notes": {"compiles_in_window": n_compiles},
    }
