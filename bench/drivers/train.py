"""Window driver for training cells: ``train_gan`` at the cell's batch.

Set-up builds the program's loop once (its own init from the seed, its jit
step, its synthetic data) and lets it run its first ``warmup_steps`` steps,
the first of which compiles.  The window opens at the ``on_step`` stamp
that ends the warm-up and closes at the first stamp ``seconds`` later; the
benchmark's hook then ends the loop.  The loop reads every step's metrics
(``log_every=1``), so a stamp comes after its step has finished.

Correctness: the first three steps went through the window's own step and
feed.  The plain reference (``bench/reference.py``, HIGHEST precision)
repeats them from the same seed once the window has closed, and the numbers
of ``compare`` are compared; besides, the parameters and both optimizer
moments after step 1 must be float32, the storage the configuration states.
"""
from __future__ import annotations

import gc
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops as F
from bench import reference as R
from bench import system
from bench.device import memory_peak_bytes

REF_STEPS = 3


class WindowClosed(Exception):
    """Raised by the benchmark's hook to end the program's loop."""


def _norms(tree) -> dict:
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


_leaves = lambda tree: {jax.tree_util.keystr(k): v  # noqa: E731
                        for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
_copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
_delta_norms = jax.jit(lambda a, b: _norms(jax.tree.map(lambda x, y: y - x, a, b)))


class StepProbe:
    """Wraps the program's step function: spans around each call, and, from
    the first three calls, what the comparison needs: the parameters before
    step 1, the optimizer's first moments after it (the first gradient
    times 1 - b1, kept on the host), how many leaves of the parameters and
    of both moments it returned in another type than float32, and the
    parameters after step 3, reduced on the device to per-leaf norms."""

    def __init__(self, make_step):
        self.make_step = make_step
        self.calls = 0
        self.p0 = None
        self.m1 = None
        self.not_f32 = None
        self.delta = None

    def __call__(self, *args, **kwargs):
        fn = self.make_step(*args, **kwargs)

        def step(gp, dp, g_opt, d_opt, z, real):
            i = self.calls
            self.calls += 1
            if i == 0:
                self.p0 = _copy((gp, dp))
            with jax.profiler.TraceAnnotation("bench.train.step_call"):
                out = fn(gp, dp, g_opt, d_opt, z, real)
            if i == 0:
                self.m1 = jax.device_get(_leaves((out[2].m, out[3].m)))
                state = (out[0], out[1], out[2].m, out[3].m, out[2].v, out[3].v)
                self.not_f32 = sum(x.dtype != jnp.float32 for x in jax.tree.leaves(state))
            elif i == REF_STEPS - 1:
                self.delta = _delta_norms(self.p0, (out[0], out[1]))
                self.p0 = None
            return out

        return step


def run_program(ctx) -> dict:
    system.import_program()
    from repro.train import trainer as T

    p = ctx.params
    warmup = p["warmup_steps"]
    if warmup < REF_STEPS:
        raise ValueError(f"warmup_steps must be >= {REF_STEPS}")
    pc = system.gan_config(ctx.cfg)
    probe = StepProbe(T.make_gan_step)
    st = {"losses": [], "t_start": None, "t_end": None, "steps": 0, "stamps": []}

    def on_step(s: int, m: dict) -> None:
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.train.on_step"):
            if st["t_start"] is not None:
                st["stamps"].append(now)
            if s <= REF_STEPS:
                st["losses"].append((m["g_loss"], m["d_loss"]))
            if s == warmup:
                ctx.tracer.start()
                st["t_start"] = time.perf_counter()
                ctx.tracer.window_begin()
            elif st["t_start"] is not None and now - st["t_start"] >= ctx.seconds:
                ctx.tracer.window_end()
                st["t_end"], st["steps"] = now, s - warmup
                ctx.tracer.stop()
                raise WindowClosed

    T.make_gan_step = probe
    try:
        T.train_gan(pc, steps=10**9, batch=p["batch"], seed=ctx.seed, log_every=1,
                    handle_signals=False, hooks=T.TrainHooks(on_step=on_step))
    except WindowClosed:
        pass
    finally:
        T.make_gan_step = probe.make_step
    peak = memory_peak_bytes()
    out = {"losses": st["losses"], "m1": probe.m1, "state_not_f32": float(probe.not_f32),
           "delta": jax.device_get(probe.delta), "steps": st["steps"],
           "step_s": np.diff([st["t_start"]] + st["stamps"]).tolist(),
           "setup_s": st["t_start"] - ctx.t_process, "window_s": st["t_end"] - st["t_start"],
           "memory_peak_bytes": peak}
    del probe
    gc.collect()
    return out


def reference_run(cfg: dict, seed: int, batch: int, *, precision=R.HIGHEST,
                  dtype=jnp.float32, rows=None) -> dict:
    """The reference's first three steps: losses, the first gradient (on the
    host, by leaf) and the per-leaf norms of the parameters' change."""
    step = jax.jit(lambda s, z, r: R.train_step(s, z, r, cfg, precision=precision,
                                                dtype=dtype, rows=rows))
    state = jax.jit(lambda k: R.init_state(k, cfg))(jax.random.PRNGKey(seed))
    p0 = (state["gp"], state["dp"])
    losses, grads = [], None
    for s in range(REF_STEPS):
        z = R.latent_batch(seed, s, batch, cfg["z_dim"])
        real = R.image_batch(seed, s, batch, cfg["img_hw"])
        state, gl, dl, gg, dg = step(state, z, real)
        losses.append((float(gl), float(dl)))
        if s == 0:
            grads = jax.device_get(_leaves((gg, dg)))
    delta = jax.device_get(_delta_norms(p0, (state["gp"], state["dp"])))
    return {"losses": losses, "grads": grads, "delta": delta}


NETS = ("[0]", "[1]")  # generator, discriminator: the first key of a leaf's path


def _net_norm_gap(prog: dict, ref: dict, net: str) -> float:
    norm = lambda t: sum(float(v) ** 2 for k, v in t.items() if k.startswith(net)) ** 0.5
    return abs(norm(prog) - norm(ref)) / norm(ref)


def _net_rel_diff(prog: dict, ref: dict, net: str) -> float:
    """||prog - ref|| / ||ref|| over every leaf of one net, in f64."""
    keys = [k for k in ref if k.startswith(net)]
    sq = lambda xs: sum(float(np.sum(np.square(np.asarray(x, np.float64)))) for x in xs)
    return (sq(np.asarray(prog[k]) - np.asarray(ref[k]) for k in keys) / sq(ref[k] for k in keys)) ** 0.5


def leaf_gaps(prog: dict, ref: dict, keep: set) -> dict:
    """Per leaf in ``keep``: the gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median kept leaf's of its net (the first key of the path)."""
    out = {}
    for net in NETS:
        keys = [k for k in ref if k.startswith(net) and k in keep]
        med = statistics.median(float(ref[k]) for k in keys)
        for k in keys:
            out[k] = abs(float(prog[k]) - float(ref[k])) / max(float(ref[k]), med)
    return out


def moved_leaves(ref_grads: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's of its net: the others move under Adam by round-off."""
    keep = set()
    for net in NETS:
        keys = [k for k in ref_grads if k.startswith(net)]
        med = statistics.median(float(ref_grads[k]) for k in keys)
        keep |= {k for k in keys if float(ref_grads[k]) >= 1e-3 * med}
    return keep


def compare(prog: dict, ref: dict, b1: float) -> tuple[dict, dict]:
    """The numbers compared, and notes on them.  Leaves count where the
    reference's first gradient is at least a thousandth of the median
    leaf's (``moved_leaves``).

    ``grad_gap`` and ``grad_med_gap``: the worst and the median leaf of the
    first gradient's norm, the program's read from its first moments as
    m1 / (1 - b1); ``grad_norm_gap``: the worse net's relative gap of that
    gradient's global norm; ``grad_diff``: the worse net's relative L2
    distance of that gradient from the reference's, element by element.  ``change_gap``: the worst leaf of the parameters' change
    over three steps.  The losses are noted, not compared (PERF.md: step 1's
    gap has no lower-precision reading three times its own, and Adam's
    sign-like first update makes the later ones spread with the seed)."""
    grads = {k: np.asarray(v, np.float64) / (1 - b1) for k, v in prog["m1"].items()}
    g_norm = {k: float(np.linalg.norm(v)) for k, v in grads.items()}
    r_norm = {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in ref["grads"].items()}
    keep = moved_leaves(r_norm)
    g = leaf_gaps(g_norm, r_norm, keep)
    c = leaf_gaps(prog["delta"], ref["delta"], keep)
    nums = {"grad_gap": max(g.values()), "grad_med_gap": statistics.median(g.values()),
            "grad_norm_gap": max(_net_norm_gap(g_norm, r_norm, net) for net in NETS),
            "grad_diff": max(_net_rel_diff(grads, ref["grads"], net) for net in NETS),
            "change_gap": max(c.values())}
    losses = [abs(a - b) for pa, ra in zip(prog["losses"], ref["losses"]) for a, b in zip(pa, ra)]
    notes = {"loss_gaps": losses, "grad_leaf": max(g, key=g.get), "change_leaf": max(c, key=c.get),
             "change_med_gap": statistics.median(c.values()), "grad_gaps": g, "change_gaps": c}
    return nums, notes


def run(ctx) -> dict:
    prog = run_program(ctx)
    ref = reference_run(ctx.cfg, ctx.seed, ctx.params["batch"])
    nums, notes = compare(prog, ref, ctx.cfg["optimizer"]["b1"])
    nums["state_not_f32"] = prog["state_not_f32"]
    step_s = prog["step_s"]
    notes["step_s_quartiles"] = statistics.quantiles(step_s, n=4) if len(step_s) > 1 else step_s
    notes["step_s_max"] = max(step_s)
    checks = {k: (v, ctx.limits[k]) for k, v in nums.items()}
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    images = prog["steps"] * ctx.params["batch"]
    passes = F.train_step_passes(ctx.cfg, ctx.params["batch"])
    return {
        "correct": correct, "attempted": prog["steps"], "failed": 0,
        "checks": checks, "memory_peak_bytes": prog["memory_peak_bytes"],
        "setup_s": prog["setup_s"], "window_s": prog["window_s"],
        "e2e": {"setup_s": prog["setup_s"],
                "train_images_per_s": images / prog["window_s"]},
        "work": {"model_flops": prog["steps"] * F.total_flops(passes),
                 "engine_passes": [dict(q, flops=q["flops"] * prog["steps"],
                                        bytes=q["bytes"] * prog["steps"]) for q in passes]},
        "trace": ctx.tracer.reduce() if ctx.trace else None,
        "notes": notes,
    }
