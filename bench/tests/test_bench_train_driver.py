"""The training driver rehearsed on the CPU at a tiny size with interpret-mode
engines: a sound run is correct, and a run with the program's step broken
underneath (state left unchanged; half of the batch left out) is not."""
import dataclasses

import pytest

from bench import run as RUN
from bench.tests.rehearse import CpuContext

CELL = "dcgan.train.b128"
SEED = 2**31 + 101


def rehearse(**kw):
    ctx = CpuContext(CELL, SEED, 0.5, params={"batch": 4}, **kw)
    return ctx, RUN.load_module("drivers", "train").run(ctx)


@pytest.fixture(scope="module")
def sound():
    return rehearse()


def test_sound_run_is_correct_and_line_has_the_keys(sound):
    ctx, out = sound
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["checks"]) == {"grad_gap", "grad_med_gap", "grad_norm_gap", "grad_diff", "change_gap",
                                  "state_not_f32"}
    assert out["checks"]["state_not_f32"][0] == 0
    bench, *_ = RUN.load_cell(CELL)
    line = RUN.assemble(bench, ctx, out, {"platform": "cpu", "kind": "cpu", "count": 1}, {})
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks" and len(line) == 6
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["metrics"]["train_images_per_s"]["unit"] == "images/s"


def test_per_layer_readers_from_a_trace(sound):
    ctx, out = sound
    ctx.trace = True
    bench, *_ = RUN.load_cell(CELL)
    out = dict(out, trace={"busy_s": 0.9, "window_s": 1.0, "kernel_s": 0.5, "chips": 1,
                           "device_ops": [["k", 0.5]], "idle_gaps": [["bench.x", 0.1]]})
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    line = RUN.assemble(bench, ctx, out, {"platform": "cpu", "kind": "cpu", "count": 1}, peak)
    m = line["metrics"]
    assert set(m) == {"mfu.train", "engine_roofline.train", "device_idle.train"}
    assert m["device_idle.train"]["value"] == pytest.approx(10.0)
    assert m["engine_roofline.train"]["bound"] in ("flops", "bytes")
    assert line["device"]["busy_s"] == 0.9 and "breakdown" in line


def _faulty(monkeypatch, wrap):
    from repro.train import trainer as T

    make = T.make_gan_step

    def make_faulty(cfg, *a, settings=None, **kw):
        settings = dataclasses.replace(settings or T.StepSettings(), donate=False)
        return wrap(make(cfg, *a, settings=settings, **kw))

    monkeypatch.setattr(T, "make_gan_step", make_faulty)


def test_state_left_unchanged_is_incorrect(monkeypatch, sound):
    def wrap(fn):
        def step(gp, dp, go, do, z, real):
            return (gp, dp, go, do, fn(gp, dp, go, do, z, real)[-1])
        return step

    _faulty(monkeypatch, wrap)
    _, out = rehearse()
    assert not out["correct"]
    assert out["checks"]["change_gap"][0] > out["checks"]["change_gap"][1]


def test_half_batch_is_incorrect(monkeypatch, sound):
    def wrap(fn):
        def step(gp, dp, go, do, z, real):
            n = z.shape[0] // 2
            return fn(gp, dp, go, do, z[:n], real[:n])
        return step

    _faulty(monkeypatch, wrap)
    _, out = rehearse()
    assert not out["correct"]


def test_moments_stored_in_bfloat16_are_incorrect(monkeypatch, sound):
    import jax
    import jax.numpy as jnp

    def wrap(fn):
        def step(gp, dp, go, do, z, real):
            gp, dp, go, do, m = fn(gp, dp, go, do, z, real)
            low = lambda t: jax.tree.map(lambda x: x.astype(jnp.bfloat16), t)  # noqa: E731
            return gp, dp, go._replace(m=low(go.m), v=low(go.v)), do, m
        return step

    _faulty(monkeypatch, wrap)
    _, out = rehearse()
    assert not out["correct"]
    assert out["checks"]["state_not_f32"][0] > 0
