"""The serving driver rehearsed on the CPU at a tiny size with interpret-mode
engines, open loop and closed loop: sound runs are correct, nothing compiles
inside the window where every request asks for the same number of images,
and a run whose served images are altered where they are produced, or
stored in bfloat16, is not correct."""
import json

import pytest

from bench import run as RUN
from bench.tests.rehearse import CpuContext

SEED = 2**31 + 202


def rehearse(cell, **kw):
    ctx = CpuContext(cell, SEED, 1.0, **kw)
    return ctx, RUN.load_module("drivers", "serve").run(ctx)


@pytest.mark.parametrize("cell,e2e", [
    ("dcgan.serve.poisson", "serve_p95_ms"),
    ("artgan.serve.offline", "serve_images_per_s"),
])
def test_sound_run_is_correct(cell, e2e):
    ctx, out = rehearse(cell, params={"rate_rps": 40.0})
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 16
    assert out["checks"]["images_not_f32"][0] == 0
    assert out["e2e"][e2e] > 0
    if ctx.params["size_min"] == ctx.params["size_max"]:
        # a dispatch that mixes sizes concatenates shapes no warm-up covers
        assert out["notes"]["compiles_in_window"] == 0
    bench = json.loads((RUN.ROOT / "BENCHMARK.json").read_text())
    if cell not in {w["name"] for w in bench["workloads"]}:
        return
    line = RUN.assemble(bench, ctx, out, {"platform": "cpu", "kind": "cpu", "count": 1}, {})
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks" and len(line) == 6
    assert set(line["metrics"]) == {e2e, "setup_s"}
    assert line["metrics"][e2e]["value"] > 0


def test_altered_answer_is_incorrect(monkeypatch):
    from repro.models import gan as G

    apply = G.generator_apply

    def altered(*a, **kw):
        img, stats = apply(*a, **kw)
        return img.at[0].add(0.5), stats

    monkeypatch.setattr(G, "generator_apply", altered)
    _, out = rehearse("dcgan.serve.poisson", params={"rate_rps": 40.0})
    assert not out["correct"]
    assert out["checks"]["img_rel_max"][0] > out["checks"]["img_rel_max"][1]


def test_images_served_in_bfloat16_are_incorrect(monkeypatch):
    import jax.numpy as jnp
    from repro.models import gan as G

    apply = G.generator_apply

    def low(*a, **kw):
        img, stats = apply(*a, **kw)
        return img.astype(jnp.bfloat16), stats

    monkeypatch.setattr(G, "generator_apply", low)
    _, out = rehearse("artgan.serve.offline")
    assert not out["correct"]
    assert out["checks"]["images_not_f32"][0] > 0
