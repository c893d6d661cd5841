"""``repro.obs``: spans and the compile counter, switched by the profiler.

Outside a profiler session nothing is recorded.  Inside one, a span is an
event on a ``/host:`` plane of the trace with its metadata as stats, and an
entry of ``snapshot()``; a compile counts under the innermost span open on
the compiling thread.  The train loop and the serve loop open their spans
once per step and per dispatch.
"""
import glob
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs.gan_zoo import tiny_dcgan
from repro.models import gan as G


@pytest.fixture(autouse=True)
def clean():
    obs.reset()
    yield
    obs.reset()


def _fresh_compile(n: int):
    """Compile and run a program no other call has compiled."""
    return jax.jit(lambda a: a * 2 + n)(np.ones(n, np.float32)).block_until_ready()


def _host_events(directory, name: str) -> list:
    """(plane name, stats) of every event called ``name`` in the trace."""
    pd = ProfileData.from_file(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)[0])
    return [(plane.name, dict(e.stats)) for plane in pd.planes for line in plane.lines
            for e in line.events if e.name == name]


def test_nothing_recorded_outside_a_profiler_session():
    assert obs.span("gan.test") is obs.span("gan.other", rid=1)  # the shared no-op
    with obs.span("gan.test", rid=1):
        _fresh_compile(3)
    assert obs.snapshot() == {"spans": {}, "compiles": {}}


def test_span_lands_in_the_trace_with_its_metadata(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("gan.test", rid=7, rows=32):
            pass
        with obs.span("gan.test", rid=8, rows=16):
            pass
    events = _host_events(tmp_path, "gan.test")
    assert len(events) == 2
    assert all(plane.startswith("/host:") for plane, _ in events)
    assert sorted((st["rid"], st["rows"]) for _, st in events) == [(7, 32), (8, 16)]
    spans = obs.snapshot()["spans"]
    assert spans["gan.test"]["count"] == 2 and spans["gan.test"]["total_s"] >= 0
    # the session has ended: nothing more is recorded
    with obs.span("gan.test"):
        pass
    assert obs.snapshot()["spans"]["gan.test"]["count"] == 2


def test_compiles_count_under_the_innermost_span(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("gan.outer"):
            with obs.span("gan.inner"):
                _fresh_compile(5)
        _fresh_compile(6)
    compiles = obs.snapshot()["compiles"]
    assert compiles["gan.inner"]["count"] >= 1
    assert compiles[obs.OUTSIDE]["count"] >= 1
    assert "gan.outer" not in compiles
    obs.reset()
    assert obs.snapshot() == {"spans": {}, "compiles": {}}


def test_threads_attribute_to_their_own_span(tmp_path):
    both_open = threading.Barrier(2)
    errors = []

    def work(name: str, n: int):
        try:
            with obs.span(name):
                both_open.wait(timeout=30)
                _fresh_compile(n)
                both_open.wait(timeout=30)
        except Exception as e:  # surfaced below
            errors.append(e)

    with jax.profiler.trace(str(tmp_path)):
        threads = [threading.Thread(target=work, args=(f"gan.thread{i}", 10 + i)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert not errors and not any(t.is_alive() for t in threads)
    snap = obs.snapshot()
    for i in range(2):
        assert snap["spans"][f"gan.thread{i}"]["count"] == 1
        assert snap["compiles"][f"gan.thread{i}"]["count"] >= 1
    assert obs.OUTSIDE not in snap["compiles"]


def test_concurrent_spans_lose_no_update(tmp_path):
    """More threads than cores, switching often, each closing many spans of
    one shared name: the aggregate counts every one."""
    workers, each = 2 * (os.cpu_count() or 1) + 2, 200
    interval = sys.getswitchinterval()

    def work():
        for _ in range(each):
            with obs.span("gan.shared"):
                pass

    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work) for _ in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert obs.snapshot()["spans"]["gan.shared"]["count"] == workers * each


def test_train_gan_opens_data_step_and_sync_once_a_step(tmp_path):
    from repro.train.trainer import train_gan

    steps = 3
    with jax.profiler.trace(str(tmp_path)):
        train_gan(tiny_dcgan("ref"), steps=steps, batch=2, log_every=1, handle_signals=False)
    spans = obs.snapshot()["spans"]
    for name in ("gan.train.data", "gan.train.step", "gan.train.sync"):
        assert spans[name]["count"] == steps, name
        assert sorted(st["step"] for _, st in _host_events(tmp_path, name)) == list(range(steps))
    # the first step compiles, under its step span
    assert obs.snapshot()["compiles"]["gan.train.step"]["count"] >= 1


def test_serve_dispatch_spans_carry_their_dispatch_index(tmp_path):
    from repro.serve import AsyncGanServer, GanServeEngine

    cfg = tiny_dcgan("ref")
    eng = GanServeEngine(G.generator_init(jax.random.PRNGKey(0), cfg), cfg, batch=4)
    rng = np.random.default_rng(0)
    with jax.profiler.trace(str(tmp_path)):
        with AsyncGanServer(eng) as server:
            futs = [server.submit(rng.standard_normal((size, cfg.z_dim), np.float32),
                                  deadline_ms=1.0) for size in (3, 1, 4, 2, 2)]
            for f in futs:
                f.result(timeout=120)
    size = {f.request.rid: f.request.size for f in futs}
    n = len(eng.dispatch_log)
    assert n >= 3
    spans = obs.snapshot()["spans"]
    for name in ("gan.serve.assemble", "gan.serve.generate", "gan.serve.complete"):
        assert spans[name]["count"] == n, name
        stats = [st for _, st in _host_events(tmp_path, name)]
        assert sorted(st["dispatch"] for st in stats) == list(range(n)), name
        for st in stats:
            rows = sum(size[r] for r in eng.dispatch_log[st["dispatch"]])
            assert st["rows"] == rows and st["bucket"] == eng.bucket_for(rows)
    assert spans["gan.serve.poll"]["count"] >= 1


@pytest.mark.parametrize("impl", ["ref", "pallas_chained_interpret"])
def test_layers_are_named_scopes_in_the_hlo(impl):
    cfg = tiny_dcgan(impl, "lax" if impl == "ref" else impl)
    key = jax.random.PRNGKey(0)
    gp, dp = G.generator_init(key, cfg), G.discriminator_init(key, cfg)

    def f(gp, dp, z):
        img, _ = G.generator_apply(gp, cfg, z)
        return G.discriminator_apply(dp, cfg, img)[0]

    text = jax.jit(f).lower(gp, dp, jnp.ones((2, cfg.z_dim))).compile().as_text()
    for i in range(len(cfg.deconvs)):
        assert f"/g.deconv{i}/" in text
    for i in range(len(G.disc_channels(cfg))):
        assert f"/d.conv{i}/" in text
