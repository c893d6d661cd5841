"""Futures-style serve API, multi-model residency, the async serving loop,
and per-request SLO accounting.

The deprecated three-method surface (try_admit/poll/step) is exercised in
test_serve_engine.py; here the same core is driven through
``submit -> GanFuture`` and ``AsyncGanServer``, including the equivalence
claim the redesign makes: same admission order, same bucket counts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gan_zoo import ARTGAN, tiny_dcgan
from repro.models import gan as G
from repro.serve import (
    AsyncGanServer,
    GanRequest,
    GanServeEngine,
    GanServeRejected,
    metrics as SM,
)


def _tiny_artgan(deconv_impl: str = "ref") -> "object":
    """ArtGAN shrunk to test scale (16ch stem, 8ch trunk) — a second,
    structurally different resident (K4S2 trunk + trailing K3S1 layer)."""
    last = len(ARTGAN.deconvs) - 1
    return dataclasses.replace(
        ARTGAN,
        stem_ch=16,
        deconvs=tuple(
            dataclasses.replace(
                d, c_in=16 if i == 0 else 8, c_out=8 if i < last else 3
            )
            for i, d in enumerate(ARTGAN.deconvs)
        ),
        deconv_impl=deconv_impl,
        disc_channels=(8, 8, 8, 8),
    )


def assert_bucket_close(out, want):
    """A bucket-padded batched generate vs the unbatched one: XLA's f32
    reduction order depends on the batch bucket, so the images agree to
    rounding (largest difference seen: 1.2e-10), not bitwise."""
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)


def _gan_engine(batch=4):
    cfg = tiny_dcgan("ref")
    p_raw = G.generator_init(jax.random.PRNGKey(0), cfg)
    return GanServeEngine(p_raw, cfg, batch=batch), p_raw, cfg


# ---------------------------------------------------------------- futures
@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_futures_equivalent_to_legacy_drive():
    """submit/result and the deprecated try_admit/step loop are the same
    core: identical admission order (dispatch batches) and bucket counts,
    identical outputs."""
    sizes = [3, 1, 2, 4, 1]
    zs = [
        jax.random.normal(jax.random.PRNGKey(i + 10), (b, 100))
        for i, b in enumerate(sizes)
    ]

    legacy, _, _ = _gan_engine(batch=4)
    reqs = [GanRequest(rid=i, z=z) for i, z in enumerate(zs)]
    pending = list(reqs)
    while pending or legacy.active:
        while pending and legacy.try_admit(pending[0]):
            pending.pop(0)
        legacy.step()

    futures_eng, _, _ = _gan_engine(batch=4)
    futs = [futures_eng.submit(z) for z in zs]
    outs = [f.result(timeout=120) for f in futs]

    assert futures_eng.dispatch_log == legacy.dispatch_log
    assert futures_eng.bucket_counts == legacy.bucket_counts
    assert futures_eng.served == legacy.served == sum(sizes)
    for r, o in zip(reqs, outs):
        np.testing.assert_array_equal(np.asarray(r.out), np.asarray(o))
    assert all(f.done() for f in futs)


def test_future_result_timeout():
    eng, _, cfg = _gan_engine(batch=4)
    # a pending request that can never admit behind a huge window would be
    # a hang; instead: a window that outlives the timeout raises
    z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))
    f = eng.submit(z, deadline_ms=60_000.0)
    with pytest.raises(TimeoutError):
        f.result(timeout=0.05)
    assert not f.done()
    # a later immediate request closes the window; both serve
    f2 = eng.submit(z)
    out = f.result(timeout=120)
    assert out.shape[0] == 1 and f2.done()


# --------------------------------------------- deadline-window edge cases
def test_deadline_already_expired_serves_immediately():
    eng, _, cfg = _gan_engine(batch=4)
    z = jax.random.normal(jax.random.PRNGKey(2), (1, cfg.z_dim))
    eng.submit(z, deadline_ms=0.0, now=100.0)
    assert not eng.window_open(now=100.0)  # window born closed
    done = eng._dispatch(now=100.0)
    assert len(done) == 1 and done[0].done


def test_mixed_deadline_and_immediate_both_orders():
    eng, _, cfg = _gan_engine(batch=4)
    z = jax.random.normal(jax.random.PRNGKey(3), (1, cfg.z_dim))
    # deadline first, immediate second: the immediate member closes it
    eng.submit(z, deadline_ms=1e6, now=0.0)
    assert eng.window_open(now=0.0)
    eng.submit(z, now=0.0)
    assert not eng.window_open(now=0.0)
    assert len(eng._dispatch(now=0.0)) == 2
    # immediate first, deadline second: never opens at all
    eng.submit(z, now=0.0)
    eng.submit(z, deadline_ms=1e6, now=0.0)
    assert not eng.window_open(now=0.0)
    assert len(eng._dispatch(now=0.0)) == 2


def test_pool_full_forces_window_close():
    eng, _, cfg = _gan_engine(batch=4)
    z2 = jax.random.normal(jax.random.PRNGKey(4), (2, cfg.z_dim))
    eng.submit(z2, deadline_ms=1e6, now=0.0)
    assert eng.window_open(now=0.0)
    eng.submit(z2, deadline_ms=1e6, now=0.0)  # 4/4 rows
    assert not eng.window_open(now=0.0)
    assert [r.size for r in eng._dispatch(now=0.0)] == [2, 2]


# ---------------------------------------------------- multi-model residency
def test_multi_model_parity_bit_for_bit():
    """Two archs resident in ONE engine, scheduled from one shared queue,
    must produce byte-identical outputs to two single-model engines."""
    cfg_a, cfg_b = tiny_dcgan("ref"), _tiny_artgan("ref")
    pa = G.generator_init(jax.random.PRNGKey(0), cfg_a)
    pb = G.generator_init(jax.random.PRNGKey(1), cfg_b)

    multi = GanServeEngine(models={"dcgan": (pa, cfg_a), "artgan": (pb, cfg_b)},
                           batch=4)
    single_a = GanServeEngine(pa, cfg_a, batch=4)
    single_b = GanServeEngine(pb, cfg_b, batch=4)

    za = jax.random.normal(jax.random.PRNGKey(5), (2, cfg_a.z_dim))
    zb = jax.random.normal(jax.random.PRNGKey(6), (1, cfg_b.z_dim))
    fa = multi.submit(za, arch="dcgan")
    fb = multi.submit(zb, arch="artgan")
    oa, ob = fa.result(timeout=240), fb.result(timeout=240)
    # one shared dispatch served both archs (two per-arch generates)
    assert multi.dispatch_log == [(fa.request.rid, fb.request.rid)]
    np.testing.assert_array_equal(np.asarray(oa), np.asarray(single_a.generate(za)))
    np.testing.assert_array_equal(np.asarray(ob), np.asarray(single_b.generate(zb)))
    # per-arch bucket accounting stayed separate
    assert multi.archs["dcgan"].bucket_counts == {2: 1}
    assert multi.archs["artgan"].bucket_counts == {1: 1}


def test_multi_model_requires_arch_and_validates_it():
    cfg = tiny_dcgan("ref")
    pa = G.generator_init(jax.random.PRNGKey(0), cfg)
    pb = G.generator_init(jax.random.PRNGKey(1), cfg)
    eng = GanServeEngine(models={"a": (pa, cfg), "b": (pb, cfg)}, batch=4)
    z = jax.random.normal(jax.random.PRNGKey(2), (1, cfg.z_dim))
    with pytest.raises(ValueError):
        eng.submit(z)  # ambiguous on a multi-model engine
    with pytest.raises(KeyError):
        eng.submit(z, arch="nope")


def test_prepack_registry_roundtrip():
    cfg = tiny_dcgan("ref")
    p = G.generator_init(jax.random.PRNGKey(0), cfg)
    G.clear_prepacked_generators()
    entry = G.register_prepacked_generator("tiny", p, cfg)
    assert entry.cfg.deconv_impl == "prepacked_ref"
    assert G.registered_archs() == ("tiny",)
    assert G.get_prepacked_generator("tiny") is entry
    # engine accepts a bare arch-id string resolved through the registry
    eng = GanServeEngine(models={"tiny": "tiny"}, batch=2)
    z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))
    out = eng.submit(z).result(timeout=120)
    want, _ = G.generator_apply(p, cfg, z, training=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    G.clear_prepacked_generators()
    with pytest.raises(KeyError):
        G.get_prepacked_generator("tiny")


# ------------------------------------------------------------- async server
def test_async_server_serves_and_stamps_slo():
    eng, p_raw, cfg = _gan_engine(batch=4)
    z = jax.random.normal(jax.random.PRNGKey(7), (1, cfg.z_dim))
    with AsyncGanServer(eng, max_queue=16, poll_interval_ms=0.5) as srv:
        futs = [srv.submit(z, deadline_ms=5.0) for _ in range(6)]
        outs = [f.result(timeout=240) for f in futs]
    assert all(o.shape == outs[0].shape for o in outs)
    want, _ = G.generator_apply(p_raw, cfg, z, training=False)
    for o in outs:
        assert_bucket_close(o, want)
    for f in futs:
        t = f.request.timing
        assert t is not None
        assert t["e2e_ms"] >= 0 and t["compute_ms"] >= 0
        assert abs(
            t["queue_wait_ms"] + t["batch_wait_ms"] + t["compute_ms"]
            - t["e2e_ms"]
        ) < 1e-6


def test_async_server_backpressure_rejects():
    eng, _, cfg = _gan_engine(batch=2)
    z = jax.random.normal(jax.random.PRNGKey(8), (1, cfg.z_dim))
    srv = AsyncGanServer(eng, max_queue=2, poll_interval_ms=0.5).start()
    try:
        futs = [srv.submit(z, deadline_ms=500.0) for _ in range(12)]
        served = rejected = 0
        for f in futs:
            try:
                f.result(timeout=240)
                served += 1
            except GanServeRejected:
                rejected += 1
    finally:
        srv.stop()
    assert rejected > 0, "bounded queue never pushed back"
    assert served > 0, "backpressure rejected everything"
    assert served + rejected == 12
    assert srv.rejected_count == rejected


def test_async_server_stop_without_drain_rejects_inflight():
    eng, _, cfg = _gan_engine(batch=4)
    z = jax.random.normal(jax.random.PRNGKey(9), (1, cfg.z_dim))
    srv = AsyncGanServer(eng, max_queue=16).start()
    futs = [srv.submit(z, deadline_ms=60_000.0) for _ in range(3)]
    srv.stop(drain=False)
    outcomes = []
    for f in futs:
        try:
            f.result(timeout=10)
            outcomes.append("served")
        except GanServeRejected:
            outcomes.append("rejected")
    assert all(f.done() for f in futs)
    assert "rejected" in outcomes  # at least the still-windowed ones


# ------------------------------------------------------------------ metrics
def test_metrics_percentile_and_summarize():
    assert SM.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert SM.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        SM.percentile([], 50)

    def req(rid, arch, t0, t3, size=1, rejected=False):
        r = GanRequest(rid=rid, z=jnp.zeros((size, 4)), arch=arch)
        r.t_submit, r.t_admit = t0, t0 + 1.0
        r.t_dispatch, r.t_done = t0 + 2.0, t3
        r.done, r.rejected = not rejected, rejected
        return r

    reqs = [req(0, "a", 0.0, 10.0), req(1, "a", 0.0, 20.0),
            req(2, "b", 5.0, 25.0), req(3, "b", 0.0, 0.0, rejected=True)]
    out = SM.summarize(reqs)
    assert out["_all"]["requests"] == 3 and out["_all"]["rejected"] == 1
    assert out["_all"]["span_s"] == 0.025  # (25 - 0) ms
    assert out["a"]["p50_ms"] == 15.0
    assert out["b"]["requests"] == 1 and out["b"]["rejected"] == 1
    # explicit span overrides the inferred one
    assert SM.summarize(reqs, span_s=2.0)["_all"]["throughput_rps"] == 1.5
