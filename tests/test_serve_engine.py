"""Continuous-batching engine: slot reuse, per-slot positions, and
equivalence with straight-line prefill+decode — plus the GAN engine's FIFO
request queue (admit into slot rows, one shared bucketed generate per step)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import data as D
from repro.configs import smoke_config
from repro.models import lm
from repro.serve.engine import GanRequest, GanServeEngine, Request, ServeEngine


def make_engine(slots=2):
    cfg = smoke_config("llama3-8b")
    params = lm.lm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    return ServeEngine(params, cfg, slots=slots, max_len=64, prompt_len=8), params, cfg


def test_engine_completes_more_requests_than_slots():
    eng, _, cfg = make_engine(slots=2)
    reqs = [
        Request(rid=i, tokens=list(range(1, 8)), max_new=4) for i in range(5)
    ]
    done = eng.run(reqs)
    assert len(done) == 5
    assert all(len(r.out) == 4 for r in done)
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)


def test_engine_matches_straightline_decode():
    """The engine's greedy output must equal plain prefill+decode."""
    eng, params, cfg = make_engine(slots=1)
    prompt = list(range(1, 8))
    done = eng.run([Request(rid=0, tokens=prompt, max_new=3)])
    got = done[0].out

    toks = jnp.asarray([(prompt + [0] * 8)[:8]], jnp.int32)
    logits, cache = lm.prefill(params, cfg, {"tokens": toks}, q_chunk=64, max_len=64)
    want = [int(jnp.argmax(logits[0]))]
    cur, pos = want[0], 7
    for _ in range(2):
        lg, cache = lm.decode_step(
            params, cfg, cache, jnp.asarray([[cur]], jnp.int32), jnp.int32(pos)
        )
        cur = int(jnp.argmax(lg[0]))
        want.append(cur)
        pos += 1
    # engine emits argmax-from-prefill as its first token too
    assert got[: len(want)] == want[: len(got)]


# -------------------------------------------------------- GAN request queue
def assert_bucket_close(out, want):
    """A bucket-padded batched generate vs the unbatched one: XLA's f32
    reduction order depends on the batch bucket, so the images agree to
    rounding (largest difference seen: 1.2e-10), not bitwise."""
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)


def _gan_engine(batch=4):
    from repro.configs.gan_zoo import tiny_dcgan
    from repro.models import gan as G

    cfg = tiny_dcgan("ref")
    p_raw = G.generator_init(jax.random.PRNGKey(0), cfg)
    return GanServeEngine(p_raw, cfg, batch=batch), p_raw, cfg


def test_gan_queue_coalesces_small_requests():
    """FIFO admission packs bursty small requests into shared slot rows:
    sizes [1, 1, 2, 3] on a 4-row pool serve in two steps (1+1+2, then 3)
    instead of four separate padded generates, and each request's rows are
    exact vs the direct generator."""
    from repro.models import gan as G

    eng, p_raw, cfg = _gan_engine(batch=4)
    zs = [
        jax.random.normal(jax.random.PRNGKey(i + 1), (b, cfg.z_dim))
        for i, b in enumerate([1, 1, 2, 3])
    ]
    reqs = [GanRequest(rid=i, z=z) for i, z in enumerate(zs)]
    assert eng.try_admit(reqs[0]) and eng.try_admit(reqs[1]) and eng.try_admit(reqs[2])
    assert not eng.try_admit(reqs[3])  # pool full: 1+1+2 rows used
    done = eng.step()
    assert [r.rid for r in done] == [0, 1, 2]
    assert eng.rows_used == 0 and eng.active == []
    assert eng.try_admit(reqs[3])
    done2 = eng.step()
    assert [r.rid for r in done2] == [3]
    # exactly two shared bucket-4 generates ran
    assert eng.bucket_counts == {4: 2}
    assert eng.served == 7
    for r in reqs:
        want, _ = G.generator_apply(p_raw, cfg, r.z, training=False)
        assert_bucket_close(r.out, want)


def test_gan_queue_run_preserves_order_and_outputs():
    from repro.models import gan as G

    eng, p_raw, cfg = _gan_engine(batch=4)
    zs = [
        jax.random.normal(jax.random.PRNGKey(i + 10), (b, cfg.z_dim))
        for i, b in enumerate([3, 1, 2, 4, 1])
    ]
    outs = eng.run(zs)
    assert [o.shape[0] for o in outs] == [3, 1, 2, 4, 1]
    assert eng.served == 11
    for z, o in zip(zs, outs):
        want, _ = G.generator_apply(p_raw, cfg, z, training=False)
        assert_bucket_close(o, want)


def test_gan_queue_rejects_oversized_request():
    eng, _, cfg = _gan_engine(batch=4)
    big = GanRequest(rid=0, z=jnp.zeros((5, cfg.z_dim)))
    with np.testing.assert_raises(ValueError):
        eng.try_admit(big)


def test_gan_queue_deadline_window():
    """Deadline-aware admission: try_admit(deadline_ms=) opens a bounded
    batching window — poll() holds while the window is open, serves when
    the earliest deadline expires, when the row pool fills, or when an
    immediate (no-deadline) request joins the batch."""
    eng, _, cfg = _gan_engine(batch=4)
    z1 = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))
    r = GanRequest(rid=0, z=z1)
    assert eng.try_admit(r, deadline_ms=50.0, now=0.0)
    assert eng.window_open(now=10.0) and eng.poll(now=10.0) == []
    done = eng.poll(now=60.0)  # deadline expired -> serve
    assert [q.rid for q in done] == [0] and r.done
    # the pool filling closes the window before any deadline
    zs = [jax.random.normal(jax.random.PRNGKey(i + 2), (2, cfg.z_dim))
          for i in range(2)]
    assert eng.try_admit(GanRequest(rid=1, z=zs[0]), deadline_ms=1e6, now=0.0)
    assert eng.poll(now=0.0) == []
    assert eng.try_admit(GanRequest(rid=2, z=zs[1]), deadline_ms=1e6, now=0.0)
    assert [q.rid for q in eng.poll(now=0.0)] == [1, 2]  # 4/4 rows
    # a mixed batch honors its most impatient member
    assert eng.try_admit(GanRequest(rid=3, z=z1), deadline_ms=1e6, now=0.0)
    assert eng.try_admit(GanRequest(rid=4, z=z1))  # FIFO default: immediate
    assert [q.rid for q in eng.poll(now=0.0)] == [3, 4]
    # the window state resets after a step
    assert eng.try_admit(GanRequest(rid=5, z=z1), deadline_ms=50.0, now=100.0)
    assert eng.poll(now=120.0) == [] and [q.rid for q in eng.poll(now=151.0)] == [5]


def test_gan_engine_defaults_to_chained_for_pallas_impls():
    """The serve engine upgrades pallas impls to the chained pipeline by
    default (and leaves ref impls bit-exact per-layer); chained=False opts
    out."""
    from repro.configs.gan_zoo import tiny_dcgan
    from repro.models import gan as G

    cfg = tiny_dcgan("pallas_fused_pre")
    p_raw = G.generator_init(jax.random.PRNGKey(0), cfg)
    eng = GanServeEngine(p_raw, cfg, batch=2)
    assert eng.cfg.deconv_impl == "pallas_chained"
    eng_pl = GanServeEngine(p_raw, cfg, batch=2, chained=False)
    assert eng_pl.cfg.deconv_impl == "pallas_fused_pre_prepacked"
    cfg_ref = tiny_dcgan("ref")
    eng_ref = GanServeEngine(p_raw, cfg_ref, batch=2)
    assert eng_ref.cfg.deconv_impl == "prepacked_ref"
