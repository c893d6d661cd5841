"""Bucketed overlapped gradient reduction (parallel.overlap).

Covers: bucket plans partition the leaf set exactly once (including the
multi-bucket regime), bucketed reduction is bit-identical to per-leaf pmean,
the ZeRO block slice/ungather round-trips under every spec shape we shard
with, and the overlapped step's results are invariant to the bucketing and
match single-device training.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_plan_buckets_covers_every_leaf_exactly_once():
    """Every leaf lands in exactly one bucket at any bucket size; small
    targets produce multiple buckets filled in reverse (backward-completion)
    order; element accounting matches the tree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.configs.gan_zoo import tiny_dcgan
    from repro.models import gan as G
    from repro.parallel.overlap import plan_buckets

    cfg = tiny_dcgan("prepacked_ref")
    gp = jax.eval_shape(lambda k: G.generator_init(k, cfg),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = compat.tree_leaves(gp)
    total = sum(int(np.prod(l.shape)) if l.shape else 1 for l in leaves)

    one = plan_buckets(gp)  # default 4 MiB: tiny config fits in one bucket
    assert one.covers_exactly_once()
    assert one.n_leaves == len(leaves)
    assert sum(one.numels) == total

    many = plan_buckets(gp, bucket_bytes=4096)
    assert many.covers_exactly_once()
    assert len(many.buckets) > 1
    assert sum(many.numels) == total
    # reverse fill: the first bucket holds the *last* flatten-order leaves
    assert many.buckets[0][0] == len(leaves) - 1

    # scalar leaves count as one element, not zero
    scal = plan_buckets({"a": jax.ShapeDtypeStruct((), jnp.float32)})
    assert scal.covers_exactly_once() and scal.numels == (1,)


def test_bucketed_reduce_matches_per_leaf_pmean():
    """reduce_bucketed (any bucketing) must be bit-identical to per-leaf
    pmean — bucketing changes the collective schedule, never the math."""
    out = run_py(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.parallel.overlap import plan_buckets, reduce_bucketed

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        grads = {
            "a": jnp.asarray(rng.standard_normal((8, 7)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((8, 3, 5)), jnp.float32),
            "c": jnp.asarray(rng.standard_normal((8,)), jnp.float32),
        }
        local = jax.tree.map(
            lambda g: jax.ShapeDtypeStruct((1,) + g.shape[1:], g.dtype), grads)
        for bb in (4 << 20, 32):  # one bucket vs several
            plan = plan_buckets(local, bucket_bytes=bb)
            assert plan.covers_exactly_once()
            if bb == 32:
                assert len(plan.buckets) > 1

            def body(g):
                red, nr = reduce_bucketed(g, plan, ("data",))
                assert nr is None
                want = jax.tree.map(lambda x: jax.lax.pmean(x, "data"), g)
                return red, want

            fn = shard_map(body, mesh=mesh, in_specs=(P("data"),),
                           out_specs=(P("data"), P("data")), check_vma=False)
            red, want = fn(grads)
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)), red, want)
        print("OK")
        """
    )
    assert "OK" in out


def test_block_slice_ungather_roundtrip():
    """_block_of -> _ungather_of is the identity for single-axis, tuple-axis
    and trailing-dim PartitionSpecs on a 4x2 mesh (the shapes gan_param_specs
    actually emits)."""
    out = run_py(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.parallel.overlap import _block_of, _ungather_of

        mesh = make_mesh((4, 2), ("data", "model"))
        cases = [
            (P("data", None), (8, 6)),
            (P(None, "model"), (5, 4)),
            (P(("data", "model"), None), (16, 3)),
            (P(None, ("data",), "model"), (2, 8, 4)),  # packed-ww shape
            (P(None, None), (3, 3)),  # fully replicated: no-op
        ]
        for spec, shape in cases:
            x = jnp.asarray(
                np.random.default_rng(0).standard_normal(shape), jnp.float32)

            def body(x_):
                blk = _block_of(x_, spec, mesh)
                return _ungather_of(blk, spec, mesh)

            fn = shard_map(body, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False)
            np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))
        print("OK")
        """
    )
    assert "OK" in out


def test_overlap_step_bucketing_invariance_and_parity():
    """The overlapped step matches single-device training, and its results
    are invariant to the bucket size (single- vs multi-bucket plans give
    identical params — the schedule changes, the function does not)."""
    out = run_py(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro import data as D
        from repro.compat import make_mesh
        from repro.configs.gan_zoo import tiny_dcgan
        from repro.models import gan as G
        from repro.optim import adamw_init
        from repro.parallel import overlap as OV
        from repro.train.trainer import make_gan_step

        cfg = tiny_dcgan("prepacked_ref")
        B = 8
        kg, kd = jax.random.split(jax.random.PRNGKey(0))
        gp0, dp0 = G.generator_init(kg, cfg), G.discriminator_init(kd, cfg)
        go0, do0 = adamw_init(gp0), adamw_init(dp0)
        cp = lambda t: jax.tree.map(jnp.copy, t)

        step_1 = make_gan_step(cfg)
        g1, d1, go1, do1 = cp(gp0), cp(dp0), cp(go0), cp(do0)
        losses_1 = []
        for s in range(3):
            z = D.latent_batch(0, s, B, cfg.z_dim)
            real = D.gan_batch(0, s, B, cfg.img_hw)
            g1, d1, go1, do1, m = step_1(g1, d1, go1, do1, z, real)
            losses_1.append((float(m["g_loss"]), float(m["d_loss"])))

        mesh = make_mesh((8,), ("data",))
        finals, norms = [], []
        for bb in (OV.DEFAULT_BUCKET_BYTES, 8192):
            fn, meta = OV.build_gan_comm_step(
                cfg, mesh, batch=B, donate=False, bucket_bytes=bb)
            assert meta["g_plan"].covers_exactly_once()
            assert meta["d_plan"].covers_exactly_once()
            if bb == 8192:
                assert len(meta["g_plan"].buckets) > 1, meta["g_plan"]
            gp, dp, go, do = cp(gp0), cp(dp0), cp(go0), cp(do0)
            plan_norms = []
            for s in range(3):
                z = D.latent_batch(0, s, B, cfg.z_dim)
                real = D.gan_batch(0, s, B, cfg.img_hw)
                gp, dp, go, do, m = fn(gp, dp, go, do, z, real)
                gl, dl = losses_1[s]
                assert abs(float(m["g_loss"]) - gl) < 1e-3, (s, bb, float(m["g_loss"]), gl)
                assert abs(float(m["d_loss"]) - dl) < 1e-3, (s, bb, float(m["d_loss"]), dl)
                plan_norms.append([float(m["g_grad_norm"]), float(m["d_grad_norm"])])
            finals.append((gp, dp))
            norms.append(plan_norms)

        check = lambda tol: lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=tol, rtol=tol)
        # parity with the single-device trajectory
        jax.tree.map(check(2e-3), finals[0][0], g1)
        jax.tree.map(check(2e-3), finals[0][1], d1)
        # bucketing invariance.  The bucket plan changes the order of XLA's
        # f32 reductions, so the reduced grads agree to rounding: their
        # global norms (taken before AdamW) to 1e-5 relative.  The params
        # agree only to 2 * steps * lr: AdamW divides each grad by its own
        # magnitude, so a leaf whose exact grad is zero (a conv bias that
        # feeds batchnorm) steps by up to lr along its rounding noise, whose
        # sign the reduction order decides.
        np.testing.assert_allclose(norms[0], norms[1], rtol=1e-5)
        plan_tol = lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2 * 3 * 2e-4, rtol=0)
        jax.tree.map(plan_tol, finals[0][0], finals[1][0])
        jax.tree.map(plan_tol, finals[0][1], finals[1][1])
        print("OK")
        """
    )
    assert "OK" in out
