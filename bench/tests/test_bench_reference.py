"""The plain reference against the program at a tiny size on the CPU: the
same init from the seed, the same data, and three training steps of the
packed-weight (chained_ref) program within f32 round-off; the eval-mode
generator from raw weights against the program's ``ref`` impl."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench import system
from bench.tests.rehearse import tiny_config

SEED = 2**31 + 5


def _leaves(tree):
    return dict(jax.tree_util.tree_flatten_with_path(tree)[0])


@pytest.mark.parametrize("name", ["dcgan", "artgan"])
def test_three_steps_match_the_program(name):
    system.import_program()
    from repro import data as D
    from repro.models import gan as G
    from repro.optim import adamw_init
    from repro.train.trainer import StepSettings, make_gan_step

    cfg = tiny_config(R.load_config(name), impl="chained_ref")
    pc = system.gan_config(cfg)
    kg, kd = jax.random.split(jax.random.PRNGKey(SEED))
    with jax.default_matmul_precision("highest"):
        gp, dp = G.generator_init(kg, pc), G.discriminator_init(kd, pc)
        state = R.init_state(jax.random.PRNGKey(SEED), cfg)
        for mine, theirs in ((state["gp"], gp), (state["dp"], dp)):
            a, b = _leaves(mine), _leaves(theirs)
            assert a.keys() == b.keys()
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
        step = make_gan_step(pc, settings=StepSettings(donate=False))
        go, do = adamw_init(gp), adamw_init(dp)
        ref_step = jax.jit(lambda s, z, r: R.train_step(s, z, r, cfg))
        for s in range(3):
            z, real = R.latent_batch(SEED, s, 4, cfg["z_dim"]), R.image_batch(SEED, s, 4, 64)
            np.testing.assert_array_equal(z, D.latent_batch(SEED, s, 4, cfg["z_dim"]))
            np.testing.assert_array_equal(real, D.gan_batch(SEED, s, 4, 64))
            gp, dp, go, do, m = step(gp, dp, go, do, z, real)
            state, gl, dl, _, _ = ref_step(state, z, real)
            assert float(m["g_loss"]) == pytest.approx(float(gl), rel=1e-5)
            assert float(m["d_loss"]) == pytest.approx(float(dl), rel=1e-5)
    for mine, theirs in ((state["gp"], gp), (state["dp"], dp)):
        a, b = _leaves(mine), _leaves(theirs)
        worst = max(float(jnp.max(jnp.abs(a[k] - b[k]))) for k in a)
        assert worst < 2e-3  # Adam moves near-zero gradients by ~lr on round-off


@pytest.mark.parametrize("name", ["dcgan", "artgan"])
def test_eval_generator_matches_the_program(name):
    system.import_program()
    from repro.models import gan as G

    cfg = tiny_config(R.load_config(name), impl="ref")
    pc = system.gan_config(cfg)
    key = jax.random.PRNGKey(SEED)
    raw = R.generator_init(key, cfg, packed=False)
    z = jnp.asarray(np.random.default_rng(0).standard_normal((5, 100)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ours = R.generator_eval_raw(raw, cfg, z)
        theirs = G.generator_apply(G.generator_init(key, pc), pc, z, training=False)[0]
    np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-6 * float(jnp.abs(theirs).max()))


def test_fp8_operands_round_to_three_mantissa_bits():
    x = jnp.asarray([1.0, 1.0625, 1.125, 448.0, -3.3])
    q = R._fp8(x)
    assert float(q[3]) == 448.0 and float(q[0]) == 1.0
    assert float(jnp.max(jnp.abs(q - x) / jnp.abs(x))) <= 2.0 ** -4
