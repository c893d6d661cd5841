"""``data.gan_inputs``: a train step's ``(z, real)`` as one compiled program.

It draws the stream of the eager ``latent_batch`` / ``gan_batch`` from the
key ``PRNGKey(seed)``, to about 1e-6 (XLA fuses the ops), is a pure function
of ``(seed, step)``, and traces once per shape: ``step`` and the key are
arguments, so neither a new step nor a new seed compiles again.
"""
import jax
import numpy as np
import pytest

from repro import data as D
from repro.data import synthetic

B, HW = 4, 16


@pytest.mark.parametrize("z_dim", [100, 0])
@pytest.mark.parametrize("step", [0, 3, 1_000_007])
@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_gan_inputs_agrees_with_the_eager_batches(seed, step, z_dim):
    z, real = D.gan_inputs(jax.random.PRNGKey(seed), step, batch=B, z_dim=z_dim, hw=HW)
    z_eager = (D.latent_batch(seed, step, B, z_dim) if z_dim
               else D.gan_batch(seed, 1_000_000 + step, B, HW))
    assert z.shape == z_eager.shape and z.dtype == z_eager.dtype
    assert real.shape == (B, HW, HW, 3) and real.dtype == np.float32
    np.testing.assert_allclose(z, z_eager, rtol=0, atol=1e-5)
    np.testing.assert_allclose(real, D.gan_batch(seed, step, B, HW), rtol=0, atol=1e-5)


def test_gan_inputs_is_a_pure_function_of_seed_and_step():
    draw = lambda seed, step: D.gan_inputs(jax.random.PRNGKey(seed), step,  # noqa: E731
                                           batch=B, z_dim=8, hw=HW)
    for a, b in zip(draw(7, 5), draw(7, 5)):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(draw(7, 5)[1], draw(7, 6)[1])
    assert not np.array_equal(draw(7, 5)[0], draw(8, 5)[0])


def test_gan_inputs_traces_once_per_shape(monkeypatch):
    traces = []
    latents = synthetic._latents
    monkeypatch.setattr(synthetic, "_latents",
                        lambda *a: traces.append(a[1:]) or latents(*a))
    for seed in (0, 2**31 + 5):
        key = jax.random.PRNGKey(seed)
        for step in range(5):
            # a shape no other test draws, so the first call here traces
            D.gan_inputs(key, step, batch=3, z_dim=5, hw=8)
    assert traces == [(3, 5)]
