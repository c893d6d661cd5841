"""Deterministic synthetic data pipeline.

Every batch is a pure function of (seed, step), so checkpoint-restart
resumes the exact data stream with no pipeline state to save — the
fault-tolerance property the trainer relies on.  Two paths draw the same
stream:

* ``latent_batch`` / ``gan_batch`` (and the LM helpers) run eagerly, one
  dispatch per op; the benchmark's plain reference matches them bit for bit.
* ``gan_inputs`` is one compiled program that returns a train step's
  ``(z, real)`` from the same bodies; ``train_gan`` calls it once a step.
  XLA fuses its ops, so it agrees with the eager path to about 1e-6, not
  bit for bit.

Either lands on the default device; a mesh step's shardings place it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _fold(key: jax.Array, step, tag: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, step), tag)


def _key(seed: int, step: int, tag: int) -> jax.Array:
    return _fold(jax.random.PRNGKey(seed), step, tag)


def _latents(key: jax.Array, batch: int, z_dim: int) -> jax.Array:
    return jax.random.normal(key, (batch, z_dim), jnp.float32)


def _images(key: jax.Array, batch: int, hw: int, ch: int) -> jax.Array:
    """Smooth synthetic 'real' images in [-1, 1]: random low-frequency
    Fourier modes — cheap, deterministic, non-degenerate statistics."""
    k1, k2, k3 = jax.random.split(key, 3)
    n_modes = 6
    freq = jax.random.uniform(k1, (batch, n_modes, 2, ch), minval=0.5, maxval=3.0)
    phase = jax.random.uniform(k2, (batch, n_modes, 2, ch), maxval=2 * jnp.pi)
    amp = jax.random.normal(k3, (batch, n_modes, ch)) / n_modes
    yy = jnp.linspace(0, 2 * jnp.pi, hw)
    img = jnp.zeros((batch, hw, hw, ch))
    for m in range(n_modes):
        wave_y = jnp.sin(freq[:, m, 0, None, :] * yy[None, :, None] + phase[:, m, 0, None, :])
        wave_x = jnp.sin(freq[:, m, 1, None, :] * yy[None, :, None] + phase[:, m, 1, None, :])
        img = img + amp[:, m, None, None, :] * wave_y[:, :, None, :] * wave_x[:, None, :, :]
    return jnp.tanh(img)


def latent_batch(seed: int, step: int, batch: int, z_dim: int) -> jax.Array:
    return _latents(_key(seed, step, 0), batch, z_dim)


def gan_batch(seed: int, step: int, batch: int, hw: int, ch: int = 3) -> jax.Array:
    return _images(_key(seed, step, 1), batch, hw, ch)


@partial(jax.jit, static_argnames=("batch", "z_dim", "hw", "ch"))
def gan_inputs(
    base_key: jax.Array, step, *, batch: int, z_dim: int, hw: int, ch: int = 3
) -> tuple[jax.Array, jax.Array]:
    """A train step's ``(z, real)`` as one program: ``latent_batch`` and
    ``gan_batch`` of ``step`` for ``base_key = PRNGKey(seed)`` (to ~1e-6).

    ``step`` is traced, so one program serves every step; the key is passed
    rather than the seed so that seeds past int32 work.  With ``z_dim == 0``
    (image-to-image) z is the image batch of step ``1_000_000 + step``."""
    if z_dim:
        z = _latents(_fold(base_key, step, 0), batch, z_dim)
    else:
        z = _images(_fold(base_key, step + 1_000_000, 1), batch, hw, ch)
    return z, _images(_fold(base_key, step, 1), batch, hw, ch)


def lm_batch(
    seed: int, step: int, batch: int, seq: int, vocab: int, *, dtype=jnp.int32
) -> dict[str, jax.Array]:
    """Synthetic token stream with Zipf-like marginal + shifted labels."""
    k = _key(seed, step, 2)
    # Zipf via inverse-CDF on a power law (cheap approximation)
    u = jax.random.uniform(k, (batch, seq + 1), minval=1e-6, maxval=1.0)
    ranks = jnp.clip((u ** (-1 / 1.1) - 1).astype(dtype), 0, vocab - 1)
    tokens = ranks[:, :-1]
    labels = ranks[:, 1:]
    return {"tokens": tokens, "labels": labels}


def embed_batch(seed: int, step: int, batch: int, seq: int, d: int) -> jax.Array:
    """Stub modality frontend: precomputed frame/patch embeddings."""
    return 0.02 * jax.random.normal(_key(seed, step, 3), (batch, seq, d), jnp.float32)
