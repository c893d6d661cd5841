from .synthetic import gan_batch, gan_inputs, lm_batch, latent_batch, embed_batch
