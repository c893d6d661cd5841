"""StepSettings: one dataclass for the step-construction knobs of
make_gan_step / train_gan / launch.steps.build_gan_step, with the legacy
kwarg spelling still accepted (mapped + DeprecationWarning)."""
import warnings

import jax.numpy as jnp
import pytest

from repro.configs.gan_zoo import tiny_dcgan
from repro.train import StepSettings, make_gan_step, train_gan
from repro.train.trainer import _merge_legacy


def test_settings_defaults_and_helpers():
    st = StepSettings()
    assert (st.lr, st.b1, st.donate, st.overlap) == (2e-4, 0.5, True, False)
    assert not st.comm
    assert StepSettings(overlap=True).comm
    assert StepSettings(grad_compression="int8").comm
    cfg = tiny_dcgan("ref")
    cfg2 = StepSettings(deconv_impl="prepacked_ref", conv_impl="ref").apply_to_cfg(cfg)
    assert cfg2.deconv_impl == "prepacked_ref" and cfg2.conv_impl == "ref"
    assert StepSettings().apply_to_cfg(cfg) is cfg  # no overrides: untouched


def test_legacy_kwargs_map_and_warn():
    base = StepSettings(lr=1e-3)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        st = _merge_legacy(base, {"lr": 5e-4, "overlap": True}, "somewhere")
    assert st.lr == 5e-4 and st.overlap and st.b1 == 0.5
    # nothing passed: settings come through untouched, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert _merge_legacy(base, {}, "somewhere") is base


def test_make_gan_step_settings_no_warning_legacy_warns():
    cfg = tiny_dcgan("ref")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        make_gan_step(cfg, settings=StepSettings())
    with pytest.warns(DeprecationWarning):
        make_gan_step(cfg, lr=1e-3)


def test_train_gan_settings_matches_legacy_kwargs():
    """The settings spelling and the legacy kwargs build the same step:
    identical metrics from identical seeds."""
    cfg = tiny_dcgan("ref")
    kw = dict(steps=2, batch=2, seed=0, log_every=1, dtype=jnp.float32)
    out_new = train_gan(cfg, settings=StepSettings(deconv_impl="ref"), **kw)
    with pytest.warns(DeprecationWarning):
        out_old = train_gan(cfg, deconv_impl="ref", **kw)
    assert out_new["metrics"] == out_old["metrics"]


@pytest.mark.parametrize("impls", [("pallas_chained", "lax"), ("ref", "pallas_chained")])
def test_meshed_gspmd_step_refuses_mosaic_kernels(impls):
    """GSPMD cannot partition a Mosaic kernel: the meshed step with a
    compiled Pallas impl raises at build time and names the shard_map step,
    which builds."""
    from repro.compat import make_mesh

    cfg = tiny_dcgan(*impls)
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="overlap=True"):
        make_gan_step(cfg, settings=StepSettings(mesh=mesh, batch=4))
    assert make_gan_step(cfg, settings=StepSettings(mesh=mesh, batch=4, overlap=True))
    # the interpret twins run no Mosaic kernel: GSPMD may partition them
    cfg_i = tiny_dcgan(*(f"{i}_interpret" if i.startswith("pallas") else i for i in impls))
    assert make_gan_step(cfg_i, settings=StepSettings(mesh=mesh, batch=4))
