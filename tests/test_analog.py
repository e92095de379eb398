"""Behavioural tests of the analog VMM emulation (paper Fig. 4 / §II-A)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DIGITAL,
    NOISELESS,
    AnalogConfig,
    NoiseConfig,
    analog_linear_init,
    analog_matmul,
)
from repro.api import apply_linear
from repro.core.hw import BSS2

KEY = jax.random.PRNGKey(42)
NOISELESS_CFG = AnalogConfig(noise=NOISELESS, signed_input="split")


def _mk(in_dim=256, out_dim=64, noise=NOISELESS, seed=0):
    return analog_linear_init(
        jax.random.PRNGKey(seed), in_dim, out_dim, noise=noise
    )


class TestChunkSaturation:
    def test_per_chunk_adc_clips_before_digital_sum(self):
        """Two chunks whose partials cancel must NOT cancel when each chunk
        saturates - the defining property of the faithful mode."""
        k, n = 256, 1
        # chunk 0 drives the membrane far positive, chunk 1 far negative
        w = jnp.concatenate(
            [jnp.full((128, n), 63.0), jnp.full((128, n), -63.0)]
        )
        a = jnp.full((1, k), 31.0)
        gain = jnp.asarray(1.0)  # enormous gain -> guaranteed saturation
        cfg = AnalogConfig(noise=NOISELESS)
        y_faithful = analog_matmul(a, w, gain, None, None, cfg)
        y_fast = analog_matmul(
            a, w, gain, None, None, cfg.replace(mode="analog_fast")
        )
        # faithful: +127 (sat) + -128 (sat) = -1 ; fast: exact cancel = 0
        assert float(y_faithful[0, 0]) == BSS2.adc_max + BSS2.adc_min
        assert float(y_fast[0, 0]) == 0.0

    def test_no_saturation_modes_agree(self):
        a = jnp.round(jax.random.uniform(KEY, (4, 256)) * 31)
        w = jnp.round(jax.random.normal(KEY, (256, 32)) * 10)
        gain = jnp.asarray(0.01)  # small partials, no saturation
        cfg = AnalogConfig(noise=NOISELESS)
        y1 = analog_matmul(a, w, gain, None, None, cfg)
        y2 = analog_matmul(
            a, w, gain, None, None, cfg.replace(mode="analog_fast")
        )
        # per-chunk rounding differs from single rounding by <= 1 LSB/chunk
        assert float(jnp.abs(y1 - y2).max()) <= 2.0


class TestKernelDispatch:
    @pytest.mark.parametrize("mode", ["analog_faithful", "analog_fast"])
    def test_matches_oracle_without_readout_keys(self, mode):
        """A call without readout noise is the kernels' oracle, bit for
        bit: batch dims flatten, K pads to whole chunks."""
        from repro.kernels import ref as R

        k1, k2, k3 = jax.random.split(KEY, 3)
        a = jnp.round(jax.random.uniform(k1, (2, 3, 200)) * 31)
        w = jnp.round(jax.random.normal(k2, (200, 40)) * 20)
        w = w * (1 + 0.02 * jax.random.normal(k3, (200, 40)))
        off = jax.random.normal(k3, (2, 40))
        gain = jnp.full((40,), 0.05)
        cfg = AnalogConfig(mode=mode, noise=NOISELESS)
        got = analog_matmul(a, w, gain, off, None, cfg)
        a2 = jnp.pad(a.reshape(6, 200), ((0, 0), (0, 56)))
        want = R.analog_mvm_ref(a2, jnp.pad(w, ((0, 56), (0, 0))), gain,
                                off, faithful=mode == "analog_faithful")
        assert got.shape == (2, 3, 40)
        np.testing.assert_array_equal(np.asarray(got).reshape(6, 40),
                                      np.asarray(want))


class TestAnalogLinear:
    def test_tracks_digital_within_quant_error(self):
        p = _mk()
        x = jax.random.normal(KEY, (32, 256)) * 0.3
        from repro.core.analog import calibrate

        p = calibrate(p, x)
        y_a = apply_linear(p, x, NOISELESS_CFG)
        y_d = apply_linear(p, x, DIGITAL)
        rel = jnp.abs(y_a - y_d).max() / jnp.abs(y_d).max()
        assert float(rel) < 0.1, float(rel)

    def test_signed_split_matches_sign_flip(self):
        """split encoding: f(-x) == -f(x) for bias-free layers."""
        p = _mk()
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        y1 = apply_linear(p, x, NOISELESS_CFG)
        y2 = apply_linear(p, -x, NOISELESS_CFG)
        np.testing.assert_allclose(np.asarray(y1), -np.asarray(y2), atol=1e-6)

    def test_offset_encoding_close_to_split(self):
        p = _mk()
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        from repro.core.analog import calibrate

        p = calibrate(p, jnp.abs(x))
        y_split = apply_linear(p, x, NOISELESS_CFG)
        y_off = apply_linear(
            p, x, NOISELESS_CFG.replace(signed_input="offset")
        )
        y_d = apply_linear(p, x, DIGITAL)
        scale = float(jnp.abs(y_d).max())
        assert float(jnp.abs(y_off - y_split).max()) / scale < 0.25

    def test_unsigned_mode_for_relu_inputs(self):
        p = _mk()
        x = jnp.abs(jax.random.normal(KEY, (8, 256))) * 0.2
        from repro.core.analog import calibrate

        p = calibrate(p, x)
        y_n = apply_linear(p, x, NOISELESS_CFG.replace(signed_input="none"))
        y_s = apply_linear(p, x, NOISELESS_CFG)
        np.testing.assert_allclose(np.asarray(y_n), np.asarray(y_s), atol=1e-6)

    def test_hil_gradients_finite_and_nonzero(self):
        p = _mk(noise=NoiseConfig())
        x = jax.random.normal(KEY, (16, 256)) * 0.3

        def loss(params):
            y = apply_linear(params, x, AnalogConfig())
            return (y**2).mean()

        g = jax.grad(loss)(p)
        gw = g["w"]
        assert bool(jnp.isfinite(gw).all())
        assert float(jnp.abs(gw).max()) > 0.0

    def test_pallas_dispatch_matches_ref_path(self):
        p = _mk()
        x = jnp.abs(jax.random.normal(KEY, (8, 256))) * 0.2
        cfg = NOISELESS_CFG.replace(signed_input="none")
        y_ref = apply_linear(p, x, cfg)
        y_pl = apply_linear(p, x, cfg.replace(use_pallas=True))
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pl), atol=1e-6)

    def test_noise_reproducible_by_seed(self):
        p1 = _mk(noise=NoiseConfig(mode="full"), seed=7)
        p2 = _mk(noise=NoiseConfig(mode="full"), seed=7)
        np.testing.assert_array_equal(
            np.asarray(p1["fpn"]["gain"]), np.asarray(p2["fpn"]["gain"])
        )

    def test_readout_noise_changes_between_passes(self):
        p = _mk(noise=NoiseConfig(readout_std=2.0))
        x = jax.random.normal(KEY, (4, 256)) * 0.3
        cfg = AnalogConfig(deterministic=False)
        y1 = apply_linear(p, x, cfg, key=jax.random.PRNGKey(1))
        y2 = apply_linear(p, x, cfg, key=jax.random.PRNGKey(2))
        assert float(jnp.abs(y1 - y2).max()) > 0.0
        # deterministic mode ignores the key
        y3 = apply_linear(
            p, x, cfg.replace(deterministic=True), key=jax.random.PRNGKey(1)
        )
        y4 = apply_linear(
            p, x, cfg.replace(deterministic=True), key=jax.random.PRNGKey(2)
        )
        np.testing.assert_array_equal(np.asarray(y3), np.asarray(y4))


class TestTraining:
    def test_qat_reduces_loss(self):
        """HIL-style training through the analog forward converges."""
        key = jax.random.PRNGKey(0)
        p = analog_linear_init(key, 64, 4, noise=NoiseConfig())
        x = jax.random.normal(key, (128, 64)) * 0.4
        from repro.core.analog import calibrate

        p = calibrate(p, x)
        w_true = jax.random.normal(jax.random.PRNGKey(9), (64, 4)) * 0.3
        y_true = x @ w_true
        cfg = AnalogConfig()

        def loss(params):
            return ((apply_linear(params, x, cfg) - y_true) ** 2).mean()

        l0 = float(loss(p))
        lr = 0.05
        val_and_grad = jax.jit(jax.value_and_grad(loss))
        for _ in range(200):
            l, g = val_and_grad(p)
            # only the master weights train; scales/gain/fpn are calibration
            p = dict(p, w=p["w"] - lr * g["w"])
        # converges to the quantization/noise floor (~0.19 of l0 here)
        assert float(loss(p)) < 0.25 * l0, (l0, float(loss(p)))
