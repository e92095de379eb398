"""Tests for the `repro.calib` subsystem (ISSUE 4): blind
measurement-driven calibration on a VirtualChip, the serializable
CalibrationSnapshot, snapshot-baked lowering through exec/api, the
static-calibration fused-group unlock, and the serve-time drift monitor
hot-swap."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, calib
from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.hw import BSS2
from repro.core.noise import NOISELESS, NoiseConfig
from repro.exec.lower import lower_layer, lower_stack, plan_with_offsets
from repro.exec.run import dispatch_count, reset_dispatch_count, run, \
    run_layer
from repro.models import ecg as ECG
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(3)

ECG_KW = dict(
    epilogues=["relu_shift", "relu_shift", "none"],
    flatten_outs=[True, False, False], input_domain="codes",
)
ECG_NAMES = ("conv", "fc1", "fc2")


def _ecg_setup(seed=0, n=32):
    cfg = ECG.ECGConfig()
    params = ECG.ecg_init(jax.random.PRNGKey(seed), cfg)
    x = jnp.round(
        jax.random.uniform(jax.random.PRNGKey(seed + 1), (n, 2, 126)) * 31
    )
    cols = ECG._im2col(x, cfg.conv_taps, cfg.conv_stride)
    return cfg, params, x, cols


class TestVirtualChip:
    def test_measure_is_blind_and_shaped(self):
        chip = calib.VirtualChip(KEY, 200, 16, noise=NoiseConfig())
        adc = chip.measure(jnp.zeros((200, 16)), jnp.zeros((5, 200)))
        assert adc.shape == (5, chip.n_chunks, 16)
        assert chip.measurements == 1

    def test_measure_clips_to_representable_codes(self):
        """The interface can only express 6-bit weights / 5-bit events:
        out-of-range requests saturate like the hardware registers."""
        chip = calib.VirtualChip(KEY, 128, 4, noise=NOISELESS)
        big = chip.measure(jnp.full((128, 4), 1000.0),
                           jnp.full((1, 128), 1000.0), gain=0.001)
        leg = chip.measure(jnp.full((128, 4), 63.0),
                           jnp.full((1, 128), 31.0), gain=0.001)
        np.testing.assert_array_equal(np.asarray(big), np.asarray(leg))

    def test_noiseless_measure_matches_oracle_plan(self):
        """On a noiseless chip one accumulated measurement IS the
        faithful executor output up to fp32 summation order at exact ADC
        rounding ties (the chip batches its chunk passes, the
        deterministic executor chunk-scans): every element within 1 LSB,
        almost all exact."""
        from repro.core.analog import analog_matmul

        p = analog_linear_init(jax.random.PRNGKey(1), 200, 8,
                               noise=NoiseConfig(readout_std=0.0))
        chip = calib.VirtualChip.from_params(
            p, KEY, noise=NoiseConfig(readout_std=0.0))
        # codes within the 6-bit synapse range: the chip clips what it
        # cannot hold (test above), the oracle below does not - an
        # out-of-range draw would compare two different weight matrices
        w_code = jnp.clip(jnp.round(jax.random.normal(KEY, (200, 8)) * 20),
                          -BSS2.w_max, BSS2.w_max)
        a = jnp.round(jax.random.uniform(KEY, (3, 200)) * 31)
        got = np.asarray(chip.measure(w_code, a, gain=0.02).sum(axis=-2))
        want = np.asarray(analog_matmul(
            a, jnp.asarray(np_effective(p, w_code)), 0.02,
            p["fpn"].get("chunk_offset"), None,
            AnalogConfig(noise=NoiseConfig(readout_std=0.0)),
        ))
        assert np.abs(got - want).max() <= 1.0
        assert (got == want).mean() > 0.7


def np_effective(params, w_code):
    from repro.core import noise as noise_lib

    return noise_lib.effective_weight(w_code, params.get("fpn", {}))


class TestBlindRecovery:
    """Acceptance: with DEFAULT NoiseConfig magnitudes, offset nulling +
    gain fit recover the hidden fixed pattern to sub-LSB residual - the
    routines only ever touch chip.measure()."""

    @pytest.mark.parametrize("mode,k,n", [("full", 200, 48),
                                          ("rank1", 256, 32)])
    def test_sub_lsb_recovery(self, mode, k, n):
        chip = calib.VirtualChip(
            jax.random.fold_in(KEY, hash(mode) % 97), k, n,
            noise=NoiseConfig(mode=mode),
        )
        rec = calib.calibrate_chip(chip)
        truth = chip.oracle()
        off_res = np.abs(np.asarray(
            rec.chunk_offset - truth["chunk_offset"]
        ))
        assert off_res.max() < 0.5          # sub-LSB, every (chunk, col)
        assert (off_res ** 2).mean() ** 0.5 < 0.2
        rel = np.abs(np.asarray(
            (rec.gain_table - truth["gain_table"]) / truth["gain_table"]
        ))
        assert rel.max() < 0.03             # ~2% spread fitted to <3%

    def test_repeats_average_readout_noise(self):
        """More repeats -> smaller offset residual (the averaging claim,
        not just a lucky seed)."""
        res = {}
        for r in (4, 64):
            chip = calib.VirtualChip(KEY, 128, 32, noise=NoiseConfig())
            off = calib.null_offsets(chip, repeats=r)
            res[r] = float(jnp.sqrt(jnp.mean(
                (off - chip.oracle()["chunk_offset"]) ** 2
            )))
        assert res[64] < res[4]


class TestSnapshotRoundTrip:
    def test_save_load_bit_exact(self, tmp_path):
        cfg, params, _, cols = _ecg_setup()
        snap = calib.calibrate_model(
            ECG.ecg_module_spec(cfg), params, KEY,
            acfg=AnalogConfig(), sample=cols,
        )
        path = tmp_path / "chip.npz"
        snap.save(path)
        back = calib.CalibrationSnapshot.load(path)
        assert back.version == snap.version
        assert set(back.layers) == set(snap.layers)
        a, b = jax.tree.leaves(snap), jax.tree.leaves(back)
        assert len(a) == len(b)
        for la, lb in zip(a, b):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
            assert la.dtype == lb.dtype

    def test_load_rejects_unknown_version(self, tmp_path):
        snap = calib.CalibrationSnapshot(source="t")
        path = tmp_path / "v.npz"
        snap.save(path)
        import numpy as onp

        with onp.load(path) as z:
            arrays = {k: z[k] for k in z.files}
        arrays["__version__"] = onp.asarray("repro-calib-v0")
        with open(path, "wb") as f:
            onp.savez(f, **arrays)
        with pytest.raises(ValueError, match="format"):
            calib.CalibrationSnapshot.load(path)


class TestMeshInvariance:
    def test_calibration_independent_of_mesh(self):
        """Property (mirrors the fixed-pattern one): the chip samples its
        hidden pattern from the LOGICAL tile grid and the routines are
        pure functions of measure() results, so a calibration measured
        under an active mesh is identical to one measured without."""
        from repro.distributed import sharding as shd

        def measure_once():
            chip = calib.VirtualChip(KEY, 256, 32, noise=NoiseConfig())
            return calib.calibrate_chip(
                chip, offset_repeats=8, gain_repeats=2
            )

        r1 = measure_once()
        if len(jax.devices()) >= 4:
            with shd.use_mesh(make_mesh((2, 2), ("data", "model"))):
                r2 = measure_once()
        else:
            with shd.use_mesh(make_mesh((1, 1), ("data", "model"))):
                r2 = measure_once()
        for a, b in zip(jax.tree.leaves(r1), jax.tree.leaves(r2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCalibratedLowering:
    def test_ecg_calibrated_matches_oracle_within_noise(self):
        """Acceptance: the plan baked from blind measurement behaves like
        the plan baked from ground truth - classification agreement plus
        logit agreement within the uncompensatable per-synapse spread."""
        cfg, params, _, cols = _ecg_setup(n=64)
        acfg = AnalogConfig()
        snap = calib.calibrate_model(
            ECG.ecg_module_spec(cfg, epilogue="relu_shift"), params,
            jax.random.fold_in(KEY, 1),
        )
        lp = [params[n] for n in ECG_NAMES]
        plan_oracle = lower_stack(lp, acfg, **ECG_KW)
        plan_cal = lower_stack(
            lp, acfg, calibs=[snap.layer(n) for n in ECG_NAMES], **ECG_KW
        )
        yo, yc = run(plan_oracle, cols), run(plan_cal, cols)
        # classification = the inference read-out: class-copy average
        # pooling, then argmax over the classes (argmax over the 10 raw
        # neurons also compares noise between copies of ONE class)
        pool = functools.partial(ECG._pool_class_copies, cfg=cfg, train=False)
        agree = float((pool(yo).argmax(-1) == pool(yc).argmax(-1)).mean())
        assert agree >= 0.9
        rel = float(jnp.abs(yo - yc).mean() / jnp.sqrt((yo ** 2).mean()))
        assert rel < 0.15
        # same static schedule: calibrated replay costs the same dispatches
        assert plan_cal.expected_dispatches == \
            plan_oracle.expected_dispatches
        assert (plan_cal.mega is None) == (plan_oracle.mega is None)

    def test_compile_calibration_kw_stack(self):
        cfg, params, x, cols = _ecg_setup()
        spec = ECG.ecg_module_spec(cfg, epilogue="relu_shift")
        acfg = AnalogConfig()
        snap = calib.calibrate_model(spec, params,
                                     jax.random.fold_in(KEY, 2))
        model = api.compile(spec, params, acfg, calibration=snap)
        assert model.calibration is snap
        want = run(lower_stack(
            [params[n] for n in ECG_NAMES], acfg,
            calibs=[snap.layer(n) for n in ECG_NAMES], **ECG_KW,
        ), cols)
        np.testing.assert_array_equal(
            np.asarray(model.run_stack(cols)), np.asarray(want)
        )
        # relower keeps the calibration (one weight update, same chip)
        again = model.relower(params)
        np.testing.assert_array_equal(
            np.asarray(again.run_stack(cols)), np.asarray(want)
        )

    def test_uncovered_layers_keep_oracle_bake(self):
        cfg, params, _, cols = _ecg_setup()
        acfg = AnalogConfig()
        snap = calib.CalibrationSnapshot()      # empty: nothing measured
        model = api.compile(ECG.ecg_module_spec(cfg, epilogue="relu_shift"),
                            params, acfg, calibration=snap)
        want = api.compile(ECG.ecg_module_spec(cfg, epilogue="relu_shift"),
                           params, acfg)
        np.testing.assert_array_equal(
            np.asarray(model.run_stack(cols)),
            np.asarray(want.run_stack(cols)),
        )

    def test_group_member_output_not_rescaled_by_joining(self):
        """Joining a shared-encoding group only coarsens the member's
        input LSB - it must NOT rescale the output (dequant happens at
        the LSB the codes were actually encoded with)."""
        p = analog_linear_init(KEY, 256, 16, noise=NOISELESS)
        p = dict(p, a_scale=jnp.asarray(0.01, jnp.float32))
        static = AnalogConfig(noise=NOISELESS, act_calib="static")
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        solo = run_layer(lower_layer(p, static), x, static)
        grouped = run_layer(lower_layer(p, static, calib=(
            calib.LayerCalibration(
                a_scale=jnp.asarray(0.01, jnp.float32),
                a_scale_in=jnp.asarray(0.07, jnp.float32),
            ))), x, static)
        # same linear map, only quantization resolution differs
        rel = float(jnp.abs(solo - grouped).mean()
                    / (jnp.abs(solo).mean() + 1e-9))
        assert rel < 0.5       # NOT the ~7x attenuation of a rescale

    def test_scales_only_record_keeps_oracle_fixed_pattern(self):
        """A record carrying only activation scales (e.g. built by
        share_group_input_scale with explicit scales) must not silently
        bake an ideal chip: unmeasured quantities fall back to the
        oracle params['fpn']."""
        p = analog_linear_init(KEY, 256, 16, noise=NoiseConfig())
        rec = calib.LayerCalibration(
            a_scale=jnp.asarray(0.05, jnp.float32))
        lp = lower_layer(p, AnalogConfig(act_calib="static"), calib=rec)
        want = lower_layer(p, AnalogConfig(act_calib="static"))
        np.testing.assert_array_equal(np.asarray(lp.w_eff),
                                      np.asarray(want.w_eff))
        np.testing.assert_array_equal(np.asarray(lp.chunk_offset),
                                      np.asarray(want.chunk_offset))
        np.testing.assert_allclose(float(lp.a_scale), 0.05)

    def test_gain_table_shape_mismatch_raises(self):
        p = analog_linear_init(KEY, 256, 16, noise=NoiseConfig())
        bad = calib.LayerCalibration(
            gain_table=jnp.ones((3, 16), jnp.float32)   # 256 rows = 2 chunks
        )
        with pytest.raises(ValueError, match="gain_table"):
            lower_layer(p, AnalogConfig(), calib=bad)


class TestFusedStaticUnlock:
    """Acceptance: lower_fused accepts differing static a_scales when a
    snapshot provides the group's shared input scale (a_scale_in) -
    bit-exact vs unfused, dispatch count unchanged."""

    def _group(self):
        ps = [analog_linear_init(jax.random.fold_in(KEY, i), 256, 32,
                                 noise=NoiseConfig()) for i in range(3)]
        scales = [0.01, 0.07, 0.03]
        ps = [dict(p, a_scale=jnp.asarray(s, jnp.float32))
              for p, s in zip(ps, scales)]
        names = [f"l{i}" for i in range(3)]
        snap = calib.CalibrationSnapshot()
        for n, p in zip(names, ps):
            chip = calib.VirtualChip.from_params(
                p, jax.random.fold_in(KEY, 7))
            snap = snap.with_layer(n, calib.calibrate_chip(
                chip, offset_repeats=16, gain_repeats=2))
        snap = calib.share_group_input_scale(
            snap, names, scales=[p["a_scale"] for p in ps])
        return ps, names, snap

    def test_bit_exact_vs_unfused_and_one_dispatch(self):
        from repro.exec.lower import lower_fused

        ps, names, snap = self._group()
        static = AnalogConfig(act_calib="static")
        calibs = [snap.layer(n) for n in names]
        fused = lower_fused(ps, static, calibs=calibs)
        # ONE shared encoding LSB (widest member) for quant AND dequant
        np.testing.assert_allclose(float(fused.a_scale_in), 0.07)
        np.testing.assert_allclose(float(fused.a_scale), 0.07)
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        reset_dispatch_count()
        got = run_layer(fused, x, static)
        assert dispatch_count() == 1            # unchanged vs same-scale
        outs = []
        for p, c in zip(ps, calibs):
            outs.append(run_layer(
                lower_layer(p, static, calib=c), x, static))
        want = jnp.concatenate(outs, axis=-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_differing_scales_still_raise_without_calibration(self):
        from repro.exec.lower import lower_fused

        ps, _, _ = self._group()
        with pytest.raises(ValueError, match="a_scale"):
            lower_fused(ps, AnalogConfig(act_calib="static"))

    def test_lower_tree_fuses_qkv_under_static_with_snapshot(self):
        from repro.models import attention as A

        p = A.attention_init(KEY, 64, 4, 2, 16, noise=NoiseConfig())
        p["wk"] = dict(p["wk"], a_scale=p["wk"]["a_scale"] * 7.0)
        static = AnalogConfig(act_calib="static")
        names = ["wq", "wk", "wv"]
        snap = calib.CalibrationSnapshot()
        for i, n in enumerate(names):
            chip = calib.VirtualChip.from_params(
                p[n], jax.random.fold_in(KEY, 20 + i))
            snap = snap.with_layer(n, calib.calibrate_chip(
                chip, offset_repeats=16, gain_repeats=2))
        snap = calib.share_group_input_scale(
            snap, names, scales=[p[n]["a_scale"] for n in names])
        lowered = api.lower_tree(p, static, calibration=snap)
        assert "_qkv_plan" in lowered           # static fusion unlocked
        # ... and attention consumes it (the a_scale_in marker)
        x = jax.random.normal(KEY, (2, 8, 64)) * 0.3
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None],
                               (2, 8))
        kw = dict(positions=pos, acfg=static, n_heads=4, n_kv_heads=2,
                  head_dim=16, rope_theta=1e4)
        reset_dispatch_count()
        A.attention_apply(lowered, x, **kw)
        n_fused = dispatch_count()
        # per-layer lowering from the SAME snapshot: 2 more dispatches
        per_layer = {k: (dict(v, _plan=lower_layer(
            p[k], static, calib=snap.layer(k)))
            if k in names else v) for k, v in p.items()}
        reset_dispatch_count()
        want, _ = A.attention_apply(per_layer, x, **kw)
        assert dispatch_count() == n_fused + 2
        got, _ = A.attention_apply(lowered, x, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_without_group_scale_no_static_fusion(self):
        """A snapshot that measured the members separately (no shared
        a_scale_in) must NOT unlock static fusion."""
        from repro.models import attention as A

        p = A.attention_init(KEY, 64, 4, 2, 16, noise=NoiseConfig())
        snap = calib.CalibrationSnapshot()
        for i, n in enumerate(["wq", "wk", "wv"]):
            chip = calib.VirtualChip.from_params(
                p[n], jax.random.fold_in(KEY, 30 + i))
            snap = snap.with_layer(n, calib.calibrate_chip(
                chip, offset_repeats=8, gain_repeats=2))
        lowered = api.lower_tree(
            p, AnalogConfig(act_calib="static"), calibration=snap)
        assert "_qkv_plan" not in lowered


class TestDriftMonitorHotSwap:
    def test_stack_offset_swap_keeps_treedef_and_cache(self):
        cfg, params, _, cols = _ecg_setup()
        spec = ECG.ecg_module_spec(cfg, epilogue="relu_shift")
        acfg = AnalogConfig()
        chips = calib.model_chips(spec, params, KEY)
        snap = calib.calibrate_model(spec, params, KEY, chips=chips)
        model = api.compile(spec, params, acfg, calibration=snap)
        plan = model.lower()
        f = jax.jit(lambda pl, c: run(pl, c))
        y0 = f(plan, cols)
        # offsets drift on every device; the monitor detects + re-nulls
        mon = calib.DriftMonitor(chips, snap, threshold_lsb=0.5)
        assert mon.maybe_refresh() is None      # stable: no refresh
        for i, c in enumerate(chips.values()):
            c.apply_drift(jax.random.fold_in(KEY, 50 + i), 2.0)
        assert mon.drift_lsb() > 0.5
        fresh = mon.maybe_refresh()
        assert fresh is not None and mon.refreshes == 1
        swapped = model.with_calibration(fresh).lower()
        assert jax.tree_util.tree_structure(swapped) == \
            jax.tree_util.tree_structure(plan)
        y1 = f(swapped, cols)
        assert f._cache_size() == 1             # hot swap: NO recompile
        # the swapped plan tracks the drifted device to sub-LSB again
        for name, lp in zip(ECG_NAMES, swapped.layers):
            res = jnp.abs(lp.chunk_offset
                          - chips[name].oracle()["chunk_offset"])
            assert float(res.max()) < 0.5
        # and actually changed the computation (drift was real)
        assert not bool((y0 == y1).all())

    def test_refresh_keeps_gains_and_scales(self):
        chip = calib.VirtualChip(KEY, 128, 8, noise=NoiseConfig())
        rec = calib.calibrate_chip(chip, offset_repeats=16,
                                   gain_repeats=2)
        snap = calib.CalibrationSnapshot(layers={"l": rec}) \
            .with_layer("l", rec.replace(a_scale=jnp.asarray(0.5)))
        mon = calib.DriftMonitor({"l": chip}, snap, threshold_lsb=0.1)
        chip.apply_drift(KEY, 1.0)
        fresh = mon.maybe_refresh()
        assert fresh is not None
        np.testing.assert_array_equal(
            np.asarray(fresh.layer("l").gain_table),
            np.asarray(rec.gain_table),
        )
        np.testing.assert_allclose(float(fresh.layer("l").a_scale), 0.5)

    def test_serve_engine_recalibrates_between_batches(self):
        from repro.configs.base import ArchConfig, RunConfig
        from repro.models import transformer as T
        from repro.serve.engine import Request, ServeEngine

        cfg = ArchConfig("t-drift", "dense", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128,
                         vocab_size=256)
        params = T.lm_init(KEY, cfg)
        run_cfg = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        spec = T.lm_module_spec(cfg, params)
        chips = calib.model_chips(spec, params, KEY)
        assert chips                            # lm_head at least
        snap = calib.calibrate_model(spec, params, KEY, chips=chips,
                                     offset_repeats=16, gain_repeats=2)
        mon = calib.DriftMonitor(chips, snap, threshold_lsb=0.5)
        eng = ServeEngine(cfg, run_cfg, params, batch_size=2, max_len=32,
                          calibration=snap, drift_monitor=mon)
        td0 = jax.tree_util.tree_structure(eng.params)
        prompt = np.arange(6) % cfg.vocab_size
        r1 = eng.serve([Request(0, prompt, 4)])[0]
        assert mon.refreshes == 0
        for i, c in enumerate(chips.values()):
            c.apply_drift(jax.random.fold_in(KEY, 70 + i), 2.0)
        r2 = eng.serve([Request(1, prompt, 4)])[0]
        assert mon.refreshes == 1               # drift detected + swapped
        assert jax.tree_util.tree_structure(eng.params) == td0
        assert r2.output is not None and len(r2.output) == 4


class TestECGNoiseModeAudit:
    """Satellite: the ECG config REQUESTS the documented full per-synapse
    map explicitly; ecg_init no longer silently upgrades the mode."""

    def test_default_config_is_full_map(self):
        assert ECG.ECGConfig().noise.mode == "full"

    def test_init_honors_requested_mode(self):
        p_full = ECG.ecg_init(KEY, ECG.ECGConfig())
        assert p_full["conv"]["fpn"]["gain"].shape == (128, 8)
        rank1 = ECG.ECGConfig(noise=NoiseConfig())     # explicit rank1
        p_r1 = ECG.ecg_init(KEY, rank1)
        assert "gain" not in p_r1["conv"]["fpn"]
        assert p_r1["conv"]["fpn"]["row_gain"].shape == (128,)

    def test_spec_declares_codes_domain_for_relu_shift(self):
        spec = ECG.ecg_module_spec(ECG.ECGConfig(), epilogue="relu_shift")
        assert spec.input_domain == "codes"
        assert spec.layer_names() == ("conv", "fc1", "fc2")


class TestPlanOffsetSwapHelpers:
    def test_plan_with_offsets_rejects_shape_mismatch(self):
        cfg, params, _, _ = _ecg_setup()
        plan = lower_stack([params[n] for n in ECG_NAMES],
                           AnalogConfig(), **ECG_KW)
        with pytest.raises(ValueError, match="shape"):
            plan_with_offsets(
                plan, [jnp.zeros((1, 1))] * len(plan.layers))

    def test_swap_requires_existing_offsets(self):
        from repro.exec.lower import layer_with_offsets

        p = analog_linear_init(KEY, 128, 8, noise=NOISELESS)
        lp = lower_layer(p, AnalogConfig(noise=NOISELESS))
        assert lp.chunk_offset is None
        with pytest.raises(ValueError, match="offset"):
            layer_with_offsets(lp, jnp.zeros((1, 8)))
