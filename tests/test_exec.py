"""Tests for the exec subsystem (ISSUE 1): plan lowering/reuse, the fused
signed-split kernel vs the two-pass oracle, the ADC epilogue fusion, and
HIL gradient parity between the Pallas-dispatch and pure-jnp paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.exec as E
from repro.api import apply_linear
from repro.core.analog import (
    AnalogConfig,
    analog_linear_init,
)
from repro.core.noise import NOISELESS, NoiseConfig
from repro.exec.run import dispatch_count, reset_dispatch_count
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.analog_mvm import analog_mvm_split_pallas
from repro.models import ecg as ECG

KEY = jax.random.PRNGKey(7)
SPLIT_CFG = AnalogConfig(noise=NOISELESS, signed_input="split")


def _mk(in_dim=256, out_dim=64, noise=NOISELESS, seed=0):
    return analog_linear_init(
        jax.random.PRNGKey(seed), in_dim, out_dim, noise=noise
    )


def _split_inputs(m, k, n, seed=0):
    ka, kw, kg, ko = jax.random.split(jax.random.PRNGKey(seed), 4)
    a_pos = jnp.round(jax.random.uniform(ka, (m, k)) * 31)
    a_neg = jnp.round(jax.random.uniform(kg, (m, k)) * 31)
    w = jnp.round(jax.random.uniform(kw, (k, n), minval=-1, maxval=1) * 63)
    w = w * (1 + 0.02 * jax.random.normal(kg, (k, n)))
    gain = jnp.full((n,), 0.02, jnp.float32)
    off = jax.random.normal(ko, (k // 128, n), jnp.float32)
    return a_pos, a_neg, w, gain, off


class TestFusedSplitKernel:
    @pytest.mark.parametrize("m,k,n", [(8, 128, 64), (100, 384, 129),
                                       (256, 256, 512)])
    @pytest.mark.parametrize("faithful", [True, False])
    def test_bit_exact_vs_two_pass_kernel(self, m, k, n, faithful):
        """Fused single-grid kernel (fp32 interpret mode) == the existing
        two-analog-pass path (two independent kernel launches), bit for
        bit: sharing the tile schedule must not change the arithmetic."""
        from repro.kernels.analog_mvm import analog_mvm_pallas

        a_pos, a_neg, w, gain, off = _split_inputs(m, k, n)
        got = analog_mvm_split_pallas(
            a_pos, a_neg, w, gain, off, faithful=faithful, interpret=True,
        )
        want = analog_mvm_pallas(
            a_pos, w, gain, off, faithful=faithful, interpret=True,
        ) - analog_mvm_pallas(
            a_neg, w, gain, off, faithful=faithful, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("faithful", [True, False])
    def test_close_to_two_pass_oracle(self, faithful):
        """Against the pure-jnp oracle the fused kernel is exact up to the
        fp32 contraction-order sensitivity of the noised float weights
        (<= 1 ADC code per chunk at round boundaries); with integer
        weights it is bit-exact (covered by the unsigned kernel suite)."""
        a_pos, a_neg, w, gain, off = _split_inputs(64, 256, 128)
        got = analog_mvm_split_pallas(
            a_pos, a_neg, w, gain, off, faithful=faithful, interpret=True,
        )
        want = R.analog_mvm_split_ref(a_pos, a_neg, w, gain, off,
                                      faithful=faithful)
        assert float(jnp.abs(got - want).max()) <= 2.0 * (256 // 128)

    def test_fused_jnp_path_bit_exact(self):
        """The stacked-batch jnp fusion equals the two-pass oracle too."""
        a_pos, a_neg, w, gain, off = _split_inputs(16, 256, 96)
        got = ops.analog_mvm_split(a_pos, a_neg, w, gain, off,
                                   128, True, False, True)
        want = ops.analog_mvm_split(a_pos, a_neg, w, gain, off,
                                    128, True, False, False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_module_level_fused_matches_two_pass(self):
        p = _mk()
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        y_fused = apply_linear(p, x, SPLIT_CFG)
        y_two = apply_linear(p, x, SPLIT_CFG.replace(
            fused_split=False))
        np.testing.assert_array_equal(np.asarray(y_fused),
                                      np.asarray(y_two))

    def test_epilogue_in_kernel_matches_reference(self):
        a_pos, a_neg, w, gain, off = _split_inputs(8, 256, 64)
        epi = ("relu_shift", 3)
        got = analog_mvm_split_pallas(a_pos, a_neg, w, gain, off,
                                      interpret=True, epilogue=epi)
        want = R.adc_epilogue_ref(
            R.analog_mvm_split_ref(a_pos, a_neg, w, gain, off), epi
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(got.min()) >= 0.0 and float(got.max()) <= 31.0


class TestAnalogPlan:
    def test_lower_once_run_twice_identical(self):
        p = _mk()
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        plan = E.lower(p, SPLIT_CFG)
        y1 = E.run(plan, x)
        y2 = E.run(plan, x)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
        # and equals the legacy per-call wrapper
        y3 = apply_linear(p, x, SPLIT_CFG)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y3))

    def test_plan_is_jit_reusable_pytree(self):
        """A plan flows through jit as a pytree: two runs of the jitted
        executor reuse ONE compiled executable (no retracing)."""
        p = _mk()
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        plan = E.lower(p, SPLIT_CFG)
        traces = []

        @jax.jit
        def f(plan, x):
            traces.append(1)
            return E.run(plan, x)

        y1 = f(plan, x)
        y2 = f(plan, x)
        assert len(traces) == 1
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_no_weight_requantization_in_run_trace(self):
        """Lowering bakes weight quantization: the executor's jaxpr must
        not divide by the weight scale (the quantize_weight signature op),
        while the legacy per-call wrapper's jaxpr does."""
        p = _mk()
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        plan = E.lower(p, SPLIT_CFG)

        def sub_jaxprs(params):
            for v in params.values():
                for item in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(item, "jaxpr"):       # ClosedJaxpr
                        yield item.jaxpr
                    elif hasattr(item, "eqns"):      # raw Jaxpr
                        yield item

        def count_wscale_divs(jaxpr):
            # quantize_weight divides the [K, N] master weights by the
            # [1, N] scale; count div eqns with that operand signature
            # (recursing into sub-jaxprs: scan/custom_vjp bodies).
            n = 0
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "div":
                    shapes = [getattr(v.aval, "shape", ()) for v in
                              eqn.invars]
                    if shapes and shapes[0] == (256, 64):
                        n += 1
                for sub in sub_jaxprs(eqn.params):
                    n += count_wscale_divs(sub)
            return n

        run_jaxpr = jax.make_jaxpr(lambda pl_, x_: E.run(pl_, x_))(plan, x)
        apply_jaxpr = jax.make_jaxpr(
            lambda p_, x_: apply_linear(p_, x_, SPLIT_CFG)
        )(p, x)
        assert count_wscale_divs(run_jaxpr.jaxpr) == 0
        assert count_wscale_divs(apply_jaxpr.jaxpr) > 0

    def test_mixed_epilogue_plan_keeps_float_input(self):
        """A plan whose FIRST layer hands off floats must quantize its
        float input even when a later layer uses a code-domain epilogue."""
        from repro.exec.lower import lower_stack

        ps = [_mk(seed=i, out_dim=256) for i in range(2)] + [_mk(seed=2)]
        plan = lower_stack(ps, SPLIT_CFG,
                           epilogues=["none", "relu_shift", "none"])
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        y_auto = E.run(plan, x)
        y_float = E.run(plan, x, x_is_codes=False)
        np.testing.assert_array_equal(np.asarray(y_auto),
                                      np.asarray(y_float))

    def test_bias_rejected_in_code_domain_handoff(self):
        p = analog_linear_init(jax.random.PRNGKey(0), 128, 128, bias=True,
                               noise=NOISELESS)
        from repro.exec.lower import lower_layer

        with pytest.raises(ValueError, match="bias"):
            lower_layer(p, SPLIT_CFG, epilogue="relu_shift")

    def test_prelowered_cfg_mismatch_falls_back(self):
        """A baked plan with different static attrs than the call-site cfg
        must not be used (per-call lowering takes over)."""
        from repro import api

        p = _mk()
        x = jnp.abs(jax.random.normal(KEY, (4, 256))) * 0.2
        lowered = api.lower_tree(p, SPLIT_CFG)         # bakes "split"
        cfg_none = SPLIT_CFG.replace(signed_input="none")
        y1 = apply_linear(lowered, x, cfg_none)
        y2 = apply_linear(p, x, cfg_none)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_weight_tied_layers_get_float_glue(self):
        """The same LayerPlan object appearing twice must still get the
        inter-layer ReLU glue at every non-final position."""
        from repro.exec.lower import lower_layer, lower_stack
        from repro.exec.plan import AnalogPlan

        p = _mk(in_dim=256, out_dim=256)
        lp = lower_layer(p, SPLIT_CFG)
        tied = AnalogPlan(layers=(lp, lp), cfg=SPLIT_CFG)
        untied = lower_stack([p, p], SPLIT_CFG)
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        np.testing.assert_array_equal(np.asarray(E.run(tied, x)),
                                      np.asarray(E.run(untied, x)))

    def test_prelowered_params_shortcut(self):
        from repro import api

        p = _mk()
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        tree = {"layer": p, "other": {"scale": jnp.ones((4,))}}
        lowered = api.lower_tree(tree, SPLIT_CFG)
        assert "_plan" in lowered["layer"]
        assert "_plan" not in lowered["other"]
        y1 = apply_linear(lowered["layer"], x, SPLIT_CFG)
        y2 = apply_linear(p, x, SPLIT_CFG)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


class TestDispatchCounts:
    def test_fused_split_halves_dispatches(self):
        p = _mk()
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        reset_dispatch_count()
        apply_linear(p, x, SPLIT_CFG)
        fused = dispatch_count()
        reset_dispatch_count()
        apply_linear(p, x, SPLIT_CFG.replace(fused_split=False))
        two_pass = dispatch_count()
        assert (fused, two_pass) == (1, 2)

    def test_ecg_split_stack_halves_dispatches(self):
        """ECG-shaped 3-layer stack in split encoding: plan executor = 3
        fused dispatches, per-call two-pass path = 6."""
        cfg = ECG.ECGConfig(noise=NOISELESS)
        params = ECG.ecg_init(jax.random.PRNGKey(0), cfg)
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(1), (2, 2, 126)) * 31
        )
        stack = [params["conv"], params["fc1"], params["fc2"]]
        from repro.exec.lower import lower_stack

        plan = lower_stack(stack, SPLIT_CFG)
        cols = ECG._im2col(x, cfg.conv_taps, cfg.conv_stride)
        reset_dispatch_count()
        E.run(plan, cols)
        fused = dispatch_count()
        plan2 = lower_stack(stack, SPLIT_CFG.replace(fused_split=False))
        reset_dispatch_count()
        E.run(plan2, cols)
        two_pass = dispatch_count()
        assert fused * 2 == two_pass
        assert fused == 3
        # the static plan metadata agrees with the traced counts
        assert plan.expected_dispatches == 3
        assert plan2.expected_dispatches == 6

    def test_cached_jit_replay_counts_zero_but_plan_knows(self):
        """The ANALOG_DISPATCHES counter bumps at TRACE time only: a
        cached-jit replay observes 0, so counter-only assertions can pass
        vacuously.  Plans carry the static expected_dispatches instead."""
        p = _mk()
        x = jax.random.normal(KEY, (8, 256)) * 0.2
        plan = E.lower(p, SPLIT_CFG)
        f = jax.jit(lambda pl_, x_: E.run(pl_, x_))
        reset_dispatch_count()
        f(plan, x).block_until_ready()
        assert dispatch_count() == plan.expected_dispatches == 1
        reset_dispatch_count()
        f(plan, x).block_until_ready()          # cached executable
        assert dispatch_count() == 0            # the vacuous-pass hazard
        assert plan.expected_dispatches == 1    # the static ground truth


class TestECGPlanExecutor:
    def test_plan_matches_module_path(self):
        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(jax.random.PRNGKey(0), cfg)
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(1), (4, 2, 126)) * 31
        )
        from repro import api

        acfg = AnalogConfig()
        plan = api.compile(
            ECG.ecg_module_spec(cfg), params, acfg
        ).lower()
        y_plan = ECG.ecg_apply_plan(plan, x, cfg)
        y_mod = ECG.ecg_apply(params, x, acfg, cfg)
        np.testing.assert_array_equal(np.asarray(y_plan),
                                      np.asarray(y_mod))

    def test_adc_chain_runs_in_code_domain(self):
        """relu_shift lowering: inter-layer activations are 5-bit codes;
        in-kernel fused epilogue == elementwise STE epilogue bit-exact."""
        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(jax.random.PRNGKey(0), cfg)
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(1), (4, 2, 126)) * 31
        )
        from repro import api

        acfg = AnalogConfig()
        spec = ECG.ecg_module_spec(cfg, epilogue="relu_shift")
        plan_ste = api.compile(
            spec, params, acfg.replace(use_pallas=True)
        ).lower()
        plan_fused = api.compile(
            spec, params, acfg.replace(use_pallas=True, fused_epilogue=True)
        ).lower()
        y_ste = ECG.ecg_apply_plan(plan_ste, x, cfg)
        y_fused = ECG.ecg_apply_plan(plan_fused, x, cfg)
        np.testing.assert_array_equal(np.asarray(y_ste),
                                      np.asarray(y_fused))
        # the classifier still separates something (not all-equal logits)
        assert float(jnp.abs(y_ste).max()) > 0.0


def _ecg_code_plan(acfg, seed=0):
    cfg = ECG.ECGConfig()
    params = ECG.ecg_init(jax.random.PRNGKey(seed), cfg)
    from repro.exec.lower import lower_stack

    plan = lower_stack(
        [params["conv"], params["fc1"], params["fc2"]], acfg,
        epilogues=["relu_shift", "relu_shift", "none"],
        flatten_outs=[True, False, False], input_domain="codes",
    )
    x = jnp.round(
        jax.random.uniform(jax.random.PRNGKey(1), (4, 2, 126)) * 31
    )
    return plan, ECG._im2col(x, cfg.conv_taps, cfg.conv_stride), params


class TestMegakernel:
    """The whole-plan megakernel (ISSUE 3): one dispatch per code-domain
    stack, bit-exact vs the layer-by-layer replay."""

    @pytest.mark.parametrize("acfg", [
        AnalogConfig(),                                 # jnp chain
        AnalogConfig(mode="analog_fast"),
        AnalogConfig(use_pallas=True),                  # Pallas interpret
        AnalogConfig(use_pallas=True, fused_epilogue=True),
    ], ids=["jnp", "jnp_fast", "pallas", "pallas_fused_epi"])
    def test_bit_exact_vs_per_layer_ecg_chain(self, acfg):
        """Acceptance bar: the ECG conv->fc1->fc2 chain through ONE
        kernel equals the layer-by-layer plan replay bit for bit (fp32,
        interpret mode on the Pallas path), fpn noise on."""
        plan, cols, _ = _ecg_code_plan(acfg)
        assert plan.mega is not None
        y_per = E.run(plan, cols, megakernel=False)
        y_mk = E.run(plan, cols, megakernel=True)
        np.testing.assert_array_equal(np.asarray(y_per), np.asarray(y_mk))

    def test_single_dispatch_and_expected_count(self):
        plan, cols, _ = _ecg_code_plan(AnalogConfig())
        reset_dispatch_count()
        E.run(plan, cols, megakernel=False)
        assert dispatch_count() == plan.expected_dispatches == 3
        reset_dispatch_count()
        E.run(plan, cols, megakernel=True)
        assert dispatch_count() == 1

    def test_auto_routes_code_chain_through_megakernel(self):
        """The default megakernel='auto' takes the single-dispatch route
        for an eligible plan and falls back for a float-glue plan."""
        plan, cols, params = _ecg_code_plan(AnalogConfig())
        reset_dispatch_count()
        E.run(plan, cols)
        assert dispatch_count() == 1
        from repro.exec.lower import lower_stack

        plan_f = lower_stack(
            [params["conv"], params["fc1"], params["fc2"]], AnalogConfig(),
            flatten_outs=[True, False, False],
        )
        assert plan_f.mega is None
        reset_dispatch_count()
        E.run(plan_f, cols)
        assert dispatch_count() == plan_f.expected_dispatches == 3

    def test_force_megakernel_raises_on_ineligible(self):
        plan, cols, params = _ecg_code_plan(AnalogConfig())
        from repro.exec.lower import lower_stack

        plan_f = lower_stack(
            [params["conv"], params["fc1"], params["fc2"]], AnalogConfig(),
            flatten_outs=[True, False, False],
        )
        with pytest.raises(ValueError, match="megakernel=True"):
            E.run(plan_f, cols, megakernel=True)
        # shape mismatch: flatten expects the position axis
        with pytest.raises(ValueError, match="megakernel=True"):
            E.run(plan, cols.reshape(-1, cols.shape[-1]), megakernel=True)

    def test_noisy_replay_falls_back(self):
        """Readout-noise replay (key given, deterministic off) keeps the
        layer-by-layer path under 'auto' and raises under True."""
        plan, cols, _ = _ecg_code_plan(AnalogConfig(deterministic=False))
        key = jax.random.PRNGKey(3)
        reset_dispatch_count()
        E.run(plan, cols, key=key)
        assert dispatch_count() == plan.expected_dispatches
        with pytest.raises(ValueError, match="noisy"):
            E.run(plan, cols, key=key, megakernel=True)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_hil_gradients_match_per_layer(self, use_pallas):
        """Differentiating through the megakernel route reproduces the
        per-layer HIL gradients exactly (frozen gain/offsets, linearized
        ADC) - on the Pallas path via the ref-chain custom VJP."""
        from repro.exec.lower import lower_stack

        acfg = AnalogConfig(use_pallas=use_pallas)
        _, cols, params = _ecg_code_plan(acfg)
        stack = [params["conv"], params["fc1"], params["fc2"]]

        def loss(ps, mk):
            plan = lower_stack(
                ps, acfg, epilogues=["relu_shift", "relu_shift", "none"],
                flatten_outs=[True, False, False], input_domain="codes",
            )
            return (E.run(plan, cols, megakernel=mk) ** 2).mean()

        g_per = jax.grad(loss)(stack, False)
        g_mk = jax.grad(loss)(stack, True)
        for i, (gp, gm) in enumerate(zip(g_per, g_mk)):
            # Each dW entry sums the STE backward over the batch rows, and
            # the two routes sum in different orders (per-layer custom-VJP
            # matmuls vs the ref chain's per-chunk einsums).  A
            # mathematically neutral batch permutation moves ONE route's
            # conv dW by up to 2 fp32 ulps of its largest entry (the
            # megakernel route by up to 4.5), so entries that cancel to
            # near zero cannot meet a pure rtol: the absolute floor is 16
            # ulps of the array's largest entry
            ulp = np.finfo(np.float32).eps * np.abs(np.asarray(gp["w"])).max()
            np.testing.assert_allclose(
                np.asarray(gp["w"]), np.asarray(gm["w"]),
                rtol=1e-6, atol=16 * ulp,
            )
            # gain is frozen INSIDE the analog passes on both paths; the
            # only gain gradient is the last layer's differentiable
            # dequantization divide - identical between the routes
            np.testing.assert_allclose(
                np.asarray(gp["gain"]), np.asarray(gm["gain"]),
                rtol=1e-6, atol=1e-6,
            )
            if i < 2:
                np.testing.assert_array_equal(
                    np.asarray(gp["gain"]),
                    np.zeros_like(np.asarray(gp["gain"])),
                )

    def test_megakernel_flows_through_jit_as_pytree(self):
        plan, cols, _ = _ecg_code_plan(AnalogConfig())
        traces = []

        @jax.jit
        def f(plan, x):
            traces.append(1)
            return E.run(plan, x, megakernel=True)

        y1 = f(plan, cols)
        y2 = f(plan, cols)
        assert len(traces) == 1
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
        np.testing.assert_array_equal(
            np.asarray(y1), np.asarray(E.run(plan, cols, megakernel=False))
        )

    def test_flatten_factor_one_consumes_position_dim(self):
        """A flatten_out layer with a size-1 position axis still merges
        it into features on the per-layer path; the megakernel route must
        produce the SAME output shape (it used to keep the singleton)."""
        from repro.exec.lower import lower_stack

        ps = [_mk(seed=0, in_dim=128, out_dim=64),
              _mk(seed=1, in_dim=64, out_dim=32)]
        plan = lower_stack(
            ps, AnalogConfig(noise=NOISELESS),
            epilogues=["relu_shift", "none"], flatten_outs=[True, False],
            input_domain="codes",
        )
        assert plan.mega is not None
        assert plan.mega.schedule[0].flatten == 1
        x = jnp.round(jax.random.uniform(KEY, (5, 1, 128)) * 31)
        y_per = E.run(plan, x, megakernel=False)
        y_mk = E.run(plan, x)                     # default "auto" routes
        assert y_per.shape == y_mk.shape == (5, 32)
        np.testing.assert_array_equal(np.asarray(y_per), np.asarray(y_mk))
        # without the position axis the shapes cannot feed the flatten
        with pytest.raises(ValueError, match="trailing batch dim"):
            E.run(plan, jnp.round(jax.random.uniform(KEY, (5, 128)) * 31),
                  megakernel=True)

    def test_digital_compile_rejects_forced_megakernel(self):
        """megakernel=True must raise in digital mode too (no analog plan
        exists), not silently run the reference path."""
        from repro import api

        p = {"a": _mk(seed=1, out_dim=256), "b": _mk(seed=2)}
        spec = api.ModuleSpec(name="2fc", kind="stack", layers=(
            api.LayerSpec("a", 256, 256), api.LayerSpec("b", 256, 64),
        ))
        model = api.compile(spec, p, AnalogConfig(mode="digital"))
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        model.apply(x, megakernel=False)          # reference path fine
        with pytest.raises(ValueError, match="megakernel=True"):
            model.apply(x, megakernel=True)

    def test_uniform_chain_and_batch_shapes(self):
        """Megakernel on a flatten-free chain: unbatched and multi-dim
        batches run bit-exact vs the per-layer replay (which itself
        flattens only trailing dims - the old reshape mangled these)."""
        from repro.exec.lower import lower_stack

        ps = [_mk(seed=i, in_dim=256, out_dim=256) for i in range(3)]
        plan = lower_stack(
            ps, AnalogConfig(noise=NOISELESS),
            epilogues=["relu_shift", "relu_shift", "none"],
            input_domain="codes",
        )
        x = jnp.round(jax.random.uniform(KEY, (2, 3, 256)) * 31)
        y = E.run(plan, x, megakernel=False)
        assert y.shape == (2, 3, 256)
        np.testing.assert_array_equal(
            np.asarray(E.run(plan, x, megakernel=True)), np.asarray(y)
        )
        np.testing.assert_array_equal(                 # unbatched [K]
            np.asarray(E.run(plan, x[0, 0], megakernel=True)),
            np.asarray(y[0, 0]),
        )


class TestInputDomain:
    def test_mixed_plan_first_layer_relu_shift_takes_float_input(self):
        """THE BUG: a mixed plan whose first layer emits relu_shift but is
        fed float features used to silently treat the input as codes
        (skipping quantization).  An explicit input_domain='float' baked
        at lower time quantizes it like any float activation."""
        from repro.exec.lower import lower_stack

        ps = [_mk(seed=0, out_dim=256), _mk(seed=1)]
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        legacy = lower_stack(ps, SPLIT_CFG, epilogues=["relu_shift", "none"])
        explicit = lower_stack(ps, SPLIT_CFG,
                               epilogues=["relu_shift", "none"],
                               input_domain="float")
        assert legacy.input_domain == "codes"      # documented legacy guess
        assert explicit.input_domain == "float"
        want = E.run(legacy, x, x_is_codes=False)  # the correct treatment
        np.testing.assert_array_equal(
            np.asarray(E.run(explicit, x)), np.asarray(want)
        )
        # and the legacy default really was wrong for float features
        assert not np.array_equal(np.asarray(E.run(legacy, x)),
                                  np.asarray(want))

    def test_code_domain_chain_bakes_codes(self):
        from repro.exec.lower import lower_stack

        ps = [_mk(seed=0, in_dim=256, out_dim=256), _mk(seed=1)]
        plan = lower_stack(ps, SPLIT_CFG,
                           epilogues=["relu_shift", "none"])
        assert plan.input_domain == "codes" and plan.expects_codes
        plan2 = lower_stack(ps, SPLIT_CFG)
        assert plan2.input_domain == "float" and not plan2.expects_codes

    def test_unknown_input_domain_rejected(self):
        from repro.exec.lower import lower_stack

        with pytest.raises(ValueError, match="input_domain"):
            lower_stack([_mk()], SPLIT_CFG, input_domain="5bit")


class TestFlattenOut:
    def test_flatten_preserves_leading_batch_dims(self):
        """flatten_out merges ONLY the trailing position axis into the
        feature axis: multi-dim batches and unbatched inputs survive
        (the old `h.reshape(h.shape[0], -1)` mangled both)."""
        from repro.exec.lower import lower_stack

        ps = [_mk(seed=0, in_dim=128, out_dim=64),
              _mk(seed=1, in_dim=256, out_dim=32)]
        plan = lower_stack(ps, AnalogConfig(noise=NOISELESS),
                           flatten_outs=[True, False])
        x = jax.random.normal(KEY, (5, 4, 128)) * 0.2   # 4 positions x 64
        y = E.run(plan, x)
        assert y.shape == (5, 32)
        x4 = jnp.broadcast_to(x, (2, 5, 4, 128))
        y4 = E.run(plan, x4)
        assert y4.shape == (2, 5, 32)
        np.testing.assert_array_equal(np.asarray(y4[0]), np.asarray(y))
        y1 = E.run(plan, x[0])                          # unbatched [4, 128]
        assert y1.shape == (32,)
        # compare against the same rows as a 1-batch (same dynamic
        # activation calibration abs-max, so bit-identical values)
        np.testing.assert_array_equal(np.asarray(y1),
                                      np.asarray(E.run(plan, x[:1])[0]))


class TestEpiloguePinning:
    def test_ste_epilogue_matches_in_kernel_and_ref(self):
        """The three ADC-epilogue implementations (elementwise STE, the
        in-kernel Pallas epilogue, the jnp oracle) are pinned to the same
        floor-shift semantics - including the negative-code edge, where
        the ReLU must clamp BEFORE the shift (a float divide of a
        negative code would round toward zero, not floor)."""
        from repro.exec.run import _epilogue_ste
        from repro.kernels.analog_mvm import _apply_epilogue

        y = jnp.asarray([-300.0, -17.0, -1.0, 0.0, 1.0, 7.0, 8.0, 9.0,
                         63.0, 64.0, 255.0, 256.0, 1000.0])
        for shift in (0, 1, 3, 5):
            epi = ("relu_shift", shift)
            a = np.asarray(_epilogue_ste(y, shift))
            b = np.asarray(_apply_epilogue(y, epi))
            c = np.asarray(R.adc_epilogue_ref(y, epi))
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
            # floor-shift: 5-bit codes, negatives clamp to 0
            want = np.clip(np.floor(np.maximum(np.asarray(y), 0.0)
                                    / (1 << shift)), 0.0, 31.0)
            np.testing.assert_array_equal(a, want)


class TestLowerFusedStaticCalib:
    def test_differing_static_scales_rejected(self):
        ps = [_mk(seed=i, out_dim=32) for i in range(3)]
        ps[1] = dict(ps[1], a_scale=ps[1]["a_scale"] * 7.0)
        static = AnalogConfig(noise=NOISELESS, act_calib="static")
        from repro.exec.lower import lower_fused

        with pytest.raises(ValueError, match="a_scale"):
            lower_fused(ps, static)
        # identical scales fuse fine; dynamic calibration never checks
        lower_fused([ps[0], ps[2]], static)
        lower_fused(ps, AnalogConfig(noise=NOISELESS))


class TestHILGradientParity:
    def test_pallas_vs_jnp_gradients(self):
        """Satellite: the Pallas-dispatch custom VJP and the pure-jnp
        faithful path must produce the SAME HIL gradients (frozen gain).
        NOISELESS params keep the integer arithmetic exact so the parity
        is not blurred by fp32 rounding-order differences."""
        p = _mk()
        x = jax.random.normal(KEY, (16, 256)) * 0.3
        cfg = AnalogConfig(signed_input="none")

        def loss(params, use_pallas):
            y = apply_linear(
                params, jnp.abs(x), cfg.replace(use_pallas=use_pallas)
            )
            return (y ** 2).mean()

        g_jnp = jax.grad(loss)(p, False)
        g_pl = jax.grad(loss)(p, True)
        np.testing.assert_allclose(
            np.asarray(g_jnp["w"]), np.asarray(g_pl["w"]),
            rtol=1e-5, atol=1e-7,
        )
        # gain is frozen calibration state on BOTH paths (paper §III-B)
        np.testing.assert_array_equal(np.asarray(g_jnp["gain"]),
                                      np.asarray(g_pl["gain"]))

    def test_gain_frozen_in_kernel_bwd(self):
        a = jnp.round(jax.random.uniform(KEY, (8, 256)) * 31)
        w = jnp.round(jax.random.normal(KEY, (256, 32)) * 10)
        gain = jnp.full((32,), 0.02)

        def f(gain_):
            return ops.analog_mvm(a, w, gain_, None, 128, True, False).sum()

        g = jax.grad(f)(gain)
        np.testing.assert_array_equal(np.asarray(g), np.zeros_like(g))

    def test_split_fused_gradient_matches_two_pass(self):
        p = _mk()
        x = jax.random.normal(KEY, (16, 256)) * 0.3

        def loss(params, fused):
            y = apply_linear(
                params, x, SPLIT_CFG.replace(fused_split=fused)
            )
            return (y ** 2).mean()

        g_fused = jax.grad(loss)(p, True)
        g_two = jax.grad(loss)(p, False)
        np.testing.assert_allclose(
            np.asarray(g_fused["w"]), np.asarray(g_two["w"]),
            rtol=1e-5, atol=1e-7,
        )
