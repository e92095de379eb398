"""Tests for the `repro.api` front door (ISSUE 2): spec -> compile ->
CompiledModel, whole-block plans with fused QKV dispatch groups,
mesh-sharded pre-lowering (plan leaves as first-class shardables), the
HIL-through-compile train contract, and the deprecation shims over the
legacy entrypoints (bit-exact by construction)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.exec as E
from repro import api
from repro.configs.base import ArchConfig, RunConfig
from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.noise import NOISELESS, NoiseConfig
from repro.distributed import sharding as shd
from repro.exec.run import dispatch_count, reset_dispatch_count
from repro.models import ecg as ECG
from repro.models import transformer as T
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(7)
ACFG = AnalogConfig(noise=NOISELESS)

TINY = ArchConfig("t-api", "dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=256)


def _mk(in_dim=256, out_dim=64, noise=NOISELESS, seed=0):
    return analog_linear_init(
        jax.random.PRNGKey(seed), in_dim, out_dim, noise=noise
    )


def _lm_batch(cfg, b=2, s=8, seed=1):
    k = jax.random.PRNGKey(seed)
    return {"tokens": jax.random.randint(k, (b, s), 0, cfg.vocab_size)}


@pytest.fixture()
def mesh11():
    with shd.use_mesh(make_mesh((1, 1), ("data", "model"))) as m:
        yield m


class TestCompileStack:
    def test_linear_spec_compile_apply(self):
        p = _mk()
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        model = api.compile(api.linear_spec(256, 64), p, ACFG)
        y = model.apply(x)
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(api.apply_linear(p, x, ACFG))
        )
        # the compiled artifact is a replayable AnalogPlan
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(E.run(model.lower(), x))
        )

    def test_shape_mismatch_rejected(self):
        with pytest.raises(ValueError, match="declares"):
            api.compile(api.linear_spec(128, 64), _mk(), ACFG)

    def test_digital_stack_matches_analog_contract(self):
        """Digital compile runs the reference path with the same
        inter-layer ReLU glue the plan executor uses."""
        ps = {"a": _mk(seed=1, out_dim=256), "b": _mk(seed=2)}
        spec = api.ModuleSpec(name="2fc", kind="stack", layers=(
            api.LayerSpec("a", 256, 256), api.LayerSpec("b", 256, 64),
        ))
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        y = api.compile(spec, ps, AnalogConfig(mode="digital")).apply(x)
        want = jnp.maximum(
            x @ ps["a"]["w"], 0.0
        ) @ ps["b"]["w"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_relower_tracks_new_params(self):
        p = _mk()
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        model = api.compile(api.linear_spec(256, 64), p, ACFG)
        p2 = dict(p, w=p["w"] * 2.0)
        y2 = model.relower(p2).apply(x)
        assert not np.array_equal(np.asarray(model.apply(x)),
                                  np.asarray(y2))


class TestCompileTree:
    def test_lm_plan_bit_exact_and_fewer_dispatches(self):
        """The pre-lowered LM tree (stacked layers lowered under vmap,
        QKV fused into one dispatch group) computes exactly the per-call
        function with fewer analog dispatches per trace."""
        params = T.lm_init(KEY, TINY)
        run = RunConfig(analog=AnalogConfig(mode="analog_faithful"))
        batch = _lm_batch(TINY)
        reset_dispatch_count()
        want, _, _ = T.lm_apply(params, batch, TINY, run)
        n_raw = dispatch_count()

        model = api.compile(T.lm_module_spec(TINY, params), params, run)
        lowered = model.lower()
        g0 = lowered["layers"]["l0"]
        assert "_qkv_plan" in g0["attn"] and "_plan" in g0["attn"]["wo"]
        assert "_plan" not in g0["attn"]["wq"]     # fused group elides it
        reset_dispatch_count()
        got, _, _ = model.apply(batch)
        n_plan = dispatch_count()
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        # per group: QKV 3 -> 1; totals include wo + mlp + lm_head
        assert n_plan < n_raw

    def test_stacked_plans_flow_through_scan(self):
        """Scan-stacked layer plans carry a leading group axis on every
        array leaf (the legacy prelower_tree skipped stacked layers)."""
        params = T.lm_init(KEY, TINY)
        lowered = api.lower_tree(params, ACFG)
        lp = lowered["layers"]["l0"]["mlp"]["up"]["_plan"]
        g = params["layers"]["l0"]["mlp"]["up"]["w"].shape[0]
        assert lp.w_eff.shape[0] == g and lp.w_eff.ndim == 3

    def test_digital_mode_is_identity(self):
        params = T.lm_init(KEY, TINY)
        assert api.lower_tree(params, AnalogConfig(mode="digital")) \
            is params

    def test_hil_gradients_reach_masters_through_compile(self):
        """compile() inside the differentiated step: STE gradients flow
        through the baked plans to the float masters (incl. the fused
        QKV group)."""
        params = T.lm_init(KEY, TINY)
        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        spec = T.lm_module_spec(TINY, params)
        batch = dict(_lm_batch(TINY),
                     labels=_lm_batch(TINY, seed=2)["tokens"])

        def loss(p):
            model = api.compile(spec, p, run)
            return T.lm_loss(model.lower(), batch, TINY, run)[0]

        g = jax.grad(loss)(params)
        gq = np.asarray(g["layers"]["l0"]["attn"]["wq"]["w"])
        assert np.isfinite(gq).all() and np.abs(gq).max() > 0


class TestFusedLowering:
    def test_lower_fused_bit_exact_vs_per_layer(self):
        """One fused dispatch over concatenated columns == the per-layer
        dispatches, bit for bit (column independence of the ADC chain)."""
        cfg = AnalogConfig(noise=NoiseConfig())       # fpn on
        ps = [analog_linear_init(jax.random.PRNGKey(i), 256, 64,
                                 noise=NoiseConfig()) for i in range(3)]
        x = jax.random.normal(KEY, (4, 256)) * 0.3
        from repro.exec.lower import lower_fused
        from repro.exec.run import run_layer

        fused = lower_fused(ps, cfg)
        y = run_layer(fused, x, cfg)
        want = jnp.concatenate(
            [api.apply_linear(p, x, cfg) for p in ps], axis=-1
        )
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))

    def test_lower_fused_rejects_mixed_input_dims(self):
        from repro.exec.lower import lower_fused

        with pytest.raises(ValueError, match="input dim"):
            lower_fused([_mk(256, 32), _mk(128, 32, seed=1)], ACFG)

    def test_fused_plan_ignored_under_static_calib(self):
        """A fused plan bakes ONE static a_scale (wq's), so a static-calib
        call site must fall back to per-layer lowering rather than
        quantizing k/v with the wrong scale."""
        from repro.models import attention as A

        p = A.attention_init(KEY, 64, 4, 2, 16, noise=NOISELESS)
        # diverge the static scales so misuse would be visible
        p["wk"] = dict(p["wk"], a_scale=p["wk"]["a_scale"] * 7.0)
        x = jax.random.normal(KEY, (2, 8, 64)) * 0.3
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None],
                               (2, 8))
        static = ACFG.replace(act_calib="static")
        kw = dict(positions=pos, acfg=static, n_heads=4, n_kv_heads=2,
                  head_dim=16, rope_theta=1e4)
        want, _ = A.attention_apply(p, x, **kw)
        lowered = api.lower_tree(p, ACFG)     # fused under dynamic calib
        got, _ = A.attention_apply(lowered, x, **kw)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_attention_fused_plan_matches_per_layer(self):
        from repro.models import attention as A

        p = A.attention_init(KEY, 64, 4, 2, 16, noise=NOISELESS)
        x = jax.random.normal(KEY, (2, 8, 64)) * 0.3
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None],
                               (2, 8))
        kw = dict(positions=pos, acfg=ACFG, n_heads=4, n_kv_heads=2,
                  head_dim=16, rope_theta=1e4)
        want, _ = A.attention_apply(p, x, **kw)
        lowered = api.lower_tree(p, ACFG)
        reset_dispatch_count()
        got, _ = A.attention_apply(lowered, x, **kw)
        assert dispatch_count() == 2          # qkv fused + wo
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


class TestMeshShardedPlans:
    def test_sharding_specs_cover_plan_leaves(self, mesh11):
        """plan_specs_like mirrors the lowered tree's structure, so every
        plan leaf resolves to a NamedSharding (the thing the deleted
        shd_mesh_absent() guard used to make impossible)."""
        params = T.lm_init(KEY, TINY)
        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        model = api.compile(T.lm_module_spec(TINY, params), params, run)
        specs = model.sharding_specs()
        shardings = shd.sharding_like(specs, model.lower())
        n_lowered = len(jax.tree.leaves(model.lower()))
        assert len(jax.tree.leaves(
            shardings, is_leaf=lambda x: x is None
        )) >= n_lowered
        for s in jax.tree.leaves(shardings):
            assert hasattr(s, "mesh")

    def test_sharded_compiled_model_bit_exact(self, mesh11):
        """1-device mesh: the sharded pre-lowered tree computes exactly
        the unsharded plan path."""
        params = T.lm_init(KEY, TINY)
        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        batch = _lm_batch(TINY)
        model = api.compile(T.lm_module_spec(TINY, params), params, run)
        want, _, _ = model.apply(batch)
        sharded = jax.device_put(
            model.lower(),
            shd.sharding_like(model.sharding_specs(), model.lower()),
        )
        got, _, _ = T.lm_apply(sharded, batch, TINY, run)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_serve_engine_prelowers_under_mesh(self, mesh11):
        """ServeEngine(prelower=True) with a mesh active: pre-lowered
        plans replay (no re-lowering/re-tracing between batches - the
        dispatch counter is trace-time) and outputs are bit-exact vs the
        unsharded engine."""
        from repro.serve.engine import Request, ServeEngine

        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        params = T.lm_init(KEY, TINY)
        prompt = np.arange(6) % TINY.vocab_size
        eng = ServeEngine(TINY, run, params, batch_size=2, max_len=32)
        assert "_qkv_plan" in eng.params["layers"]["l0"]["attn"]
        r1 = eng.serve([Request(0, prompt, 4)])[0]
        n1 = dispatch_count()
        r2 = eng.serve([Request(1, prompt, 4)])[0]
        assert dispatch_count() == n1        # pure replay
        np.testing.assert_array_equal(r1.output, r2.output)

    def test_serve_engine_mesh_matches_no_mesh(self):
        from repro.serve.engine import Request, ServeEngine

        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        params = T.lm_init(KEY, TINY)
        prompt = np.arange(6) % TINY.vocab_size
        r_plain = ServeEngine(TINY, run, params, batch_size=2, max_len=32) \
            .serve([Request(0, prompt, 4)])[0]
        with shd.use_mesh(make_mesh((1, 1), ("data", "model"))):
            r_mesh = ServeEngine(TINY, run, params, batch_size=2,
                                 max_len=32) \
                .serve([Request(0, prompt, 4)])[0]
        np.testing.assert_array_equal(r_plain.output, r_mesh.output)


class TestMegakernelKnob:
    """CompiledModel.apply - the api surface of the whole-plan
    megakernel, against the layer-by-layer replay of the same plan."""

    def _model(self, acfg=None):
        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(jax.random.PRNGKey(0), cfg)
        spec = ECG.ecg_module_spec(cfg, epilogue="relu_shift")
        model = api.compile(spec, params, acfg or AnalogConfig())
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(1), (4, 2, 126)) * 31
        )
        return model, x

    def test_compiled_ecg_chain_is_megakernel_eligible(self):
        model, x = self._model()
        plan = model.lower()
        assert plan.mega is not None
        assert plan.input_domain == "codes"
        assert plan.expected_dispatches == 3

    def test_apply_knob_bit_exact_and_single_dispatch(self):
        model, x = self._model()
        cfg = ECG.ECGConfig()
        cols = ECG._im2col(x, cfg.conv_taps, cfg.conv_stride)
        reset_dispatch_count()
        model.apply(x)
        assert dispatch_count() == 1                  # ONE analog program
        reset_dispatch_count()
        y_mk = model.run_stack(cols)
        assert dispatch_count() == 1
        reset_dispatch_count()
        y_per = E.run_layers(model.lower(), cols)
        assert dispatch_count() == model.lower().expected_dispatches == 3
        np.testing.assert_array_equal(np.asarray(y_mk), np.asarray(y_per))

    def test_megakernel_runs_no_three_pass_dot(self):
        """The ECG chain's megakernel keeps its own in-kernel dots: a
        compile of the chain with the Pallas kernels traces no
        ``code_dot``."""
        from repro.obs import metrics as obs_metrics

        model, x = self._model(AnalogConfig(use_pallas=True))
        obs_metrics.reset_metrics()
        jax.block_until_ready(jax.jit(model.apply).lower(x).compile()(x))
        assert obs_metrics.counter("exec.run.megakernel").value > 0
        assert obs_metrics.counter("analog.mvm.bf16x3_dots").value == 0

    def test_ecg_layers_carry_named_scopes(self):
        """The chain's stages are named in the lowered program's op
        metadata, where a device trace can attribute time to them."""
        model, x = self._model()
        text = jax.jit(model.apply).lower(x).as_text(debug_info=True)
        for scope in ("ecg.im2col", "ecg.analog_chain", "ecg.class_pool"):
            assert scope in text, scope

    def test_float_glue_spec_not_packed(self):
        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(jax.random.PRNGKey(0), cfg)
        model = api.compile(ECG.ecg_module_spec(cfg), params, AnalogConfig())
        plan = model.lower()
        assert plan.mega is None and plan.input_domain == "float"

    def test_stack_sharding_specs_cover_mega_leaves(self, mesh11):
        """The stack spec tree mirrors the plan INCLUDING the megakernel
        packing (replicated), so a compiled code-domain model device_puts
        under a mesh like any other plan."""
        model, x = self._model()
        specs = model.sharding_specs()
        plan = model.lower()
        shardings = shd.sharding_like(specs, plan)
        assert len(jax.tree.leaves(shardings)) == len(jax.tree.leaves(plan))
        sharded = jax.device_put(plan, shardings)
        import repro.exec as E2

        np.testing.assert_array_equal(
            np.asarray(E2.run(sharded, ECG._im2col(x, 64, 2))),
            np.asarray(E2.run(plan, ECG._im2col(x, 64, 2))),
        )


class TestDeprecationShims:
    def test_analog_linear_apply_warns_and_matches(self):
        from repro.core.analog import analog_linear_apply

        p = _mk()
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        with pytest.warns(DeprecationWarning, match="analog_linear_apply"):
            y_old = analog_linear_apply(p, x, ACFG)
        np.testing.assert_array_equal(
            np.asarray(y_old), np.asarray(api.apply_linear(p, x, ACFG))
        )

    def test_linear_lower_warns_and_matches(self):
        from repro.models.layers import linear_lower

        p = _mk()
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        with pytest.warns(DeprecationWarning, match="linear_lower"):
            plan_old = linear_lower(p, ACFG)
        plan_new = api.compile(api.linear_spec(256, 64), p, ACFG).lower()
        np.testing.assert_array_equal(
            np.asarray(E.run(plan_old, x)), np.asarray(E.run(plan_new, x))
        )

    def test_ecg_lower_warns_and_matches(self):
        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(jax.random.PRNGKey(0), cfg)
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(1), (4, 2, 126)) * 31
        )
        acfg = AnalogConfig()
        with pytest.warns(DeprecationWarning, match="ecg_lower"):
            plan_old = ECG.ecg_lower(params, acfg, cfg)
        model = api.compile(ECG.ecg_module_spec(cfg), params, acfg)
        np.testing.assert_array_equal(
            np.asarray(ECG.ecg_apply_plan(plan_old, x, cfg)),
            np.asarray(model.apply(x)),
        )

    def test_prelower_tree_warns_and_matches(self):
        from repro.exec.lower import prelower_tree

        p = _mk()
        x = jax.random.normal(KEY, (4, 256)) * 0.2
        with pytest.warns(DeprecationWarning, match="prelower_tree"):
            old = prelower_tree({"layer": p}, ACFG)
        new = api.lower_tree({"layer": p}, ACFG)
        assert "_plan" in old["layer"] and "_plan" in new["layer"]
        np.testing.assert_array_equal(
            np.asarray(api.apply_linear(old["layer"], x, ACFG)),
            np.asarray(api.apply_linear(new["layer"], x, ACFG)),
        )

    def test_internal_paths_do_not_warn(self):
        """The model zoo routes through the api directly - no deprecation
        noise from ordinary forwards."""
        params = T.lm_init(KEY, TINY)
        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            T.lm_apply(params, _lm_batch(TINY), TINY, run)
            api.compile(T.lm_module_spec(TINY, params), params, run)
