"""Tests for first-class fusion groups (ISSUE 5): GroupSpec declaration +
validation, the three group kinds (column_concat / batch_concat /
expert_stack) lowered through the spec-driven front door, bit-exactness of
every fused replay vs its unfused baseline across faithful/fast x
pallas/jnp, the ``"_qkv_plan"`` deprecation shim, group sharding specs,
and the drift hot-swap over group plans."""
import dataclasses

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
from repro.exec.lower import lowering_count, reset_lowering_count
from repro.exec.run import (
    dispatch_count,
    reset_dispatch_count,
    run_batch_concat,
    run_group,
)
from repro.models import attention as A
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import transformer as T
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(7)
ACFG = AnalogConfig(noise=NOISELESS)
MODES = [("analog_faithful", False), ("analog_faithful", True),
         ("analog_fast", False), ("analog_fast", True)]


def _cfg(mode, pallas, **kw):
    return AnalogConfig(mode=mode, use_pallas=pallas, noise=NoiseConfig(),
                        **kw)


@pytest.fixture()
def mesh11():
    with shd.use_mesh(make_mesh((1, 1), ("data", "model"))) as m:
        yield m


# ---------------------------------------------------------------- GroupSpec
class TestGroupSpecValidation:
    def _layers(self):
        return (
            api.LayerSpec("a", 64, 32),
            api.LayerSpec("b", 64, 32),
            api.LayerSpec("c", 128, 32),
            api.LayerSpec("e", 64, 32, stacked=4),
            api.LayerSpec("e2", 64, 32, stacked=4),
        )

    def _spec(self, groups):
        return api.ModuleSpec(name="t", kind="tree",
                              layers=self._layers(), groups=groups)

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown kind.*valid kinds"):
            self._spec((api.GroupSpec("g", "row_concat", ("a", "b")),))

    def test_unknown_member_rejected(self):
        with pytest.raises(ValueError, match="not declared layers"):
            self._spec((
                api.GroupSpec("g", "column_concat", ("a", "nope")),
            ))

    def test_column_concat_mismatched_in_dim_rejected(self):
        with pytest.raises(ValueError, match="agree on in_dim"):
            self._spec((api.GroupSpec("g", "column_concat", ("a", "c")),))

    def test_batch_concat_mismatched_geometry_rejected(self):
        with pytest.raises(ValueError, match="weight geometry"):
            self._spec((api.GroupSpec("g", "batch_concat", ("a", "c")),))

    def test_code_domain_member_epilogue_rejected(self):
        layers = (api.LayerSpec("a", 64, 32, epilogue="relu_shift"),
                  api.LayerSpec("b", 64, 32))
        with pytest.raises(ValueError, match="epilogue"):
            api.ModuleSpec(name="t", kind="tree", layers=layers,
                           groups=(api.GroupSpec(
                               "g", "column_concat", ("a", "b")),))

    def test_expert_stack_needs_stacked_member(self):
        with pytest.raises(ValueError, match="stacked"):
            self._spec((api.GroupSpec("g", "expert_stack", ("a",)),))
        with pytest.raises(ValueError, match="one expert_stack group"):
            self._spec((api.GroupSpec("g", "expert_stack", ("e", "e2")),))

    def test_non_sibling_members_rejected(self):
        layers = (api.LayerSpec("x.a", 64, 32),
                  api.LayerSpec("y.b", 64, 32))
        with pytest.raises(ValueError, match="siblings"):
            api.ModuleSpec(name="t", kind="tree", layers=layers,
                           groups=(api.GroupSpec(
                               "g", "column_concat", ("x.a", "y.b")),))

    def test_groups_rejected_on_stack_specs(self):
        with pytest.raises(ValueError, match="tree-spec feature"):
            api.ModuleSpec(name="t", kind="stack", layers=self._layers(),
                           groups=(api.GroupSpec(
                               "g", "column_concat", ("a", "b")),))

    def test_legacy_group_tags_normalize_to_column_concat(self):
        spec = api.ModuleSpec(name="t", kind="tree", layers=(
            api.LayerSpec("a", 64, 32, group="g"),
            api.LayerSpec("b", 64, 48, group="g"),
        ))
        assert spec.group("g").kind == "column_concat"
        assert spec.group("g").members == ("a", "b")

    def test_spec_accessors_are_immutable_and_actionable(self):
        """Satellite bugfix: group membership comes back as tuples (the
        old ``groups()`` leaked mutable lists from the frozen spec) and
        ``layer()``/``group()`` errors name what IS declared."""
        spec = self._spec((api.GroupSpec("g", "batch_concat", ("a", "b")),))
        gm = spec.group_members()
        assert gm == {"g": ("a", "b")}
        assert isinstance(gm["g"], tuple) and isinstance(spec.groups, tuple)
        gm["g"] = ()            # mutating the returned dict ...
        assert spec.group_members() == {"g": ("a", "b")}  # ... is inert
        with pytest.raises(KeyError, match="declared layers: a, b, c, e"):
            spec.layer("missing")
        with pytest.raises(KeyError, match="declared groups: g"):
            spec.group("missing")

    def test_layer_error_lists_names(self):
        spec = self._spec(())
        with pytest.raises(KeyError, match="a, b, c, e, e2"):
            spec.layer("missing")
        with pytest.raises(KeyError, match=r"declared groups: \(none\)"):
            spec.group("missing")


# ------------------------------------------------------------- batch_concat
class TestBatchConcat:
    def _members(self, n=4, d=64, noise=NOISELESS):
        return [analog_linear_init(jax.random.PRNGKey(i), d, d, noise=noise)
                for i in range(n)]

    def _inputs(self, n=4, d=64, shape=(2, 6)):
        return [jax.random.normal(jax.random.PRNGKey(10 + i),
                                  shape + (d,)) * (0.2 + 0.1 * i)
                for i in range(n)]

    @pytest.mark.parametrize("mode,pallas", MODES)
    @pytest.mark.parametrize("act_calib", ["dynamic", "static"])
    def test_bit_exact_vs_solo_dispatches(self, mode, pallas, act_calib):
        """ONE batch_concat dispatch == the 4 solo dispatches, bit for
        bit, under both calibration modes (each member's rows encode at
        that member's own activation scale)."""
        cfg = _cfg(mode, pallas, act_calib=act_calib)
        ps = self._members(noise=NoiseConfig())
        xs = self._inputs()
        fused = E.lower_batch_concat(ps, cfg)
        gp = E.GroupPlan("batch_concat", fused, ("a", "b", "c", "d"),
                         (64,) * 4)
        reset_dispatch_count()
        got = run_batch_concat(gp, xs, cfg)
        assert dispatch_count() == 1
        reset_dispatch_count()
        want = [E.run_layer(E.lower_layer(p, cfg), x, cfg)
                for p, x in zip(ps, xs)]
        assert dispatch_count() == 4
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_rwkv_replays_as_one_dispatch(self):
        """Acceptance: r/k/v/g 4 -> 1, dispatch-count-verified, bit-exact
        vs the unfused per-call block."""
        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, d)) * 0.3
        reset_dispatch_count()
        want, _ = R.rwkv_apply(params, x, acfg=ACFG, n_heads=heads)
        n_solo = dispatch_count()
        model = api.compile(R.rwkv_module_spec(d, heads), params, ACFG)
        reset_dispatch_count()
        got, _ = model.apply(x)
        n_fused = dispatch_count()
        # r/k/v/g collapse 4 -> 1; wo stays solo
        assert (n_solo, n_fused) == (5, 2)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_group_calibrated_static_matches_solo(self):
        """share_group_input_scale extends to batch_concat: the group
        encodes at ONE shared input LSB, still bit-exact vs solo members
        lowered from the same snapshot (they carry the same
        a_scale_in)."""
        from repro import calib

        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads)
        names = ["wr", "wk", "wv", "wg"]
        static = ACFG.replace(act_calib="static")
        snap = calib.share_group_input_scale(
            calib.CalibrationSnapshot(), names,
            scales=[params[n]["a_scale"] * (1 + i)
                    for i, n in enumerate(names)],
        )
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, d)) * 0.3
        model = api.compile(R.rwkv_module_spec(d, heads), params, static,
                            calibration=snap)
        got, _ = model.apply(x)
        per_layer = {
            k: (dict(v, _plan=E.lower_layer(
                params[k], static, calib=snap.layer(k)))
                if k in names else v)
            for k, v in params.items()
        }
        want, _ = R.rwkv_apply(per_layer, x, acfg=static, n_heads=heads)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_cfg_mismatch_falls_back_to_solo(self):
        """A baked group whose static attrs disagree with the call-site
        cfg must not replay (solo per-call lowering takes over)."""
        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads)
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 6, d))) \
            * 0.3
        lowered = api.compile(
            R.rwkv_module_spec(d, heads), params, ACFG
        ).lower()                              # bakes "split"
        cfg_none = ACFG.replace(signed_input="none")
        got, _ = R.rwkv_apply(lowered, x, acfg=cfg_none, n_heads=heads)
        want, _ = R.rwkv_apply(params, x, acfg=cfg_none, n_heads=heads)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_custom_group_name_still_fuses(self):
        """Consumers resolve groups by (kind, members), not by magic
        name: a batch_concat group under any name replays fused."""
        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads)
        spec = R.rwkv_module_spec(d, heads)
        renamed = dataclasses.replace(
            spec,
            layers=tuple(dataclasses.replace(l, group=None)
                         for l in spec.layers),
            groups=(api.GroupSpec("projections", "batch_concat",
                                  ("wr", "wk", "wv", "wg")),),
        )
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, d)) * 0.3
        model = api.compile(renamed, params, ACFG)
        reset_dispatch_count()
        got, _ = model.apply(x)
        assert dispatch_count() == 2           # still 4 -> 1 (+ wo)
        want, _ = R.rwkv_apply(params, x, acfg=ACFG, n_heads=heads)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_wrong_kind_group_falls_back_to_solo(self):
        """A spec-valid column_concat group over the rwkv projections
        (same in_dim) must not be fed to the batch_concat replay - the
        consumer matches on kind and falls back to solo dispatches."""
        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads)
        spec = R.rwkv_module_spec(d, heads)
        wrong = dataclasses.replace(
            spec,
            layers=tuple(dataclasses.replace(l, group=None)
                         for l in spec.layers),
            groups=(api.GroupSpec("rkvg", "column_concat",
                                  ("wr", "wk", "wv", "wg")),),
        )
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, d)) * 0.3
        got, _ = api.compile(wrong, params, ACFG).apply(x)
        want, _ = R.rwkv_apply(params, x, acfg=ACFG, n_heads=heads)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_lm_rwkv_arch_compiles_groups_through_scan(self):
        """Scan-stacked RWKV blocks: the batch_concat group lowers under
        vmap (member axis after the stack prefix) and replays bit-exact
        through jax.lax.scan."""
        cfg = ArchConfig("t-rwkv", "ssm", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=4, d_ff=128,
                         vocab_size=256, block="rwkv")
        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        params = T.lm_init(KEY, cfg)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)}
        want, _, _ = T.lm_apply(params, batch, cfg, run)
        model = api.compile(T.lm_module_spec(cfg, params), params, run)
        lo = model.lower()["layers"]["l0"]["rwkv"]
        assert lo["_groups"]["rkvg"].fused.w_eff.ndim == 4
        assert "_plan" not in lo["wr"]         # fused members elided
        got, _, _ = model.apply(batch)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# ------------------------------------------------------------- expert_stack
class TestExpertStack:
    @pytest.mark.parametrize("mode,pallas", MODES)
    def test_prelowered_bit_exact_vs_percall(self, mode, pallas):
        cfg = _cfg(mode, pallas)
        e, c, k, n = 4, 6, 96, 32
        w = jax.random.normal(jax.random.PRNGKey(3), (e, k, n)) * 0.1
        xe = jax.random.normal(jax.random.PRNGKey(4), (e, c, k)) * 0.3
        plan = E.lower_expert_stack(w, cfg)
        gp = E.GroupPlan("expert_stack", plan, ("up",), (n,))
        got = E.run_expert_stack(gp, xe, cfg)
        want = M._analog_expert_matmul(xe, w, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_moe_module_spec_matches_percall(self):
        d, ff, e, top_k = 64, 32, 4, 2
        params = M.moe_init(KEY, d, ff, e)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, d)) * 0.3
        want, aux_w = M.moe_apply(params, x, acfg=ACFG, top_k=top_k)
        model = api.compile(M.moe_module_spec(d, ff, e, top_k=top_k),
                            params, ACFG)
        got, aux_g = model.apply(x)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        np.testing.assert_array_equal(np.asarray(aux_w), np.asarray(aux_g))
        for name in ("up", "gate", "down"):
            gp = model.group_plan(name)
            assert gp is not None and gp.kind == "expert_stack"
            assert gp.fused.w_eff.shape[0] == e

    def test_zero_lowerings_per_call_under_cached_jit(self):
        """Acceptance: MoE experts lower ZERO times per call - the
        expert bake happens at compile() time; cached jitted replays
        perform no lowering work, while the per-call path re-derives the
        expert codes/gains inside every traced forward."""
        d, ff, e, top_k = 64, 32, 4, 2
        params = M.moe_init(KEY, d, ff, e)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, d)) * 0.3
        reset_lowering_count()
        model = api.compile(M.moe_module_spec(d, ff, e, top_k=top_k),
                            params, ACFG)
        assert lowering_count() > 0            # baked once, at compile
        lowered = model.lower()

        @jax.jit
        def f(p, x):
            return M.moe_apply(p, x, acfg=ACFG, top_k=top_k)[0]

        f(lowered, x)                          # trace + compile
        reset_lowering_count()
        f(lowered, x)
        f(lowered, x + 0.1)
        assert lowering_count() == 0           # pure replay
        reset_lowering_count()
        jax.make_jaxpr(lambda p, xx: M.moe_apply(
            p, xx, acfg=ACFG, top_k=top_k)[0])(params, x)
        assert lowering_count() > 0            # per-call path re-lowers


# ------------------------------------------------- column_concat + the shim
class TestColumnConcatAndShim:
    def _attn(self):
        p = A.attention_init(KEY, 64, 4, 2, 16, noise=NOISELESS)
        x = jax.random.normal(KEY, (2, 8, 64)) * 0.3
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None],
                               (2, 8))
        kw = dict(positions=pos, acfg=ACFG, n_heads=4, n_kv_heads=2,
                  head_dim=16, rope_theta=1e4)
        return p, x, kw

    def test_qkv_plan_shim_is_the_group_plan(self):
        """The legacy ``"_qkv_plan"`` key survives as a deprecation
        shim: the SAME fused LayerPlan object the qkv GroupPlan carries,
        and a legacy consumer reading only that key replays bit-exact."""
        p, x, kw = self._attn()
        lowered = api.lower_tree(p, ACFG)
        assert lowered["_qkv_plan"] is lowered["_groups"]["qkv"].fused
        want, _ = A.attention_apply(lowered, x, **kw)
        legacy = {k: v for k, v in lowered.items() if k != "_groups"}
        reset_dispatch_count()
        got, _ = A.attention_apply(legacy, x, **kw)
        assert dispatch_count() == 2           # still fused via the alias
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        raw, _ = A.attention_apply(p, x, **kw)
        np.testing.assert_array_equal(np.asarray(raw), np.asarray(got))

    def test_run_group_splits_member_columns(self):
        p, x, kw = self._attn()
        lowered = api.lower_tree(p, ACFG)
        gp = lowered["_groups"]["qkv"]
        assert gp.member_names == ("wq", "wk", "wv")
        q, k, v = run_group(gp, x, ACFG)
        assert q.shape[-1] == 64 and k.shape[-1] == 32
        np.testing.assert_array_equal(
            np.asarray(q),
            np.asarray(E.run_layer(E.lower_layer(p["wq"], ACFG), x, ACFG)),
        )

    def test_group_plan_accessor(self):
        p, x, kw = self._attn()
        spec = api.tree_spec("attn", p)
        assert [g.name for g in spec.groups] == ["qkv"]
        model = api.compile(spec, p, ACFG)
        gp = model.group_plan("qkv")
        assert gp.kind == "column_concat" and gp.member_ns == (64, 32, 32)
        with pytest.raises(KeyError, match="declared groups: qkv"):
            model.group_plan("nope")
        # static calib without group calibration: declared but not fused
        static_model = api.compile(
            spec, p, ACFG.replace(act_calib="static"))
        assert static_model.group_plan("qkv") is None

    def test_digital_compile_has_no_group_plans(self):
        p, _, _ = self._attn()
        model = api.compile(api.tree_spec("attn", p), p,
                            AnalogConfig(mode="digital"))
        assert model.group_plan("qkv") is None


# --------------------------------------------------- sharding + drift swap
class TestGroupShardingAndSwap:
    def test_sharding_specs_cover_group_leaves(self, mesh11):
        """plan_specs_like mirrors _groups entries of all three kinds, so
        every group-plan leaf resolves to a NamedSharding."""
        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads)
        model = api.compile(R.rwkv_module_spec(d, heads), params, ACFG)
        specs = model.sharding_specs()
        shardings = shd.sharding_like(specs, model.lower())
        assert len(jax.tree.leaves(shardings)) == len(
            jax.tree.leaves(model.lower())
        )
        for s in jax.tree.leaves(shardings):
            assert hasattr(s, "mesh")
        pm = M.moe_init(KEY, 64, 32, 4)
        mm = api.compile(M.moe_module_spec(64, 32, 4, top_k=2), pm, ACFG)
        sh = shd.sharding_like(mm.sharding_specs(), mm.lower())
        assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(mm.lower()))

    def test_drift_swap_covers_batch_concat_groups(self):
        """with_calibration hot-swaps member offset tables into a
        batch_concat GroupPlan (stacked member-wise): same treedef, only
        chunk_offset leaves change."""
        from repro.calib.snapshot import (
            CalibrationSnapshot, LayerCalibration,
        )

        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads, noise=NoiseConfig())
        model = api.compile(
            R.rwkv_module_spec(d, heads, noise=NoiseConfig()), params,
            AnalogConfig(noise=NoiseConfig()),
        )
        gp = model.group_plan("rkvg")
        assert gp.fused.chunk_offset is not None
        c = gp.fused.chunk_offset.shape[-2]
        snap = CalibrationSnapshot()
        tables = {}
        for i, name in enumerate(("wr", "wk", "wv", "wg")):
            tables[name] = jax.random.normal(
                jax.random.fold_in(KEY, i), (c, d)) * 0.1
            snap = snap.with_layer(
                name, LayerCalibration(chunk_offset=tables[name]))
        swapped = model.with_calibration(snap)
        assert jax.tree.structure(swapped.lower()) == jax.tree.structure(
            model.lower()
        )
        sgp = swapped.group_plan("rkvg")
        np.testing.assert_array_equal(
            np.asarray(sgp.fused.chunk_offset),
            np.asarray(jnp.stack([tables[n] for n in
                                  ("wr", "wk", "wv", "wg")], axis=0)),
        )
        # weights untouched; expert stacks and uncovered layers kept
        np.testing.assert_array_equal(np.asarray(sgp.fused.w_eff),
                                      np.asarray(gp.fused.w_eff))
