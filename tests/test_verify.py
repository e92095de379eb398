"""Tests for ``repro.verify`` (ISSUE 7): the domain-transition table as
the single source of packing eligibility, per-rule positive/negative
invariant checks on deliberately corrupted plans (each pinpointing the
offending pytree path), the retrace/captured-constant detectors, the AST
lint (including repo-cleanliness), the ``api.compile(..., verify=True)``
/ ``CompiledModel.verify()`` wiring, and hypothesis properties tying
verifier verdicts to ``megakernel_ineligible_reason`` and to ACTUAL
dispatch counts on randomly generated chains."""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.exec as E
from repro import api
from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.noise import NOISELESS, NoiseConfig
from repro.exec.lower import megakernel_ineligible_reason, plan_with_offsets
from repro.exec.run import dispatch_count, reset_dispatch_count
from repro.verify import (
    RULES,
    VerifyError,
    assert_no_retrace,
    captured_constants,
    check,
    run_lint,
    verify_plan,
    verify_spec,
    verify_swap,
)
from repro.verify.lint import lint_source

KEY = jax.random.PRNGKey(0)
ACFG = AnalogConfig(noise=NOISELESS, act_calib="static")
REPO = pathlib.Path(__file__).resolve().parents[1]


def _chain(dims=(32, 48, 40, 24), epilogues=None, acfg=ACFG,
           input_domain="codes", noise=NOISELESS, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(dims) - 1)
    layers = [
        analog_linear_init(k, a, b, noise=noise)
        for k, a, b in zip(ks, dims[:-1], dims[1:])
    ]
    if epilogues is None:
        epilogues = ["relu_shift"] * (len(dims) - 2) + ["none"]
    return E.lower_stack(layers, acfg, epilogues=epilogues,
                         input_domain=input_domain)


def _rule_hits(diags, rule):
    return [d for d in diags if d.rule == rule]


# ------------------------------------------------------------------ registry
class TestRegistry:
    def test_rules_registered_with_docs_and_tiers(self):
        want_cheap = {"chunk-alignment", "domain-chain", "pack-consistency",
                      "dispatch-count", "group-layout",
                      "calibration-compat", "placement-coverage",
                      "fleet-calibration-compat"}
        want_full = {"drift-swap", "sharding-specs", "packed-layout"}
        assert set(RULES) == want_cheap | want_full
        for r in RULES.values():
            assert r.doc, r.id
            assert r.cheap == (r.id in want_cheap), r.id

    def test_clean_plan_verifies_empty(self):
        assert verify_plan(_chain()) == ()

    def test_check_raises_with_diagnostics(self):
        plan = dataclasses.replace(_chain(), mega=None)
        diags = verify_plan(plan)
        with pytest.raises(VerifyError, match="pack-consistency") as ei:
            check(diags)
        assert ei.value.diagnostics == diags


# ------------------------------------------------------- per-rule negatives
class TestChunkAlignment:
    def test_ragged_weight_rows_pinpointed(self):
        plan = _chain()
        lp = plan.layers[1]
        bad = dataclasses.replace(
            lp, store=dataclasses.replace(lp.store, codes=lp.store.codes[:-1])
        )
        plan = dataclasses.replace(
            plan, layers=(plan.layers[0], bad) + plan.layers[2:]
        )
        hits = _rule_hits(
            verify_plan(plan, rules=("chunk-alignment",)),
            "chunk-alignment",
        )
        assert hits and hits[0].path == "plan.layers[1].store.codes"
        assert "chunks" in hits[0].message

    def test_wrong_offset_grid_pinpointed(self):
        plan = _chain()
        bad = dataclasses.replace(
            plan.layers[0], chunk_offset=jnp.zeros((3, 7))
        )
        plan = dataclasses.replace(
            plan, layers=(bad,) + plan.layers[1:]
        )
        hits = _rule_hits(
            verify_plan(plan, rules=("chunk-alignment",)),
            "chunk-alignment",
        )
        assert hits and hits[0].path == "plan.layers[0].chunk_offset"

    def test_wrong_bias_width_pinpointed(self):
        plan = _chain()
        bad = dataclasses.replace(plan.layers[2], bias=jnp.zeros((5,)))
        plan = dataclasses.replace(
            plan, layers=plan.layers[:2] + (bad,)
        )
        hits = verify_plan(plan, rules=("chunk-alignment",))
        assert [d.path for d in hits] == ["plan.layers[2].bias"]


class TestDomainChain:
    def test_unknown_epilogue_pinpointed(self):
        plan = _chain()
        bad = dataclasses.replace(plan.layers[1], epilogue="softmax")
        plan = dataclasses.replace(
            plan, layers=(plan.layers[0], bad) + plan.layers[2:]
        )
        hits = verify_plan(plan, rules=("domain-chain",))
        assert [d.path for d in hits] == ["plan.layers[1].epilogue"]
        assert "softmax" in hits[0].message

    def test_width_break_pinpointed(self):
        plan = _chain()
        bad = dataclasses.replace(plan.layers[1], k=17)
        plan = dataclasses.replace(
            plan, layers=(plan.layers[0], bad) + plan.layers[2:]
        )
        hits = verify_plan(plan, rules=("domain-chain",))
        assert any(d.path == "plan.layers[0]" for d in hits)

    def test_bad_stack_spec(self):
        from repro.api.module import LayerSpec, ModuleSpec

        spec = ModuleSpec(name="bad", kind="stack", layers=(
            LayerSpec("a", 8, 16), LayerSpec("b", 32, 4),
        ))
        hits = verify_spec(spec)
        assert hits and "layers[0]" in hits[0].path
        assert verify_spec(ModuleSpec(name="ok", kind="stack", layers=(
            LayerSpec("a", 8, 16), LayerSpec("b", 16, 4),
        ))) == ()


class TestPackConsistency:
    def test_eligible_but_unpacked(self):
        plan = dataclasses.replace(_chain(), mega=None)
        hits = verify_plan(plan, rules=("pack-consistency",))
        assert [d.path for d in hits] == ["plan.mega"]
        assert "no packing" in hits[0].message

    def test_stale_pack_on_ineligible_chain(self):
        # a float chain packed under act_calib='static', then the cfg
        # flipped to dynamic: the pack is stale (in-kernel encode needs
        # the baked static LSB)
        plan = _chain(input_domain="float")
        assert plan.mega is not None
        plan = dataclasses.replace(
            plan, cfg=plan.cfg.replace(act_calib="dynamic")
        )
        hits = verify_plan(plan, rules=("pack-consistency",))
        assert [d.path for d in hits] == ["plan.mega"]
        assert "act_calib" in hits[0].message


class TestDispatchCount:
    def test_truncated_schedule(self):
        plan = _chain()
        mega = dataclasses.replace(
            plan.mega, schedule=plan.mega.schedule[:-1]
        )
        plan = dataclasses.replace(plan, mega=mega)
        hits = verify_plan(plan, rules=("dispatch-count",))
        assert [d.path for d in hits] == ["plan.mega.schedule"]

    def test_corrupted_schedule_entry_pinpointed(self):
        plan = _chain()
        sched = list(plan.mega.schedule)
        sched[1] = sched[1]._replace(shift=sched[1].shift + 3)
        plan = dataclasses.replace(
            plan, mega=dataclasses.replace(plan.mega, schedule=tuple(sched))
        )
        hits = verify_plan(plan, rules=("dispatch-count",))
        assert [d.path for d in hits] == ["plan.mega.schedule[1].shift"]

    def test_wrong_handoff_tag_pinpointed(self):
        plan = _chain()
        sched = list(plan.mega.schedule)
        sched[0] = sched[0]._replace(handoff="relu")
        plan = dataclasses.replace(
            plan, mega=dataclasses.replace(plan.mega, schedule=tuple(sched))
        )
        hits = verify_plan(plan, rules=("dispatch-count",))
        assert [d.path for d in hits] == ["plan.mega.schedule[0].handoff"]
        assert "'codes'" in hits[0].message


class TestGroupLayout:
    def _rwkv_group(self):
        d, heads = 64, 4
        model = api.compile(
            __import__("repro.models.rwkv", fromlist=["x"])
            .rwkv_module_spec(d, heads),
            __import__("repro.models.rwkv", fromlist=["x"])
            .rwkv_init(KEY, d, heads),
            AnalogConfig(noise=NOISELESS),
        )
        gps = [gp for _, gp in _walk_groups(model.lower())]
        assert gps
        return gps[0]

    def test_member_width_mismatch_pinpointed(self):
        gp = self._rwkv_group()
        bad = dataclasses.replace(gp, member_ns=gp.member_ns[:-1] + (7,))
        hits = verify_plan(bad, rules=("group-layout",))
        assert hits and all(d.rule == "group-layout" for d in hits)
        assert any("member" in d.path for d in hits)

    def test_batch_concat_needs_member_axis(self):
        gp = self._rwkv_group()
        assert gp.kind == "batch_concat"
        bad = dataclasses.replace(
            gp, fused=dataclasses.replace(
                gp.fused, store=dataclasses.replace(
                    gp.fused.store, codes=gp.fused.store.codes[0]
                )
            )
        )
        hits = verify_plan(bad, rules=("group-layout",))
        assert any(d.path.endswith(".fused.store.codes") for d in hits)

    def test_scan_stacked_batch_concat_clean(self):
        """The LM rwkv arch lowers its batch_concat group under vmap:
        every fused leaf gains a scan-stack prefix ([S, G, ...]) and the
        cheap rules must accept the shifted member axis (api.compile
        verifies by default, so a false positive breaks compile)."""
        from repro.configs.base import ArchConfig
        from repro.models import transformer as T

        cfg = ArchConfig("t-rwkv", "ssm", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=4, d_ff=128,
                         vocab_size=256, block="rwkv", remat=False)
        params = T.lm_init(KEY, cfg)
        model = api.compile(
            T.lm_module_spec(cfg, params), params,
            AnalogConfig(noise=NOISELESS),
        )
        gps = [gp for _, gp in _walk_groups(model.lower())]
        assert any(gp.fused.store.codes.ndim == 4 for gp in gps)
        assert verify_plan(
            model.lower(),
            rules=("group-layout", "chunk-alignment"),
        ) == ()

    def test_expert_stack_clean(self):
        from repro.models import moe as M

        model = api.compile(
            M.moe_module_spec(64, 32, 4, top_k=2),
            M.moe_init(KEY, 64, 32, 4), AnalogConfig(noise=NOISELESS),
        )
        assert verify_plan(
            model.lower(), rules=("group-layout",)
        ) == ()


def _walk_groups(tree, path=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            if k == "_groups":
                for name, gp in v.items():
                    yield f"{path}.{name}", gp
            elif isinstance(v, (dict, list, tuple)):
                yield from _walk_groups(v, f"{path}.{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk_groups(v, f"{path}[{i}]")


class TestDriftSwap:
    def _offset_plan(self):
        # default NoiseConfig bakes fpn -> chunk_offset tables
        return _chain(acfg=AnalogConfig(act_calib="static"),
                      noise=NoiseConfig())

    def test_identity_swap_is_clean(self):
        plan = self._offset_plan()
        assert plan.layers[0].chunk_offset is not None
        assert verify_plan(plan, rules=("drift-swap",)) == ()
        fresh = plan_with_offsets(
            plan, [jnp.zeros_like(lp.chunk_offset) for lp in plan.layers]
        )
        assert verify_swap(plan, fresh) == ()

    def test_static_metadata_change_flagged(self):
        plan = self._offset_plan()
        cfg = plan.cfg.replace(gain_headroom=plan.cfg.gain_headroom + 1)
        other = dataclasses.replace(plan, cfg=cfg)
        hits = verify_swap(plan, other)
        assert hits and "static metadata" in hits[0].message

    def test_leaf_shape_change_pinpointed(self):
        plan = self._offset_plan()
        bad0 = dataclasses.replace(
            plan.layers[0],
            chunk_offset=plan.layers[0].chunk_offset[:, :-1],
        )
        other = dataclasses.replace(plan, layers=(bad0,) + plan.layers[1:])
        hits = verify_swap(plan, other)
        assert hits and "chunk_offset" in hits[0].path


class TestShardingSpecs:
    def test_float_glue_pack_specs_complete(self):
        # mixed-domain chain: the pack carries deq/bias/enc extras, every
        # one of which must receive a spec (regression: they used to be
        # left as raw arrays in the spec tree)
        plan = _chain(epilogues=["relu_shift", "none", "none"])
        assert plan.mega is not None and plan.mega.deq is not None
        assert verify_plan(plan, rules=("sharding-specs",)) == ()

    def test_incomplete_specs_flagged(self, monkeypatch):
        from repro.distributed import sharding as shd

        plan = _chain()
        orig = shd.analog_plan_specs

        def stale(p, axes):     # old behavior: w_cat spec'd, gain left raw
            specs = orig(p, axes)
            return dataclasses.replace(
                specs, mega=dataclasses.replace(specs.mega, gain=p.mega.gain)
            )

        monkeypatch.setattr(shd, "analog_plan_specs", stale)
        hits = verify_plan(plan, rules=("sharding-specs",))
        assert hits and all(d.rule == "sharding-specs" for d in hits)
        assert any(".gain" in d.path for d in hits)


class TestCalibrationCompat:
    def test_version_mismatch(self):
        from repro import calib

        snap = dataclasses.replace(
            calib.CalibrationSnapshot(), version="repro-calib-v0"
        )
        hits = verify_plan(
            _chain(), calibration=snap, rules=("calibration-compat",)
        )
        assert [d.path for d in hits] == ["calibration.version"]

    def test_table_geometry_vs_plan(self):
        from repro import calib
        from repro.models import ecg as ECG

        cfg = ECG.ECGConfig()
        spec = ECG.ecg_module_spec(cfg, epilogue="relu_shift")
        model = api.compile(spec, ECG.ecg_init(KEY, cfg), AnalogConfig())
        name = spec.layers[1].name
        snap = calib.CalibrationSnapshot().with_layer(
            name, calib.LayerCalibration(gain_table=jnp.ones((2, 3)))
        )
        hits = verify_plan(
            model.lower(), spec=spec, calibration=snap,
            rules=("calibration-compat",),
        )
        assert hits and hits[0].path == f"calibration[{name!r}].gain_table"
        assert "chunk grid" in hits[0].message

    def test_group_shared_scale_disagreement(self):
        from repro import calib
        from repro.models import rwkv as R

        d, heads = 64, 4
        spec = R.rwkv_module_spec(d, heads)
        names = list(spec.groups[0].members)
        snap = calib.CalibrationSnapshot()
        for i, n in enumerate(names):
            snap = snap.with_layer(
                n, calib.LayerCalibration(a_scale_in=jnp.float32(0.1 + i))
            )
        hits = verify_plan(
            {}, spec=spec, calibration=snap,
            rules=("calibration-compat",),
        )
        assert hits and "a_scale_in" in hits[0].path


# ------------------------------------------------------------- api wiring
class TestApiWiring:
    def test_compile_verifies_by_default_and_model_verify_clean(self):
        from repro.models import ecg as ECG

        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(KEY, cfg)
        model = api.compile(
            ECG.ecg_module_spec(cfg, epilogue="relu_shift"), params,
            AnalogConfig(),
        )
        assert model.verify() == ()
        assert model.verify(strict=True) == ()

    def test_compile_verify_false_skips(self):
        from repro.models import ecg as ECG

        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(KEY, cfg)
        m = api.compile(ECG.ecg_module_spec(cfg), params, AnalogConfig(),
                        verify=False)
        assert m.verify() == ()

    def test_model_verify_strict_raises_on_corruption(self):
        from repro.models import ecg as ECG

        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(KEY, cfg)
        model = api.compile(
            ECG.ecg_module_spec(cfg, epilogue="relu_shift"), params,
            AnalogConfig(),
        )
        bad = dataclasses.replace(
            model, lowered=dataclasses.replace(model.lowered, mega=None)
        )
        diags = bad.verify()
        assert _rule_hits(diags, "pack-consistency")
        with pytest.raises(VerifyError):
            bad.verify(strict=True)


# ---------------------------------------------------------------- retrace
class TestRetrace:
    def test_cached_replay_is_clean(self):
        plan = _chain()
        fn = jax.jit(lambda x: E.run(plan, x))
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(1), (4, 32)) * 31
        )
        assert assert_no_retrace(fn, x, label="stack-replay") == ()

    def test_per_call_lowering_flagged(self):
        ks = jax.random.split(KEY, 2)
        layers = [analog_linear_init(ks[0], 32, 48, noise=NOISELESS),
                  analog_linear_init(ks[1], 48, 24, noise=NOISELESS)]
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 32)))

        def bad(x):
            return E.run(E.lower_stack(layers, ACFG), x)

        diags = assert_no_retrace(bad, x, label="relower-per-call")
        assert diags and "re-lowering" in diags[0].message
        with pytest.raises(VerifyError):
            assert_no_retrace(bad, x, strict=True)

    def test_captured_constant_flagged(self):
        big = jnp.ones((256, 256))          # 256 KiB closure capture

        def leaky(x):
            return x @ big

        diags = captured_constants(leaky, jnp.ones((4, 256)))
        assert diags and diags[0].rule == "captured-constant"
        clean = captured_constants(
            lambda x, w: x @ w, jnp.ones((4, 256)), big
        )
        assert clean == ()


# ------------------------------------------------------------------- lint
class TestLint:
    def test_fpn_read_forbidden_outside_lower_and_calib(self):
        src = "def f(params):\n    return params['fpn']\n"
        assert lint_source(src, "src/repro/models/foo.py")
        assert lint_source(src, "src/repro/exec/lower.py") == []
        assert lint_source(src, "src/repro/calib/device.py") == []
        # stores stay legal everywhere
        assert lint_source(
            "def f(params, t):\n    params['fpn'] = t\n",
            "src/repro/models/foo.py",
        ) == []

    def test_fpn_get_and_suppression(self):
        src = "def f(params):\n    return params.get('fpn', {})\n"
        assert lint_source(src, "src/repro/models/foo.py")
        ok = ("def f(params):\n"
              "    return params.get('fpn', {})  # verify: allow-fpn-access\n")
        assert lint_source(ok, "src/repro/models/foo.py") == []

    def test_deprecated_shim_call(self):
        src = ("from repro.core.analog import analog_linear_apply\n"
               "y = analog_linear_apply(p, x, cfg)\n")
        hits = lint_source(src, "examples/foo.py")
        assert hits and hits[0].rule == "deprecated-shim"
        assert "apply_linear" in hits[0].message
        # the shim's own home may mention it
        assert lint_source(src, "src/repro/core/analog.py") == []

    def test_numpy_in_kernel_body(self):
        src = ("import numpy as np\n"
               "import jax.numpy as jnp\n"
               "def k(x_ref, o_ref):\n"
               "    o_ref[...] = np.maximum(x_ref[...], 0)\n")
        hits = lint_source(src, "src/repro/kernels/foo.py")
        assert hits and hits[0].rule == "numpy-in-kernel"
        ok = src.replace("np.maximum", "jnp.maximum")
        assert lint_source(ok, "src/repro/kernels/foo.py") == []
        host = ("import numpy as np\n"
                "def h(x):\n    return np.maximum(x, 0)\n")
        assert lint_source(host, "src/repro/kernels/foo.py") == []

    def test_frozen_plan_dataclass(self):
        src = ("import dataclasses, jax\n"
               "@dataclasses.dataclass\n"
               "class P:\n    x: int\n"
               "jax.tree_util.register_dataclass(P, data_fields=['x'],"
               " meta_fields=[])\n")
        hits = lint_source(src, "src/repro/exec/foo.py")
        assert hits and hits[0].rule == "frozen-plan-dataclass"
        ok = src.replace("@dataclasses.dataclass",
                         "@dataclasses.dataclass(frozen=True)")
        assert lint_source(ok, "src/repro/exec/foo.py") == []

    def test_packed_weights_rule(self):
        build = ("from repro.exec.plan import WeightStore\n"
                 "s = WeightStore(codes=c, w_scale=w, gain=g)\n")
        hits = lint_source(build, "src/repro/models/foo.py")
        assert hits and hits[0].rule == "packed-weights"
        # the lowering, the plan definitions and the plan store may build
        for home in ("src/repro/exec/lower.py", "src/repro/exec/plan.py",
                     "src/repro/exec/store.py"):
            assert lint_source(build, home) == []
        weff = "lp = LayerPlan(w_eff=w, a_scale=a)\n"
        hits = lint_source(weff, "src/repro/serve/foo.py")
        assert hits and hits[0].rule == "packed-weights"
        assert "derived view" in hits[0].message
        # reading the derived view stays legal everywhere
        assert lint_source(
            "y = x @ lp.store.w_eff\n", "src/repro/models/foo.py"
        ) == []
        ok = ("s = WeightStore(codes=c, w_scale=w, gain=g)"
              "  # verify: allow-packed-weights\n")
        assert lint_source(ok, "src/repro/models/foo.py") == []

    def test_repo_is_lint_clean(self):
        assert run_lint(REPO) == []


# -------------------------------------------------- parity: pinned messages
class TestIneligibilityMessageParity:
    """The delegated chain_ineligible_reason keeps the exact pre-ISSUE-7
    message strings (the README fallback matrix documents them)."""

    def test_short_stack(self):
        plan = _chain(dims=(32, 24), epilogues=["none"])
        assert megakernel_ineligible_reason(plan) == \
            "megakernel needs a stack of >= 2 layers"

    def test_dynamic_float_message(self):
        plan = _chain(input_domain="float",
                      acfg=AnalogConfig(noise=NOISELESS))
        assert megakernel_ineligible_reason(plan) == (
            "layer 0 (consumes 'float', epilogue 'relu_shift'): float "
            "activations under act_calib='dynamic' cannot be encoded "
            "in-kernel; the baked static LSB needs act_calib='static'"
        )

    def test_offset_signed_message(self):
        plan = _chain(
            input_domain="float",
            acfg=AnalogConfig(noise=NOISELESS, act_calib="static",
                              signed_input="offset"),
        )
        assert megakernel_ineligible_reason(plan) == (
            "layer 0 (consumes 'float', epilogue 'relu_shift'): "
            "signed_input 'offset' is not packable (the offset "
            "encoding's column-sum correction stays per-layer); use "
            "'none' or 'split'"
        )

    def test_last_layer_epilogue_message(self):
        plan = _chain(dims=(32, 48, 24))
        bad = dataclasses.replace(plan.layers[-1], epilogue="relu_shift")
        plan = dataclasses.replace(plan, layers=plan.layers[:-1] + (bad,))
        assert megakernel_ineligible_reason(plan) == (
            "layer 1 (consumes 'codes', epilogue 'relu_shift'): the last "
            "layer must dequantize (epilogue 'none')"
        )

    def test_width_mismatch_message(self):
        plan = _chain()
        bad = dataclasses.replace(plan.layers[1], k=17)
        plan = dataclasses.replace(
            plan, layers=(plan.layers[0], bad) + plan.layers[2:]
        )
        assert megakernel_ineligible_reason(plan) == (
            "layer 0 (consumes 'codes', epilogue 'relu_shift'): hand-off "
            "width n=48 does not feed layer 1 width k=17"
        )


# ------------------------------------------------------ property tests
# Randomly generated chains: verifier verdicts must agree with
# megakernel_ineligible_reason (packing presence) and with ACTUAL
# dispatch counts from an eager layer-by-layer replay.  The exhaustive
# grid runs everywhere; hypothesis (when installed) additionally samples
# the full product space.
GRID = [
    {"n_layers": n, "epilogues": epis, "input_domain": ind,
     "act_calib": ac, "signed": sg}
    for n, epis, ind, ac, sg in [
        (2, ["relu_shift", "none"], "codes", "static", "none"),
        (2, ["none", "none"], None, "static", "split"),
        (3, ["relu_shift", "relu_shift", "none"], "codes", "dynamic",
         "none"),
        (3, ["relu_shift", "none", "none"], "codes", "static", "split"),
        (3, ["none", "relu_shift", "none"], None, "static", "none"),
        (2, ["relu_shift", "none"], None, "dynamic", "offset"),
        (4, ["relu_shift", "relu_shift", "relu_shift", "none"], "codes",
         "static", "offset"),
        (2, ["none", "none"], None, "dynamic", "split"),
    ]
]
DIMS = (16, 24, 32, 48, 24)


def _build(cfg):
    n = cfg["n_layers"]
    acfg = AnalogConfig(
        noise=NOISELESS, act_calib=cfg["act_calib"],
        signed_input=cfg["signed"],
    )
    return _chain(dims=DIMS[: n + 1], epilogues=cfg["epilogues"][:n],
                  acfg=acfg, input_domain=cfg["input_domain"])


def _check_verdict(cfg):
    plan = _build(cfg)
    reason = megakernel_ineligible_reason(plan)
    # the packing and the (delegated) eligibility walk agree...
    assert (reason is None) == (plan.mega is not None)
    # ...and the full verifier is clean on every as-lowered plan
    assert verify_plan(plan) == ()


def _check_dispatches(cfg):
    plan = _build(cfg)
    b, k0 = 2, plan.layers[0].k
    if plan.expects_codes:
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(3), (b, k0)) * 31
        )
    else:
        x = jax.random.normal(jax.random.PRNGKey(3), (b, k0)) * 0.3
    reset_dispatch_count()
    y = E.run_layers(plan, x)                  # layer-by-layer replay
    assert np.asarray(y).shape[0] == b
    # one dispatch a layer, whatever the domains and signed encodings
    assert dispatch_count() == plan.expected_dispatches == len(plan.layers)


class TestGridProperties:
    @pytest.mark.parametrize("cfg", GRID, ids=lambda c: (
        f"L{c['n_layers']}-{c['input_domain']}-{c['act_calib']}-"
        f"{c['signed']}"
    ))
    def test_verdict_agrees_with_packing_and_verifier(self, cfg):
        _check_verdict(cfg)

    @pytest.mark.parametrize("cfg", GRID, ids=lambda c: (
        f"L{c['n_layers']}-{c['input_domain']}-{c['act_calib']}-"
        f"{c['signed']}"
    ))
    def test_expected_dispatches_matches_actual(self, cfg):
        _check_dispatches(cfg)


try:
    import hypothesis
    import hypothesis.strategies as st

    hypothesis.settings.register_profile(
        "verify-props", deadline=None, max_examples=15, derandomize=True
    )
    hypothesis.settings.load_profile("verify-props")
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    chain_cfg = st.fixed_dictionaries({
        "n_layers": st.integers(2, 4),
        "epilogues": st.lists(
            st.sampled_from(["relu_shift", "none"]), min_size=4,
            max_size=4,
        ),
        "input_domain": st.sampled_from(["codes", None]),
        "act_calib": st.sampled_from(["static", "dynamic"]),
        "signed": st.sampled_from(["none", "split", "offset"]),
    })

    class TestHypothesisProperties:
        @hypothesis.given(chain_cfg)
        def test_verdict_agrees_with_packing_and_verifier(self, cfg):
            _check_verdict(cfg)

        @hypothesis.given(chain_cfg)
        def test_expected_dispatches_matches_actual(self, cfg):
            _check_dispatches(cfg)
else:
    @pytest.mark.skip(reason="property sampling needs hypothesis")
    def test_hypothesis_properties():
        pass
