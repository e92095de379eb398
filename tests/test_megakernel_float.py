"""Float-domain megakernel tests (ISSUE 6): mixed-domain stacks and the
fused attention+MLP block replay as ONE ``pallas_call``, bit-exact against
the per-layer executor, with HIL gradients flowing through the per-layer
reference chain and drift hot-swaps keeping the compiled executable."""
import functools

import jax
import jax.numpy as jnp
import pytest

import repro.api as api
import repro.exec as E
from repro.configs.base import ArchConfig, RunConfig
from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.noise import NOISELESS
from repro.exec.run import dispatch_count, reset_dispatch_count
from repro.models import transformer as T

KEY = jax.random.PRNGKey(7)

ARCH = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=2, n_kv_heads=2, d_ff=96, vocab_size=64,
                  remat=False)
SEQ = 8


def _acfg(faithful=True, use_pallas=False, **kw):
    return AnalogConfig(
        mode="analog_faithful" if faithful else "analog_fast",
        act_calib="static", use_pallas=use_pallas, **kw,
    )


def _mixed_stack(acfg, seed=0):
    """codes-in -> relu_shift (codes hand-off) -> float glue -> float glue:
    the mixed chain the float-domain megakernel exists for."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    layers = [
        analog_linear_init(ks[0], 32, 48, noise=NOISELESS),
        analog_linear_init(ks[1], 48, 40, noise=NOISELESS),
        analog_linear_init(ks[2], 40, 24, noise=NOISELESS),
    ]
    return E.lower_stack(
        layers, acfg,
        signed_inputs=[None, None, None],
        epilogues=["relu_shift", "none", "none"],
        flatten_outs=[False, False, False],
        input_domain="codes",
    )


def _codes(b, k, seed=9):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (b, k), 0, 32
    ).astype(jnp.float32)


def _block_params(seed=0):
    return T._layer_init(jax.random.PRNGKey(seed), "attn_mlp", ARCH)


def _block_plan(acfg, params=None):
    return E.lower_block(
        params if params is not None else _block_params(), acfg,
        n_heads=ARCH.n_heads, n_kv_heads=ARCH.n_kv_heads, head_dim=ARCH.hd,
        seq=SEQ, rope_theta=ARCH.rope_theta,
    )


def _block_x(b=3, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed), (b, SEQ, ARCH.d_model)
    ) * 0.5


# ------------------------------------------------------- mixed-domain chain
@pytest.mark.parametrize("faithful", [True, False])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_mixed_chain_megakernel_bitexact(faithful, use_pallas):
    plan = _mixed_stack(_acfg(faithful, use_pallas))
    assert E.megakernel_ineligible_reason(plan) is None
    x = _codes(5, 32)
    assert E.megakernel_fallback_reason(plan, x, plan.cfg, None, True) is None
    y_mega = E.run(plan, x)
    y_ref = E.run_layers(plan, x)
    assert y_mega.shape == y_ref.shape
    assert jnp.array_equal(y_mega, y_ref)


def test_mixed_chain_gradient_parity():
    plan = _mixed_stack(_acfg())
    x = _codes(4, 32) / 31.0  # keep the loss surface smooth-ish

    def loss(x, replay):
        # codes-domain entry expects integer codes; re-scale inside so the
        # grad w.r.t. the float input is well-defined through the STE chain
        return (replay(plan, jnp.round(x * 31)) ** 2).mean()

    g_m = jax.grad(lambda x: loss(x, E.run))(x)
    g_f = jax.grad(lambda x: loss(x, E.run_layers))(x)
    assert jnp.allclose(g_m, g_f, atol=1e-6)


# -------------------------------------------------------- attention+MLP block
@pytest.mark.parametrize("faithful", [True, False])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_block_megakernel_bitexact(faithful, use_pallas):
    plan = _block_plan(_acfg(faithful, use_pallas))
    x = _block_x()
    y_mega = E.run(plan, x)
    y_fall = E.run_layers(plan, x)
    assert y_mega.shape == x.shape
    assert jnp.array_equal(y_mega, y_fall)


def test_block_single_dispatch():
    plan = _block_plan(_acfg())
    assert plan.block is not None and plan.mega is not None
    assert plan.expected_dispatches == 1
    x = _block_x(b=2)
    reset_dispatch_count()
    E.run(plan, x)
    assert dispatch_count() == 1        # ONE pallas_call for the block
    reset_dispatch_count()
    E.run_layers(plan, x)
    assert dispatch_count() == 4        # per-layer replay: qkv/o/ug/down


def test_block_hil_gradient_parity():
    plan = _block_plan(_acfg())
    x = _block_x(b=2)

    def loss(x, replay):
        return (replay(plan, x) ** 2).mean()

    g_m = jax.grad(lambda x: loss(x, E.run))(x)
    g_f = jax.grad(lambda x: loss(x, E.run_layers))(x)
    assert float(jnp.linalg.norm(g_m)) > 0.0
    assert jnp.allclose(g_m, g_f, atol=1e-6)


def test_block_seq_mismatch_raises():
    plan = _block_plan(_acfg())
    x = jax.random.normal(KEY, (2, SEQ + 1, ARCH.d_model))
    with pytest.raises(ValueError, match="re-lower"):
        E.run(plan, x)


# -------------------------------------------------------------- drift swap
def test_block_drift_hot_swap_keeps_executable():
    plan = _block_plan(_acfg())
    offs = [
        None if lp.chunk_offset is None
        else lp.chunk_offset + 2.0
        for lp in plan.layers
    ]
    plan2 = E.plan_with_offsets(plan, offs)
    assert plan2.block is not None and plan2.mega is not None
    # identical static schedule -> identical treedef -> no recompile
    assert jax.tree_util.tree_structure(plan) == \
        jax.tree_util.tree_structure(plan2)

    @jax.jit
    def f(pl, x):
        return E.run(pl, x)

    x = _block_x(b=2)
    y1 = f(plan, x)
    y2 = f(plan2, x)
    assert f._cache_size() == 1         # offset swap reused the executable
    assert bool(jnp.any(y1 != y2))      # ...but the offsets took effect


# ------------------------------------------------------------- diagnostics
def test_ineligible_reason_names_layer_and_domain():
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    bad = E.lower_stack(
        [analog_linear_init(ks[0], 16, 24, noise=NOISELESS),
         analog_linear_init(ks[1], 24, 8, noise=NOISELESS)],
        AnalogConfig(act_calib="dynamic"),
        signed_inputs=[None, None], epilogues=["none", "none"],
        flatten_outs=[False, False], input_domain="float",
    )
    reason = E.megakernel_ineligible_reason(bad)
    assert reason is not None
    assert "layer 0" in reason
    assert "'float'" in reason and "'none'" in reason
    assert "act_calib" in reason
    x = jax.random.normal(KEY, (4, 16))
    assert E.megakernel_fallback_reason(bad, x, bad.cfg, None,
                                        False) == reason


# --------------------------------------------------------------------- api
def test_compile_block_applies_and_lowers():
    params = _block_params()
    m = api.compile_block(
        params, _acfg(), n_heads=ARCH.n_heads, n_kv_heads=ARCH.n_kv_heads,
        head_dim=ARCH.hd, seq=SEQ, rope_theta=ARCH.rope_theta,
    )
    x = _block_x(b=2)
    y = m.apply(x)
    assert jnp.array_equal(y, E.run_layers(m.lower(), x))
    plan = m.lower()
    assert plan.block is not None and plan.expected_dispatches == 1
    # relower round-trips through the BLOCK compile branch
    m2 = m.relower(params)
    assert jnp.array_equal(m2.apply(x), y)


def test_compile_block_digital_raises():
    with pytest.raises(ValueError, match="digital"):
        api.compile_block(
            _block_params(), AnalogConfig(mode="digital", act_calib="static"),
            n_heads=ARCH.n_heads, n_kv_heads=ARCH.n_kv_heads,
            head_dim=ARCH.hd, seq=SEQ,
        )


def test_block_spec_requires_geometry():
    with pytest.raises(ValueError, match="block_geom"):
        api.ModuleSpec(name="b", kind="block")


def test_lower_block_rejects_dynamic_calib():
    with pytest.raises(ValueError, match="act_calib"):
        _block_plan(AnalogConfig(act_calib="dynamic"))


# ----------------------------------------------------------- model wiring
def test_attach_block_plans_lm_parity():
    params = T.lm_init(jax.random.PRNGKey(0), ARCH)
    acfg = _acfg()
    p2 = T.attach_block_plans(params, ARCH, acfg, seq=SEQ)
    assert "_block_plan" in p2["layers"]["l0"]
    # stacked plan leaves carry the scan-group axis
    bp = p2["layers"]["l0"]["_block_plan"]
    assert bp.layers[0].w_eff.shape[0] == T.n_groups(ARCH)

    run = RunConfig(analog=acfg, activation_dtype="float32")
    batch = {"tokens": jax.random.randint(KEY, (2, SEQ), 0, ARCH.vocab_size)}
    reset_dispatch_count()
    y_base = T.lm_apply(params, batch, ARCH, run)[0]
    d_base = dispatch_count()
    reset_dispatch_count()
    y_block = T.lm_apply(p2, batch, ARCH, run)[0]
    d_block = dispatch_count()
    # fp32 activations -> the fused block is bit-exact vs the per-layer
    # model path (bf16 runs differ only by residual-stream rounding)
    assert jnp.array_equal(y_base, y_block)
    assert d_block == T.n_groups(ARCH)            # ONE dispatch per block
    assert d_base > d_block

    # non-baked seq lengths keep the per-layer path (parity is trivial)
    batch2 = {"tokens": jax.random.randint(KEY, (2, SEQ - 3), 0,
                                           ARCH.vocab_size)}
    assert jnp.array_equal(
        T.lm_apply(params, batch2, ARCH, run)[0],
        T.lm_apply(p2, batch2, ARCH, run)[0],
    )


def test_attach_block_plans_rejects_foreign_glue():
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=64,
                     n_heads=2, n_kv_heads=2, d_ff=96, vocab_size=64,
                     act="gelu")
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="swiglu"):
        T.attach_block_plans(params, cfg, _acfg(), seq=SEQ)
