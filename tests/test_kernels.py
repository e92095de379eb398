"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle over
shape/dtype sweeps, as required for every kernel in kernels/."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.analog_mvm import (
    analog_mvm_pallas, analog_mvm_split_pallas, expert_mvm_pallas,
    split_bf16x3,
)
from repro.kernels.preproc import maxmin_pool_2d_pallas

KEY = jax.random.PRNGKey(0)

MVM_SHAPES = [
    (1, 128, 1),
    (8, 128, 64),
    (100, 384, 700),     # non-aligned M/N, 3 chunks
    (256, 256, 512),     # exactly one BSS-2 tile grid
    (17, 512, 129),
    (64, 1024, 256),
]


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested ones (jit, pallas_call) too."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _mvm_inputs(m, k, n, dtype=jnp.float32, with_noise=True):
    ka, kw, kg, ko = jax.random.split(jax.random.fold_in(KEY, m * k + n), 4)
    a = jnp.round(jax.random.uniform(ka, (m, k)) * 31).astype(dtype)
    w = jnp.round(
        jax.random.uniform(kw, (k, n), minval=-1, maxval=1) * 63
    ).astype(dtype)
    if with_noise:
        w = w * (1 + 0.02 * jax.random.normal(kg, (k, n))).astype(dtype)
    gain = jnp.full((n,), 0.02, jnp.float32)
    off = jax.random.normal(ko, (k // 128, n), jnp.float32)
    return a, w, gain, off


def _split_case(case):
    ks = jax.random.split(jax.random.fold_in(KEY, 7), 4)
    codes = jnp.round(jax.random.uniform(ks[0], (256, 384), minval=-63,
                                         maxval=63))
    col = 1 + 0.02 * jax.random.normal(ks[1], (1, 384))
    row = 1 + 0.02 * jax.random.normal(ks[2], (256, 1))
    if case == "rank1":
        return (codes * col) * row
    if case == "full_gain_map":
        return codes * (1 + 0.02 * jax.random.normal(ks[3], codes.shape))
    if case == "negative":
        return (-jnp.abs(codes) * col) * row
    if case == "zeros":
        return jnp.where(jnp.arange(384) % 3 == 0, 0.0, (codes * col) * row)
    return jnp.where(codes >= 0, 63.0, -63.0) * col * row     # largest code


@pytest.mark.parametrize("case", ["rank1", "full_gain_map", "negative",
                                  "zeros", "largest_code"])
def test_weight_split_is_exact(case):
    """The three bf16 parts of the effective weights add back to them bit
    for bit, in the order the contraction adds its passes (a zero comes
    back as +0 whatever its sign, which no sum can tell apart)."""
    w = _split_case(case)
    hi, mid, lo = (p.astype(jnp.float32) for p in split_bf16x3(w))
    back = (lo + mid) + hi
    bits = lambda x: np.asarray(jax.lax.bitcast_convert_type(x, jnp.int32))
    nz = np.asarray(w != 0)
    assert nz.mean() > 0.5
    np.testing.assert_array_equal(bits(back)[nz], bits(w)[nz])
    np.testing.assert_array_equal(bits(back)[~nz], 0)
    # each part is a bf16 number, and the leading part carries the sign
    assert bool(jnp.all((hi == 0) | (jnp.sign(hi) == jnp.sign(w))))


class TestAnalogMVMKernel:
    @pytest.mark.parametrize("m,k,n", MVM_SHAPES)
    @pytest.mark.parametrize("faithful", [True, False])
    def test_fp32_exact_vs_oracle(self, m, k, n, faithful):
        a, w, gain, off = _mvm_inputs(m, k, n)
        got = analog_mvm_pallas(
            a, w, gain, off, faithful=faithful, interpret=True
        )
        want = R.analog_mvm_ref(a, w, gain, off, faithful=faithful)
        tol = 0.0 if faithful else 1.0   # fast mode: summation-order LSB
        assert float(jnp.abs(got - want).max()) <= tol

    @pytest.mark.parametrize("kernel", ["single", "split", "grouped"])
    def test_kernel_dots_contract_codes_in_three_bf16_passes(self, kernel):
        """Activation codes are exact in bf16, the fp32 weights are not:
        every analog pass of every kernel is three dots of bf16 codes
        against bf16 parts of the weights, with fp32 results - the three
        of the six passes of an fp32 dot at full precision that do not
        multiply by zero."""
        a, w, gain, off = _mvm_inputs(8, 256, 64)
        if kernel == "single":
            fn, passes = lambda: analog_mvm_pallas(a, w, gain, off), 1
        elif kernel == "split":
            fn, passes = (lambda: analog_mvm_split_pallas(a, a, w, gain,
                                                          off)), 2
        else:
            te, lv = jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)
            fn, passes = (lambda: expert_mvm_pallas(
                a, a, w[None], gain[None], off[None], te, lv,
                block_m=8)), 2
        dots = [e for e in _eqns(jax.make_jaxpr(fn)().jaxpr)
                if e.primitive.name == "dot_general"]
        assert len(dots) == 3 * passes
        for e in dots:
            assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
            assert e.outvars[0].aval.dtype == jnp.float32
            assert e.params["preferred_element_type"] == jnp.float32

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_input_dtypes(self, dtype):
        a, w, gain, off = _mvm_inputs(16, 256, 128, dtype=dtype,
                                      with_noise=False)
        got = analog_mvm_pallas(a, w, gain, off, interpret=True)
        want = R.analog_mvm_ref(
            a.astype(jnp.float32), w.astype(jnp.float32), gain, off
        )
        assert float(jnp.abs(got - want).max()) == 0.0

    def test_none_offset(self):
        a, w, gain, _ = _mvm_inputs(8, 256, 64)
        got = analog_mvm_pallas(a, w, gain, None, interpret=True)
        want = R.analog_mvm_ref(a, w, gain, None)
        assert float(jnp.abs(got - want).max()) == 0.0

    @pytest.mark.parametrize("block_m,block_n", [(128, 128), (256, 512),
                                                 (512, 256)])
    def test_block_shape_invariance(self, block_m, block_n):
        a, w, gain, off = _mvm_inputs(100, 384, 300)
        got = analog_mvm_pallas(
            a, w, gain, off, block_m=block_m, block_n=block_n, interpret=True
        )
        want = R.analog_mvm_ref(a, w, gain, off)
        assert float(jnp.abs(got - want).max()) == 0.0

    def test_output_is_integer_valued_and_bounded(self):
        a, w, gain, off = _mvm_inputs(32, 512, 64)
        y = np.asarray(analog_mvm_pallas(a, w, gain, off, interpret=True))
        np.testing.assert_array_equal(y, np.round(y))
        c = 512 // 128
        assert y.min() >= -128 * c and y.max() <= 127 * c

    def test_custom_vjp_hil_gradient(self):
        a, w, gain, _ = _mvm_inputs(16, 256, 32, with_noise=False)

        def loss(a, w, gain):
            return (ops.analog_mvm(a, w, gain, None, 128, True, False) ** 2).sum()

        da, dw, dg = jax.grad(loss, argnums=(0, 1, 2))(a, w, gain)
        # HIL gradient == gradient of the linearization y = gain * a @ w
        y = ops.analog_mvm(a, w, gain, None, 128, True, False)
        g = 2 * y
        np.testing.assert_allclose(np.asarray(da), np.asarray((g * gain) @ w.T),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(a.T @ (g * gain)),
                                   rtol=1e-5)


def _pack_chain(dims, seed=0, flatten=None, noise=True):
    """Lower a code-domain chain and return (pack, x_codes, b)."""
    from repro.core.analog import AnalogConfig, analog_linear_init
    from repro.core.noise import NOISELESS, NoiseConfig
    from repro.exec.lower import lower_stack

    nz = NoiseConfig() if noise else NOISELESS
    ps = [analog_linear_init(jax.random.fold_in(KEY, seed + i), k, n,
                             noise=nz)
          for i, (k, n) in enumerate(dims)]
    plan = lower_stack(
        ps, AnalogConfig(noise=nz),
        epilogues=["relu_shift"] * (len(dims) - 1) + ["none"],
        flatten_outs=flatten or [False] * len(dims),
        input_domain="codes",
    )
    assert plan.mega is not None
    return plan.mega


class TestExpertMVMKernel:
    """The grouped held-expert kernel: each live tile through its own
    expert, bit-exact vs the per-expert two-pass oracle; dead tiles
    compute nothing."""

    @pytest.mark.parametrize("tiles,live", [
        ([0, 0, 1, 2, 2, 2], 6),      # every tile live
        ([1, 2, 2, 2, 2, 2], 3),      # three dead tiles after the groups
        ([0, 0, 0, 0], 0),            # no row routed to any held expert
    ])
    @pytest.mark.parametrize("faithful", [True, False])
    def test_fp32_exact_vs_oracle(self, tiles, live, faithful):
        e, k, n, bm = 3, 256, 200, 8
        r = len(tiles) * bm
        ka, kb, kw = jax.random.split(KEY, 3)
        a_pos = jnp.round(jax.random.uniform(ka, (r, k)) * 31)
        a_neg = jnp.round(jax.random.uniform(kb, (r, k)) * 31)
        w, gain, off = [], [], []
        for i in range(e):
            _, wi, gi, oi = _mvm_inputs(8, k, n + i)
            w.append(wi[:, :n])
            gain.append(gi[:n] * (1 + i))
            off.append(oi[:, :n])
        w, gain, off = jnp.stack(w), jnp.stack(gain), jnp.stack(off)
        te = jnp.asarray(tiles, jnp.int32)
        got = expert_mvm_pallas(a_pos, a_neg, w, gain, off, te,
                                jnp.asarray([live], jnp.int32),
                                faithful=faithful, block_m=bm,
                                block_n=128, interpret=True)
        assert got.shape == (r, n)
        for t in range(live):
            rows = slice(t * bm, (t + 1) * bm)
            want = R.analog_mvm_split_ref(
                a_pos[rows], a_neg[rows], w[tiles[t]], gain[tiles[t]],
                off[tiles[t]], faithful=faithful)
            tol = 0.0 if faithful else 2.0   # fast: summation-order LSBs
            assert float(jnp.abs(got[rows] - want).max()) <= tol
        # the jnp path of the public wrapper agrees on the live rows
        ref = ops.expert_mvm(a_pos, a_neg, w, gain, off, te,
                             jnp.asarray([live], jnp.int32), block_m=bm,
                             faithful=faithful, use_pallas=False)
        tol = 0.0 if faithful else 2.0
        diff = jnp.abs(got[:live * bm] - ref[:live * bm])
        assert float(diff.max(initial=0.0)) <= tol


class TestAnalogPlanMegakernel:
    """Whole-plan megakernel vs the pure-jnp packed-chain oracle."""

    @pytest.mark.parametrize("dims", [
        [(256, 128), (128, 64)],
        [(128, 123), (123, 123), (123, 10)],      # odd widths, chunk pads
        [(512, 512), (512, 512), (512, 512)],
    ])
    @pytest.mark.parametrize("faithful", [True, False])
    def test_fp32_exact_vs_oracle(self, dims, faithful):
        from repro.kernels.analog_plan import analog_plan_pallas

        pack = _pack_chain(dims)
        b = 12
        x = jnp.round(jax.random.uniform(KEY, (b, dims[0][0])) * 31)
        x = jnp.pad(x, ((0, 0), (0, pack.schedule[0].k_pad - dims[0][0])))
        got = analog_plan_pallas(
            x, pack.w_cat, pack.gain, pack.off, schedule=pack.schedule,
            chunk_rows=pack.chunk_rows, faithful=faithful, block_b=4,
            interpret=True,
        )
        want = R.analog_plan_ref(
            x, pack.w_cat, pack.gain, pack.off, pack.schedule,
            chunk_rows=pack.chunk_rows, faithful=faithful,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_flatten_chain_exact(self):
        """im2col-style flatten inside the kernel: the position rows merge
        into the next layer's contraction axis in VMEM."""
        from repro.kernels.analog_plan import analog_plan_pallas

        pack = _pack_chain([(128, 8), (256, 64)], flatten=[True, False])
        assert pack.schedule[0].flatten == 32
        b, npos = 6, 32
        x = jnp.round(jax.random.uniform(KEY, (b * npos, 128)) * 31)
        got = analog_plan_pallas(
            x, pack.w_cat, pack.gain, pack.off, schedule=pack.schedule,
            chunk_rows=pack.chunk_rows, block_b=2, interpret=True,
        )
        want = R.analog_plan_ref(x, pack.w_cat, pack.gain, pack.off,
                                 pack.schedule, chunk_rows=pack.chunk_rows)
        assert got.shape == (b, 64)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("block_b", [1, 3, 8, 16])
    def test_block_shape_invariance_and_batch_padding(self, block_b):
        """Batch blocking (and the zero-code pad rows it introduces) must
        not change any real row - rows are independent end to end."""
        from repro.kernels.analog_plan import analog_plan_pallas

        pack = _pack_chain([(256, 200), (200, 40)], seed=5)
        b = 10
        x = jnp.round(jax.random.uniform(KEY, (b, 256)) * 31)
        got = analog_plan_pallas(
            x, pack.w_cat, pack.gain, pack.off, schedule=pack.schedule,
            chunk_rows=pack.chunk_rows, block_b=block_b, interpret=True,
        )
        want = R.analog_plan_ref(x, pack.w_cat, pack.gain, pack.off,
                                 pack.schedule, chunk_rows=pack.chunk_rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_output_is_integer_valued_codes(self):
        from repro.kernels.analog_plan import analog_plan_pallas

        pack = _pack_chain([(128, 64), (64, 32)], seed=2)
        x = jnp.round(jax.random.uniform(KEY, (8, 128)) * 31)
        y = np.asarray(analog_plan_pallas(
            x, pack.w_cat, pack.gain, pack.off, schedule=pack.schedule,
            chunk_rows=pack.chunk_rows, block_b=8, interpret=True,
        ))
        np.testing.assert_array_equal(y, np.round(y))


class TestMaxMinPoolKernel:
    @pytest.mark.parametrize("b,t,window", [(1, 128, 32), (5, 4096, 32),
                                            (16, 1024, 16), (3, 96, 32)])
    def test_vs_oracle(self, b, t, window):
        x = jax.random.normal(jax.random.fold_in(KEY, b * t), (b, t))
        got = maxmin_pool_2d_pallas(x, window=window, interpret=True)
        want = R.maxmin_pool_ref(x, window=window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_nonneg_output(self):
        x = jax.random.normal(KEY, (4, 512))
        y = ops.maxmin_pool(x, 32, use_pallas=False)
        assert float(y.min()) >= 0.0  # max - min >= 0: positive activations

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
    def test_dtypes(self, dtype):
        x = (jax.random.normal(KEY, (2, 256)) * 100).astype(dtype)
        got = maxmin_pool_2d_pallas(x, window=32, interpret=True)
        want = R.maxmin_pool_ref(x, window=32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert got.dtype == dtype
