"""Data-substrate tests: ECG synthesis statistics, bit-exact preprocessing
chain, pipeline determinism/shardability (hypothesis property tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property suites need hypothesis (requirements-dev)"
)
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro import obs
from repro.data.ecg_synth import ECGDatasetConfig, make_dataset, synth_record
from repro.data.lm_data import DataConfig, SyntheticLM
from repro.data.preprocess import _preprocess, preprocess
from repro.kernels.preproc import preprocess_pallas


def fig7_codes(raw, window=32, quant_shift=4):
    """The Fig. 7 chain in numpy: derivative, max-min over each whole
    window, floor(. / 2**quant_shift) clipped to [0, 31]."""
    deriv = np.diff(raw, axis=-1)
    n = deriv.shape[-1] // window
    win = deriv[..., :n * window].reshape(deriv.shape[:-1] + (n, window))
    pooled = win.max(-1) - win.min(-1)
    return np.clip(np.floor(pooled / 2.0 ** quant_shift), 0, 31)


def ramp_windows(b, c, t, window, quant_shift, samples, seed):
    """f32[b, c, t] raw samples whose windows' max-min spans 0 to about
    40 codes: some windows quantize to 0, some clip at 31.  ``samples``:
    "integer" (integer-valued, as ADC counts) or "fraction" (non-integer
    fp32)."""
    rng = np.random.default_rng(seed)
    amp = rng.uniform(0, 40, (b, c, t // window + 1)) * 2.0 ** quant_shift
    amp = np.repeat(amp, window, axis=-1)[..., :t - 1]
    step = amp * rng.uniform(-0.5, 0.5, (b, c, t - 1))
    if samples == "integer":
        step = np.round(step)
    start = rng.integers(1024, 3072, (b, c, 1)).astype(np.float64)
    raw = np.concatenate([start, start + np.cumsum(step, -1)], -1)
    return raw.astype(np.float32)


class TestECGSynth:
    def test_deterministic(self):
        a = synth_record(1, 7, True)
        b = synth_record(1, 7, True)
        np.testing.assert_array_equal(a, b)
        c = synth_record(1, 8, True)
        assert not np.array_equal(a, c)

    def test_shape_and_range(self):
        r = synth_record(0, 0, False)
        assert r.shape == (2, 4033)
        assert r.min() >= 0 and r.max() <= 4095  # 12-bit ADC counts

    def test_afib_rr_irregularity(self):
        """A-fib records must show higher RR-interval variability - the
        discriminating statistic the classifier learns."""

        def rr_cv(rec):
            x = rec[0] - rec[0].mean()
            # crude R-peak detection on the dominant channel
            thr = np.percentile(x, 99)
            peaks = np.where(
                (x[1:-1] > thr) & (x[1:-1] >= x[:-2]) & (x[1:-1] >= x[2:])
            )[0]
            rr = np.diff(peaks)
            rr = rr[rr > 30]
            return np.std(rr) / (np.mean(rr) + 1e-9) if len(rr) > 3 else 0.0

        cv_sinus = np.mean([rr_cv(synth_record(3, i, False))
                            for i in range(8)])
        cv_afib = np.mean([rr_cv(synth_record(3, i, True))
                           for i in range(8)])
        assert cv_afib > 1.5 * cv_sinus, (cv_sinus, cv_afib)

    def test_dataset_split_disjoint_and_balanced(self):
        cfg = ECGDatasetConfig(n_train=64, n_test=32)
        xtr, ytr = make_dataset(cfg, "train")
        xte, yte = make_dataset(cfg, "test")
        assert xtr.shape == (64, 2, 4033) and xte.shape == (32, 2, 4033)
        assert 0.2 < ytr.mean() < 0.8
        # different index ranges -> no record collisions
        assert not np.array_equal(xtr[0], xte[0])


class TestPreprocess:
    def test_output_is_5bit_codes(self):
        x, _ = make_dataset(ECGDatasetConfig(n_train=4, n_test=1), "train")
        out = np.asarray(preprocess(jnp.asarray(x)))
        assert out.shape == (4, 2, 126)
        assert out.min() >= 0 and out.max() <= 31
        np.testing.assert_array_equal(out, np.round(out))

    def test_bit_exact_reference(self):
        """Fig. 7 chain reproduced step-by-step in numpy."""
        rng = np.random.default_rng(0)
        raw = rng.integers(0, 4096, (3, 2, 4033)).astype(np.float32)
        got = np.asarray(preprocess(jnp.asarray(raw)))
        np.testing.assert_array_equal(got, fig7_codes(raw))

    # (B, C, T, window, quant_shift, samples): partial blocks (B = 1, 3,
    # 9), one and two channels, (T-1) % window != 0, every shift
    FUSED_CASES = [
        (1, 2, 4033, 32, 4, "integer"),
        (3, 1, 4033, 32, 0, "fraction"),
        (9, 2, 4033, 32, 6, "integer"),
        (3, 2, 1000, 32, 4, "fraction"),
        (9, 1, 700, 16, 0, "integer"),
        (1, 2, 4033, 16, 6, "fraction"),
        (3, 2, 513, 16, 4, "integer"),
    ]

    @pytest.mark.parametrize("b,c,t,window,shift,samples", FUSED_CASES)
    def test_fused_kernel_bit_exact(self, b, c, t, window, shift, samples):
        """The fused Pallas kernel (interpreted) against the numpy chain,
        on windows that quantize to 0, into [1, 30] and past 31."""
        raw = ramp_windows(b, c, t, window, shift, samples,
                           seed=b * t + window + shift)
        got = preprocess_pallas(jnp.asarray(raw), window=window,
                                quant_shift=shift, interpret=True)
        want = fig7_codes(raw, window, shift)
        assert got.dtype == jnp.float32 and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), want)
        assert want.min() == 0 and want.max() == 31   # both clips hit

    @pytest.mark.parametrize("shape", [(3, 2, 4033), (2, 4033),
                                       (2, 3, 1, 1000)])
    def test_pallas_path_equals_jnp_path(self, shape):
        raw = ramp_windows(int(np.prod(shape[:-2])), shape[-2], shape[-1],
                           32, 4, "fraction", seed=len(shape))
        raw = jnp.asarray(raw.reshape(shape))
        got = preprocess(raw, use_pallas=True)
        want = preprocess(raw, use_pallas=False)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_default_flag_runs_jnp_chain_on_cpu(self):
        """``use_pallas=None`` is the platform's choice: the jnp chain on
        the CPU, bit for bit."""
        raw = jnp.asarray(ramp_windows(3, 2, 4033, 32, 4, "fraction", 5))
        assert jax.default_backend() == "cpu"
        got = preprocess(raw)
        want = preprocess(raw, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got),
                                      fig7_codes(np.asarray(raw)))

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_call_moves_nothing_but_its_samples(self, use_pallas):
        """A call on samples already on the device transfers nothing
        more: ``window`` and ``quant_shift`` are compile-time constants."""
        raw = jax.device_put(ramp_windows(2, 2, 4033, 32, 4, "integer", 3))
        with jax.transfer_guard_host_to_device("disallow"):
            for _ in range(2):      # the compiling call, then a cached one
                out = preprocess(raw, use_pallas=use_pallas)
            jax.block_until_ready(out)

    def test_positive_activations(self):
        """max-min pooling guarantees non-negative activations (paper:
        'provides positive activations')."""
        raw = np.random.default_rng(1).normal(2048, 300, (2, 2, 4033))
        out = np.asarray(preprocess(jnp.asarray(raw.astype(np.float32))))
        assert out.min() >= 0


class TestPreprocessHostStage:
    """``preprocess`` is a host stage around the jitted chain: one span
    and one ``ecg.preprocess_us`` sample per eager call while a collector
    or a profiler records, none otherwise or at trace time, and the same
    codes as the chain."""

    RAW = np.random.default_rng(2).integers(0, 4096, (3, 2, 4033)).astype(
        np.float32)

    @staticmethod
    def _samples():
        h = obs.registry().get("ecg.preprocess_us")
        return 0 if h is None else h.count

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_equals_jitted_chain_bit_for_bit(self, use_pallas):
        raw = jnp.asarray(self.RAW)
        want = _preprocess(raw, use_pallas=use_pallas)
        got = preprocess(raw, use_pallas=use_pallas)
        with obs.collect():
            got_observed = preprocess(raw, use_pallas=use_pallas)
        for g in (got, got_observed):
            assert g.dtype == want.dtype and g.shape == want.shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(want))

    def test_each_eager_call_adds_one_sample(self):
        raw = jnp.asarray(self.RAW)
        before = self._samples()
        with obs.collect() as tr:
            for _ in range(3):
                preprocess(raw)
        assert self._samples() == before + 3
        h = obs.registry().get("ecg.preprocess_us")
        assert all(v > 0 for v in h.samples[-3:])
        assert [s["path"] for s in tr.spans()] == ["ecg.preprocess"] * 3

    def test_unobserved_call_adds_no_sample(self):
        raw = jnp.asarray(self.RAW)
        assert not obs.observed()
        before = self._samples()
        preprocess(raw)
        assert self._samples() == before

    def test_inside_jit_adds_no_sample(self):
        raw = jnp.asarray(self.RAW)
        before = self._samples()
        with obs.collect():
            got = jax.jit(lambda r: preprocess(r) + 0)(raw)
        assert self._samples() == before
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(_preprocess(raw)))

    def test_repeated_calls_do_not_grow_jit_cache(self):
        raw = jnp.asarray(self.RAW)
        preprocess(raw)
        size = _preprocess._cache_size()
        for _ in range(3):
            preprocess(raw)
        with obs.collect():
            for _ in range(3):
                preprocess(raw)
        assert _preprocess._cache_size() == size

    def test_stages_carry_named_scopes(self):
        text = _preprocess.lower(jnp.asarray(self.RAW)).as_text(
            debug_info=True)
        for scope in ("ecg.derivative", "ecg.maxmin_pool", "ecg.quantize"):
            assert scope in text, scope


class TestLMData:
    def test_deterministic_and_step_indexed(self):
        d = SyntheticLM(DataConfig(vocab_size=64, seq_len=16,
                                   global_batch=4))
        b1 = d.batch(3)
        b2 = d.batch(3)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        b3 = d.batch(4)
        assert not np.array_equal(b1["tokens"], b3["tokens"])

    def test_labels_are_shifted_tokens(self):
        d = SyntheticLM(DataConfig(vocab_size=64, seq_len=16,
                                   global_batch=2))
        b = d.batch(0)
        ex = d.example(0)
        np.testing.assert_array_equal(b["tokens"][0], ex[:-1])
        np.testing.assert_array_equal(b["labels"][0], ex[1:])

    @given(st.integers(0, 50), st.integers(1, 4))
    @settings(max_examples=10, deadline=None)
    def test_shards_partition_global_batch(self, step, log2_shards):
        """Union of shard batches == global batch, no overlap (resumable
        sharded pipeline invariant)."""
        n_shards = 2 ** log2_shards if log2_shards <= 2 else 4
        d = SyntheticLM(DataConfig(vocab_size=32, seq_len=8,
                                   global_batch=8))
        full = d.batch(step)["tokens"]
        parts = [
            d.batch(step, shard=s, n_shards=n_shards)["tokens"]
            for s in range(n_shards)
        ]
        np.testing.assert_array_equal(np.concatenate(parts), full)

    def test_vocabulary_range(self):
        d = SyntheticLM(DataConfig(vocab_size=50, seq_len=64,
                                   global_batch=2))
        b = d.batch(0)
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 50
