"""LFM2 (short-conv and attention layers, a held-expert MoE layer) on the
program's normal path against the plain reference
(``benchmarks/chip/reference/lfm2.py``), at ``SMOKE`` size on the CPU:
seeded weights, static activation LSBs from the reference's calibration
pass, analog faithful mode with the Pallas kernels interpreted.

Tolerances: on the CPU the program and the reference run the same fp32
operations in the same order (the analog arithmetic is integer codes and
ADC counts, exact), so the logits agree to ``ATOL``, far below the
0.01-0.1 logit scale of these models; the bfloat16 reference, the
control, misses by more than ``10 * ATOL`` (``test_control_fails``)."""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, configs
from repro.configs.base import RunConfig
from repro.core.analog import AnalogConfig
from repro.models import moe as M
from repro.models import transformer as T
from repro.serve import serve_step as SS

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARCH = configs.get_smoke("lfm2-8b-a1b")
RUN = RunConfig(analog=AnalogConfig(mode="analog_faithful",
                                    act_calib="static", use_pallas=True),
                activation_dtype="float32")
# fp32 reduction order only: the program and the reference agree exactly
# on the CPU; a summation-order flip would move a logit by ~1e-7
ATOL = 1e-5


def _reference():
    path = ROOT / "benchmarks" / "chip" / "reference" / "lfm2.py"
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def ref_config(cfg=ARCH) -> dict:
    """The reference's configuration of an ``ArchConfig``."""
    types = ["full_attention" if k.startswith("attn") else "conv"
             for k in cfg.layer_kinds]
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "vocab_size": cfg.vocab_size,
        "layer_types": types, "num_hidden_layers": cfg.n_layers,
        "num_dense_layers": sum(k.endswith("_mlp")
                                for k in cfg.layer_kinds),
        "norm_eps": 1e-5, "rope_theta": cfg.rope_theta,
        "conv_L_cache": cfg.conv_taps, "num_experts_per_tok": cfg.top_k,
        "analog": {"chunk_rows": RUN.analog.chunk_rows},
    }


@pytest.fixture(scope="module")
def weights():
    """SMOKE weights with a seeded selection bias and calibrated LSBs."""
    p = T.lm_init(jax.random.PRNGKey(0), ARCH)
    for i, kind in enumerate(ARCH.layer_kinds):
        if kind.endswith("_moe"):
            moe = p["layers"][f"l{i}"]["moe"]
            moe["expert_bias"] = 0.01 * jax.random.normal(
                jax.random.PRNGKey(100 + i), moe["expert_bias"].shape)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 16),
                                           0, ARCH.vocab_size))
    return REF.calibrate(p, ref_config(), tokens)


@pytest.fixture(scope="module")
def lowered(weights):
    return api.compile(T.lm_module_spec(ARCH, weights), weights,
                       RUN).lower()


def _prompts(b=2, s=12, seed=2):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (b, s),
                                         0, ARCH.vocab_size), np.int32)


def _prefill(lowered, tokens, max_len):
    cache = T.init_lm_cache(ARCH, tokens.shape[0], max_len,
                            dtype=jnp.float32)
    return SS.serve_prefill(lowered, {"tokens": jnp.asarray(tokens)},
                            cache, cfg=ARCH, run=RUN)


def test_prefill_logits_match_reference(weights, lowered):
    tokens = _prompts()
    logits, _ = _prefill(lowered, tokens, 12)
    ref = REF.Replay(weights, ref_config(), "highest")
    want, _ = ref.run(tokens, np.zeros((2, 1), np.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want[0]),
                               rtol=0, atol=ATOL)
    assert float(jnp.abs(want[0]).max()) > 100 * ATOL


def test_control_fails(weights, lowered):
    """The same model in bfloat16 misses the program by far more than the
    tolerance: the comparison is tight enough to see the precision."""
    tokens = _prompts()
    logits, _ = _prefill(lowered, tokens, 12)
    low = REF.Replay(weights, ref_config(), "bfloat16")
    got, _ = low.run(tokens, np.zeros((2, 1), np.int32))
    assert float(jnp.abs(logits - got[0]).max()) > 10 * ATOL


def test_serve_runs_three_pass_dots(lowered):
    """Every analog kernel of a serve compile, dense and grouped, makes
    three bf16 dots per signed-split pass, and tracing them counts."""
    from repro.obs import metrics as obs_metrics

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for v in e.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)

    obs_metrics.reset_metrics()
    jaxpr = jax.make_jaxpr(lambda t: _prefill(lowered, t, 11)[0])(
        _prompts(3, 11, seed=5))
    names = []
    for e in eqns(jaxpr.jaxpr):
        if e.primitive.name == "pallas_call":
            names.append(e.params["name"])
            dots = [d for d in eqns(e.params["jaxpr"])
                    if d.primitive.name == "dot_general"]
            assert len(dots) == 2 * 3, e.params["name"]
            assert {v.aval.dtype for d in dots for v in d.invars} == {
                jnp.dtype(jnp.bfloat16)}
    grouped = names.count("expert_mvm_pallas")
    assert grouped > 0 and len(names) > grouped, names
    assert obs_metrics.counter("analog.mvm.bf16x3_dots").value > 0


def test_decode_through_cache_equals_full_forward(weights, lowered):
    """Left-padded prompts of mixed lengths: prefill, then three decode
    steps through both kinds of state (conv: the last two gated inputs;
    attention: keys and values) give each position the logits of one
    forward over the whole sequence, without a cache."""
    lengths, new = (5, 9), 3
    b, p = len(lengths), max(lengths)
    toks = np.zeros((b, p), np.int32)
    for i, n in enumerate(lengths):
        toks[i, p - n:] = _prompts(1, n, seed=10 + i)[0]
    logits, cache = _prefill(lowered, toks, p + new)
    steps, seq = [logits], toks
    for _ in range(new):
        nxt = jnp.argmax(steps[-1], -1).astype(jnp.int32)[:, None]
        seq = np.concatenate([seq, np.asarray(nxt)], axis=1)
        logits, cache = SS.serve_decode(lowered, nxt, cache, cfg=ARCH,
                                        run=RUN)
        steps.append(logits)
    full, _, _ = T.lm_apply(lowered, {"tokens": jnp.asarray(seq)}, ARCH, RUN)
    for i, got in enumerate(steps):
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(full[:, p - 1 + i]),
                                   rtol=0, atol=ATOL)


def _moe_node(weights, held):
    """Layer 2's MoE node holding the experts ``held`` of its weights."""
    moe = weights["layers"]["l2"]["moe"]
    idx = jnp.asarray(held)
    return {**moe, "held": moe["held"][idx],
            "experts": jax.tree.map(lambda a: a[idx], moe["experts"])}


def test_held_shares_add_up_to_the_uncut_layer(weights):
    """Guide §4's share test: the partial outputs of chips holding
    disjoint sets of experts add up to the layer holding all of them."""
    full_arch = dataclasses.replace(ARCH, held_experts=ARCH.n_experts)
    p = T.lm_init(jax.random.PRNGKey(3), full_arch)
    moe = p["layers"]["l2"]["moe"]
    moe["expert_bias"] = 0.01 * jax.random.normal(
        jax.random.PRNGKey(4), moe["expert_bias"].shape)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 12, ARCH.d_model))
    acfg = RUN.analog
    whole, stats = M.held_moe_apply(moe, u, acfg=acfg, top_k=ARCH.top_k)
    parts = [M.held_moe_apply(_moe_node(p, h), u, acfg=acfg,
                              top_k=ARCH.top_k)
             for h in ([0, 1, 2, 3], [4, 5, 6, 7])]
    total = parts[0][0] + parts[1][0]
    # one sum of up to 4 terms against two partial sums: fp32 association
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=1e-6)
    rows = np.concatenate([np.asarray(s["rows"]) for _, s in parts])
    np.testing.assert_array_equal(rows, np.asarray(stats["rows"]))
    assert int(rows.sum()) == 2 * 12 * ARCH.top_k    # every pair, once


def test_forced_skew_drops_nothing(weights):
    """Every token sent to one held expert: its group spans many row
    tiles, and every routed row is still computed (the reference runs
    every held expert over every token)."""
    node = dict(weights["layers"]["l3"]["moe"])
    node["expert_bias"] = node["expert_bias"].at[1].add(10.0)
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 40, ARCH.d_model))
    got, stats = M.held_moe_apply(node, u, acfg=RUN.analog,
                                  top_k=ARCH.top_k)
    assert int(stats["rows"][1]) == 80
    ref = REF.Model(ref_config(), "highest")
    want, _ = ref.moe("m", node, u.reshape(80, ARCH.d_model))
    np.testing.assert_allclose(np.asarray(got).reshape(80, -1),
                               np.asarray(want), rtol=0, atol=ATOL)


def test_module_specs_on_the_front_door(weights):
    """The short-conv mixer and the held-expert layer each compile through
    ``api.compile`` on their own and replay what the model path computes
    from raw parameters (lowered per call)."""
    from repro.models import shortconv as C

    u = jax.random.normal(jax.random.PRNGKey(7), (2, 12, ARCH.d_model))
    conv = weights["layers"]["l0"]["conv"]
    spec = C.shortconv_module_spec(ARCH.d_model, ARCH.conv_taps)
    got, st = api.compile(spec, conv, RUN).apply(u)
    want, st_want = C.shortconv_apply(conv, u, acfg=RUN.analog)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(st_want))
    moe = weights["layers"]["l2"]["moe"]
    spec = M.held_moe_module_spec(ARCH.d_model, ARCH.moe_d_ff,
                                  ARCH.held_experts, top_k=ARCH.top_k)
    got, stats = api.compile(spec, moe, RUN).apply(u)
    want, _ = M.held_moe_apply(moe, u, acfg=RUN.analog, top_k=ARCH.top_k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(stats["rows"].sum()) > 0


def test_router_counts_and_tiles():
    """The grouped layout: groups padded to whole tiles, in held order,
    dead tiles past the live ones."""
    sel = jnp.asarray([[0, 5, 2, 7], [2, 3, 1, 0], [6, 2, 4, 0]])
    held = jnp.asarray([0, 2, 3], jnp.int32)
    rows, row, is_held, token, counts = M.held_rows(sel, held, 8)
    np.testing.assert_array_equal(np.asarray(counts), [3, 3, 1])
    assert int(rows.live_tiles[0]) == 3
    np.testing.assert_array_equal(np.asarray(rows.tile_expert)[:3],
                                  [0, 1, 2])
    assert int(rows.row_live.sum()) == 7
    for t in range(3):
        for j in range(4):
            if is_held[t, j]:
                assert int(token[row[t, j]]) == t


def test_param_counts_pin_the_published_size():
    """FULL at the published 8.3 B (8.34 B with the head tied), about
    1.5 B active; one chip's six-layer share holds about 670 M."""
    cfg = configs.get_arch("lfm2-8b-a1b")
    assert abs(cfg.param_count() / 8.34e9 - 1) < 0.01
    assert abs(cfg.param_count() / 8.3e9 - 1) < 0.01
    assert 1.45e9 < cfg.active_param_count() < 1.6e9
    share = dataclasses.replace(cfg, n_layers=6,
                                layer_kinds=cfg.layer_kinds[:6],
                                held_experts=8)
    assert abs(share.param_count(held=True) / 670e6 - 1) < 0.01



@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k"])
def test_full_long_sequences_take_flash_attention(monkeypatch, shape):
    """FULL lowers its attention layers blockwise past the flash threshold,
    without a cache (the training forward) and into one (the served
    prefill): no layer builds the S x S scores of the dense path."""
    from repro.configs.base import SHAPES
    from repro.models import attention as A

    cfg = configs.get_arch("lfm2-8b-a1b")
    sh = SHAPES[shape]
    b, s = sh.global_batch, sh.seq_len
    dense = []
    real = A._dense_attention

    def spy(q, k, v, **kw):
        dense.append(k.shape[1])
        return real(q, k, v, **kw)

    monkeypatch.setattr(A, "_dense_attention", spy)
    run = RunConfig(analog=AnalogConfig(mode="digital"))
    params = jax.eval_shape(lambda k: T.lm_init(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if sh.kind == "train":
        step = jax.jit(lambda p, t: T.lm_apply(p, t, cfg, run)[0])
        text = step.lower(params, tokens).as_text()
    else:
        cache = jax.eval_shape(lambda: T.init_lm_cache(cfg, b, s))
        prefill, _ = SS.make_serve_steps(cfg, run)
        text = prefill.lower(params, tokens, cache).as_text()
    assert dense == []
    assert f"{s}x{s}" not in text


def test_long_prompt_into_the_cache_matches_dense():
    """Past the flash threshold a prompt written into the cache attends
    blockwise; it matches the dense scores over the cache."""
    from repro.models import attention as A

    cfg = ARCH
    p = T.lm_init(jax.random.PRNGKey(3), cfg)["layers"]["l2"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    digital = AnalogConfig(mode="digital")
    out = {}
    for thr in (8, 4096):
        cache = A.init_cache(2, 40, cfg.n_kv_heads, cfg.hd, jnp.float32)
        out[thr], _ = A.attention_apply(
            p, x, positions=jnp.broadcast_to(jnp.arange(24), (2, 24)),
            acfg=digital, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd, rope_theta=cfg.rope_theta, cache=cache,
            flash_threshold=thr)
    # the two paths differ only in how the softmax is summed (per block
    # of keys, then across blocks): fp32 rounding
    np.testing.assert_allclose(out[8], out[4096], rtol=1e-5, atol=1e-6)
