"""Plan store tests (ISSUE 8): ``.npz`` save -> load -> replay bit-exact
for stack, tree (all three group kinds), block and megakernel plans;
calibration hot-swaps applied AFTER load; packed codes matching the
legacy fp32 bake across faithful/fast x pallas/jnp; the ServeEngine plan
cache cold-starting with ZERO lowering work; and the version gate."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.exec as E
from repro import api
from repro.api.compile import swap_calibration
from repro.calib.snapshot import CalibrationSnapshot, LayerCalibration
from repro.configs.base import ArchConfig, RunConfig
from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.noise import NOISELESS, NoiseConfig
from repro.exec.lower import lowering_count, reset_lowering_count
from repro.exec.store import FORMAT_VERSION, load_plan, save_plan
from repro.models import attention as A
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import transformer as T

KEY = jax.random.PRNGKey(7)
ACFG = AnalogConfig(noise=NOISELESS)
MODES = [("analog_faithful", False), ("analog_faithful", True),
         ("analog_fast", False), ("analog_fast", True)]

ARCH = ArchConfig(name="t-store", family="dense", n_layers=2, d_model=64,
                  n_heads=2, n_kv_heads=2, d_ff=96, vocab_size=64,
                  remat=False)
SEQ = 8
# the plan's two replays: the megakernel route and the per-layer one
REPLAYS = pytest.mark.parametrize(
    "replay", [E.run, E.run_layers], ids=["run", "run_layers"])


def _cfg(mode, pallas, **kw):
    return AnalogConfig(mode=mode, use_pallas=pallas, noise=NoiseConfig(),
                        **kw)


def _assert_tree_bitexact(got, want):
    """Same treedef, every leaf bitwise identical (dtype included)."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for lg, lw in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        ag, aw = np.asarray(lg), np.asarray(lw)
        assert ag.dtype == aw.dtype
        np.testing.assert_array_equal(ag, aw)


def _mixed_stack(acfg, seed=0):
    """codes-in chain with a megakernel packing baked."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    layers = [
        analog_linear_init(ks[0], 32, 48, noise=NoiseConfig()),
        analog_linear_init(ks[1], 48, 40, noise=NoiseConfig()),
        analog_linear_init(ks[2], 40, 24, noise=NoiseConfig()),
    ]
    return E.lower_stack(
        layers, acfg,
        epilogues=["relu_shift", "none", "none"],
        input_domain="codes",
    )


def _codes(b, k, seed=9):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (b, k), 0, 32
    ).astype(jnp.float32)


# ------------------------------------------------------------ stack plans
class TestStackRoundTrip:
    @REPLAYS
    def test_stack_roundtrip_bit_exact(self, tmp_path, replay):
        acfg = AnalogConfig(mode="analog_faithful", act_calib="static",
                            noise=NoiseConfig())
        plan = _mixed_stack(acfg)
        assert plan.mega is not None
        assert plan.layers[0].store.codes.dtype == jnp.int8
        path = str(tmp_path / "stack.npz")
        save_plan(path, plan)

        reset_lowering_count()
        loaded = load_plan(path)
        assert lowering_count() == 0           # cache load = zero lowering
        assert loaded.mega is not None         # re-packed, not re-lowered
        _assert_tree_bitexact(loaded, plan)

        x = _codes(5, 32)
        np.testing.assert_array_equal(
            np.asarray(replay(loaded, x)), np.asarray(replay(plan, x)),
        )

    def test_codes_stay_int8_on_disk(self, tmp_path):
        plan = _mixed_stack(AnalogConfig(act_calib="static",
                                         noise=NoiseConfig()))
        path = str(tmp_path / "stack.npz")
        save_plan(path, plan)
        with np.load(path, allow_pickle=False) as z:
            dtypes = {str(z[k].dtype) for k in z.files if k != "__tree__"
                      and k != "__version__"}
        assert "int8" in dtypes                # the packed-bytes win

    def test_megakernel_pack_not_saved_directly(self, tmp_path):
        plan = _mixed_stack(AnalogConfig(act_calib="static",
                                         noise=NoiseConfig()))
        with pytest.raises(TypeError):
            save_plan(str(tmp_path / "mega.npz"), plan.mega)

    def test_unknown_version_refused(self, tmp_path):
        path = str(tmp_path / "bad.npz")
        np.savez(path, __version__=np.asarray("repro-plan-v999"),
                 __tree__=np.asarray(json.dumps({"t": "none"})))
        assert FORMAT_VERSION != "repro-plan-v999"
        with pytest.raises(ValueError, match="re-lower and re-save"):
            load_plan(path)


# -------------------------------------------- trees, all three group kinds
class TestTreeRoundTrip:
    def test_column_concat_tree(self, tmp_path):
        p = A.attention_init(KEY, 64, 4, 2, 16, noise=NOISELESS)
        lowered = api.lower_tree(p, ACFG)
        assert lowered["_groups"]["qkv"].kind == "column_concat"
        path = str(tmp_path / "attn.npz")
        save_plan(path, lowered)
        reset_lowering_count()
        loaded = load_plan(path)
        assert lowering_count() == 0
        _assert_tree_bitexact(loaded, lowered)

        x = jax.random.normal(KEY, (2, 8, 64)) * 0.3
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None],
                               (2, 8))
        kw = dict(positions=pos, acfg=ACFG, n_heads=4, n_kv_heads=2,
                  head_dim=16, rope_theta=1e4)
        want, _ = A.attention_apply(lowered, x, **kw)
        got, _ = A.attention_apply(loaded, x, **kw)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_batch_concat_tree(self, tmp_path):
        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads)
        lowered = api.compile(
            R.rwkv_module_spec(d, heads), params, ACFG
        ).lower()
        assert lowered["_groups"]["rkvg"].kind == "batch_concat"
        path = str(tmp_path / "rwkv.npz")
        save_plan(path, lowered)
        loaded = load_plan(path)
        _assert_tree_bitexact(loaded, lowered)

        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, d)) * 0.3
        want, _ = R.rwkv_apply(lowered, x, acfg=ACFG, n_heads=heads)
        got, _ = R.rwkv_apply(loaded, x, acfg=ACFG, n_heads=heads)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_expert_stack_tree(self, tmp_path):
        d, ff, e, top_k = 64, 32, 4, 2
        params = M.moe_init(KEY, d, ff, e)
        lowered = api.compile(
            M.moe_module_spec(d, ff, e, top_k=top_k), params, ACFG
        ).lower()
        assert lowered["_groups"]["up"].kind == "expert_stack"
        path = str(tmp_path / "moe.npz")
        save_plan(path, lowered)
        loaded = load_plan(path)
        _assert_tree_bitexact(loaded, lowered)

        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, d)) * 0.3
        want, _ = M.moe_apply(lowered, x, acfg=ACFG, top_k=top_k)
        got, _ = M.moe_apply(loaded, x, acfg=ACFG, top_k=top_k)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# ------------------------------------------------------------- block plans
class TestBlockRoundTrip:
    @REPLAYS
    def test_block_roundtrip_megakernel_replay(self, tmp_path, replay):
        acfg = AnalogConfig(mode="analog_faithful", act_calib="static")
        plan = E.lower_block(
            T._layer_init(jax.random.PRNGKey(0), "attn_mlp", ARCH), acfg,
            n_heads=ARCH.n_heads, n_kv_heads=ARCH.n_kv_heads,
            head_dim=ARCH.hd, seq=SEQ, rope_theta=ARCH.rope_theta,
        )
        assert plan.block is not None and plan.mega is not None
        path = str(tmp_path / "block.npz")
        save_plan(path, plan)
        reset_lowering_count()
        loaded = load_plan(path)
        assert lowering_count() == 0
        assert loaded.mega is not None
        _assert_tree_bitexact(loaded, plan)

        x = jax.random.normal(jax.random.PRNGKey(1),
                              (3, SEQ, ARCH.d_model)) * 0.5
        np.testing.assert_array_equal(
            np.asarray(replay(loaded, x)), np.asarray(replay(plan, x)),
        )


# ----------------------------------------------- hot-swaps AFTER the load
class TestPostLoadHotSwap:
    def test_stack_offsets_swap_after_load(self, tmp_path):
        plan = _mixed_stack(AnalogConfig(act_calib="static",
                                         noise=NoiseConfig()))
        path = str(tmp_path / "stack.npz")
        save_plan(path, plan)
        loaded = load_plan(path)

        off0 = loaded.layers[0].chunk_offset
        assert off0 is not None
        table = jax.random.normal(KEY, off0.shape) * 0.1
        swapped = E.plan_with_offsets(
            loaded, [table] + [None] * (len(loaded.layers) - 1))
        assert jax.tree.structure(swapped) == jax.tree.structure(loaded)
        np.testing.assert_array_equal(
            np.asarray(swapped.layers[0].chunk_offset),
            np.asarray(table))
        # weights untouched: the swap moves offset leaves only
        np.testing.assert_array_equal(
            np.asarray(swapped.layers[0].store.codes),
            np.asarray(loaded.layers[0].store.codes))
        np.testing.assert_array_equal(
            np.asarray(swapped.layers[0].store.w_eff),
            np.asarray(loaded.layers[0].store.w_eff))
        # drifted replay actually uses the new tables
        x = _codes(4, 32)
        y0 = E.run(loaded, x)
        y1 = E.run(swapped, x)
        assert not np.array_equal(np.asarray(y0), np.asarray(y1))

    def test_tree_calibration_swap_after_load(self, tmp_path):
        d, heads = 64, 4
        params = R.rwkv_init(KEY, d, heads, noise=NoiseConfig())
        lowered = api.compile(
            R.rwkv_module_spec(d, heads, noise=NoiseConfig()), params,
            AnalogConfig(noise=NoiseConfig()),
        ).lower()
        path = str(tmp_path / "rwkv.npz")
        save_plan(path, lowered)
        loaded = load_plan(path)

        gp = loaded["_groups"]["rkvg"]
        c = gp.fused.chunk_offset.shape[-2]
        snap, tables = CalibrationSnapshot(), {}
        for i, name in enumerate(("wr", "wk", "wv", "wg")):
            tables[name] = jax.random.normal(
                jax.random.fold_in(KEY, i), (c, d)) * 0.1
            snap = snap.with_layer(
                name, LayerCalibration(chunk_offset=tables[name]))
        swapped = swap_calibration(loaded, snap)
        assert jax.tree.structure(swapped) == jax.tree.structure(loaded)
        sgp = swapped["_groups"]["rkvg"]
        np.testing.assert_array_equal(
            np.asarray(sgp.fused.chunk_offset),
            np.asarray(jnp.stack([tables[n] for n in
                                  ("wr", "wk", "wv", "wg")], axis=0)))
        np.testing.assert_array_equal(np.asarray(sgp.fused.store.codes),
                                      np.asarray(gp.fused.store.codes))


# -------------------------------------- packed == fp32 bake, every backend
class TestPackedMatchesFp32Bake:
    @pytest.mark.parametrize("mode,pallas", MODES)
    @REPLAYS
    def test_dequant_on_load_matches_baked_fp32(self, mode, pallas, replay):
        """Replace every packed store with a legacy-style one whose
        ``codes`` ARE the materialized fp32 ``w_eff`` (gain tables
        nulled): the executor must produce bitwise-identical outputs, so
        in-kernel dequantization is exactly the old fp32 bake."""
        cfg = _cfg(mode, pallas, act_calib="static")
        plan = _mixed_stack(cfg)
        layers = []
        for lp in plan.layers:
            st = lp.store
            legacy = dataclasses.replace(  # verify: allow-packed-weights
                st, codes=st.w_eff, col_gain=None, row_gain=None,
                chunk_gain=None, gain_map=None,
            )
            layers.append(dataclasses.replace(lp, store=legacy))
        baked = dataclasses.replace(plan, layers=tuple(layers), mega=None)
        baked = dataclasses.replace(baked,
                                    mega=E.pack_megakernel(baked))
        assert baked.layers[0].store.codes.dtype == jnp.float32
        assert plan.layers[0].store.codes.dtype == jnp.int8

        x = _codes(5, 32)
        np.testing.assert_array_equal(
            np.asarray(replay(plan, x)), np.asarray(replay(baked, x)),
        )


# --------------------------------------------------- serve-side plan cache
class TestServePlanCache:
    def test_cold_start_from_cache_lowers_nothing(self, tmp_path):
        from repro.serve.engine import Request, ServeEngine

        run = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        params = T.lm_init(jax.random.PRNGKey(0), ARCH)
        cache = str(tmp_path / "plan.npz")

        eng1 = ServeEngine(ARCH, run, params, batch_size=2, max_len=32,
                           plan_cache=cache)
        import os
        assert os.path.exists(cache)           # miss -> compiled + saved

        reset_lowering_count()
        eng2 = ServeEngine(ARCH, run, params, batch_size=2, max_len=32,
                           plan_cache=cache)
        assert lowering_count() == 0           # hit -> ZERO lowering work

        prompt = np.arange(6) % ARCH.vocab_size
        r1 = eng1.serve([Request(0, prompt, 5)])[0]
        r2 = eng2.serve([Request(1, prompt, 5)])[0]
        np.testing.assert_array_equal(r1.output, r2.output)
