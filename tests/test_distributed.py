"""Distribution-substrate tests: shape-aware spec resolution, mesh-shape
invariance of the analog noise, sharded train step on a host mesh, serving
engine, and the launcher loop (fault-tolerant driver)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh


@pytest.fixture()
def mesh22():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (run under forced host device count)")
    with shd.use_mesh(make_mesh((2, 2), ("data", "model"))) as m:
        yield m


class TestSpecResolution:
    def test_no_mesh_is_noop(self):
        assert shd.resolve_spec(("batch", "mlp"), (4, 8)) == P()
        x = jnp.ones((4, 4))
        assert shd.constrain(x, "batch", None) is x

    def test_divisibility_fallback(self, mesh22):
        # kv_heads=3 cannot take model(2); kv_seq picks it up instead
        spec = shd.resolve_spec(
            ("batch", "kv_seq", "kv_heads", None), (4, 8, 3, 16)
        )
        assert spec == P("data", "model", None, None)
        # kv_heads=4 divisible: right-to-left gives heads the model axis
        spec = shd.resolve_spec(
            ("batch", "kv_seq", "kv_heads", None), (4, 8, 4, 16)
        )
        assert spec == P("data", None, "model", None)

    def test_collapsed_dims(self, mesh22):
        # more names than dims: trailing names win, leading ones drop
        spec = shd.resolve_spec(("batch", "seq", "mlp"), (16, 8))
        assert spec == P(None, "model")

    def test_batch_multi_axis(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        with shd.use_mesh(
            make_mesh((2, 2, 1), ("pod", "data", "model"))
        ):
            spec = shd.resolve_spec(("batch", None), (8, 4))
            assert spec == P(("pod", "data"), None)

    def test_rules_for_run_overrides(self):
        from repro.configs.base import RunConfig

        rules = shd.rules_for(RunConfig(fsdp=False, seq_sp=False))
        assert rules["embed"] == () and rules["seq_sp"] == ()
        rules = shd.rules_for(RunConfig())
        assert rules["embed"] == ("data",)


class TestMeshInvariance:
    def test_fpn_independent_of_mesh(self):
        """Fixed-pattern noise is generated from the logical shape + seed,
        so the analog function is identical under any sharding."""
        from repro.core.analog import analog_linear_init

        p1 = analog_linear_init(jax.random.PRNGKey(3), 256, 64)
        if len(jax.devices()) >= 4:
            with shd.use_mesh(make_mesh((2, 2), ("data", "model"))):
                p2 = analog_linear_init(jax.random.PRNGKey(3), 256, 64)
        else:
            p2 = analog_linear_init(jax.random.PRNGKey(3), 256, 64)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            p1, p2,
        )


class TestShardedTrainStep:
    def test_train_step_on_host_mesh(self):
        from repro import configs
        from repro.configs.base import RunConfig
        from repro.launch.mesh import make_host_mesh
        from repro.train import train_step as TS

        cfg = configs.get_smoke("glm4-9b")
        run = RunConfig()
        with shd.use_mesh(make_host_mesh()):
            state = TS.init_state(jax.random.PRNGKey(0), cfg, run)
            step = TS.make_train_step(cfg, run)
            b = {
                "tokens": jnp.zeros((4, 16), jnp.int32),
                "labels": jnp.zeros((4, 16), jnp.int32),
            }
            state, m = step(state, b, jax.random.PRNGKey(0))
        assert bool(jnp.isfinite(m["loss"]))

    def test_moe_shard_map_matches_gspmd(self):
        """The explicit-collective EP path computes the same function as
        the GSPMD path (same routing, same experts)."""
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        from repro.core.analog import DIGITAL
        from repro.models import moe as M

        params = M.moe_init(jax.random.PRNGKey(0), 32, 16, 4)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32)).astype(
            jnp.bfloat16
        )
        with shd.use_mesh(make_mesh((2, 2), ("data", "model"))):
            y_sm, aux1 = M.moe_apply(
                params, x, acfg=DIGITAL, top_k=2, dispatch="shard_map"
            )
            y_gs, aux2 = M.moe_apply(
                params, x, acfg=DIGITAL, top_k=2, dispatch="gspmd_ep"
            )
        np.testing.assert_allclose(
            np.asarray(y_sm, np.float32), np.asarray(y_gs, np.float32),
            atol=2e-2, rtol=2e-2,
        )
        np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-5)

    def test_cp_flash_matches_plain(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        from repro.models.flash import flash_attention, flash_attention_cp

        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 2, 3, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 2, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 2, 16))
        plain = flash_attention(q, k, v, block_q=16, block_kv=16)
        with shd.use_mesh(make_mesh((2, 2), ("data", "model"))):
            cp = flash_attention_cp(q, k, v, block_q=16, block_kv=16)
        np.testing.assert_allclose(
            np.asarray(plain), np.asarray(cp), atol=3e-5
        )


_COLUMN_PARALLEL_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed import sharding as shd
from repro.kernels import ops
from repro.launch.mesh import make_mesh
k = jax.random.PRNGKey(0)
a_p = jnp.round(jax.random.uniform(k, (8, 256)) * 31)
a_n = jnp.round(jax.random.uniform(jax.random.fold_in(k, 1), (8, 256)) * 31)
w = jax.random.normal(jax.random.fold_in(k, 2), (256, 96)) * 20
g = jnp.full((96,), 0.02)
off = jax.random.normal(jax.random.fold_in(k, 3), (2, 96))
def both(a_p, a_n, w, g, off):
    return (ops.analog_mvm_split(a_p, a_n, w, g, off, 128, True, True),
            ops.analog_mvm(a_p, w, g, off, 128, True, True))
one = both(a_p, a_n, w, g, off)
for shape in ((1, 4), (2, 2), (4, 1)):
    mesh = make_mesh(shape, ("data", "model"))
    with shd.use_mesh(mesh):
        rows, cols = shd.vmm_axes(8, 96)
        assert (rows, cols) == ("data", "model")
        put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
        args = (a_p, a_n, put(w, None, cols), put(g, cols),
                put(off, None, cols))
        f = jax.jit(both)
        hlo = f.lower(*args).compile().as_text()
        got = f(*args)
    # weights placed as the plan leaves are: no collective at all
    for op in ("all-gather", "all-to-all", "collective-permute",
               "all-reduce", "reduce-scatter"):
        assert op not in hlo, (shape, op)
    assert got[0].sharding.device_set == set(mesh.devices.flat)
    for x, y in zip(one, got):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
with shd.use_mesh(make_mesh((1, 4), ("data", "model"))):
    try:
        jax.jit(both)(a_p, a_n, w[:, :90], g[:90], off[:, :90])
    except ValueError as e:
        assert "output columns" in str(e), e
    else:
        raise AssertionError("90 columns ran split over 4 devices")
print("OK")
"""


class TestColumnParallelKernels:
    def test_pallas_vmm_on_4_device_mesh_matches_one_device(self):
        """Mosaic kernels cannot be partitioned automatically: under a
        mesh the VMM kernels split rows over ``data`` and output columns
        over ``model`` (kernels.ops._column_parallel), which must
        reproduce the one-device result exactly on (1, 4), (2, 2) and
        (4, 1) meshes, move nothing between devices when the weights sit
        where the plan leaves are placed, and refuse a column count the
        mesh does not divide.  Run in a child with 4 host devices (this
        process's device count is fixed at JAX start-up)."""
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
               "PYTHONPATH": os.path.join(root, "src")}
        proc = subprocess.run([sys.executable, "-c", _COLUMN_PARALLEL_SCRIPT],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert proc.stdout.strip().endswith("OK")


class TestServeEngine:
    def test_batched_requests_complete(self):
        from repro import configs
        from repro.configs.base import RunConfig
        from repro.models import transformer as T
        from repro.serve.engine import Request, ServeEngine

        cfg = configs.get_smoke("stablelm-3b")
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, RunConfig(), params, batch_size=3,
                          max_len=64)
        rng = np.random.default_rng(0)
        reqs = [
            Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 5),
                    max_new_tokens=4)
            for i in range(5)
        ]
        done = eng.serve(reqs)
        assert all(r.output is not None and len(r.output) == 4
                   for r in done)

    def test_greedy_deterministic(self):
        from repro import configs
        from repro.configs.base import RunConfig
        from repro.models import transformer as T
        from repro.serve.engine import Request, ServeEngine

        cfg = configs.get_smoke("glm4-9b")
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, RunConfig(), params, batch_size=2,
                          max_len=32)
        prompt = np.arange(6) % cfg.vocab_size
        r1 = eng.serve([Request(0, prompt, 6)])[0]
        r2 = eng.serve([Request(1, prompt, 6)])[0]
        np.testing.assert_array_equal(r1.output, r2.output)


class TestLauncher:
    def test_train_resume_roundtrip(self, tmp_path):
        from repro.launch.train import train_loop

        d = str(tmp_path / "ck")
        out1 = train_loop("stablelm-3b", smoke=True, steps=6, batch=4,
                          seq_len=16, ckpt_dir=d, ckpt_every=3, log_every=0)
        out2 = train_loop("stablelm-3b", smoke=True, steps=8, batch=4,
                          seq_len=16, ckpt_dir=d, ckpt_every=3, log_every=0)
        # resumed from step 6: only 2 new losses
        assert len(out2["losses"]) == 2
        assert np.isfinite(out2["losses"]).all()

    def test_analog_mode_launcher(self):
        from repro.launch.train import train_loop

        out = train_loop("stablelm-3b", smoke=True, steps=4, batch=2,
                         seq_len=16, mode="analog_fast", log_every=0)
        assert np.isfinite(out["losses"]).all()
