"""Pipeline parallelism: GPipe schedule over the pod axis must compute the
exact sequential composition of stages."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import sharding as shd
from repro.distributed.pipeline import pipeline_apply, split_stages
from repro.launch.mesh import make_mesh


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _make_params(key, n_stages, d):
    ks = jax.random.split(key, 2)
    return {
        "w": jax.random.normal(ks[0], (n_stages, d, d)) * 0.5,
        "b": jax.random.normal(ks[1], (n_stages, d)) * 0.1,
    }


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
class TestPipeline:
    def test_matches_sequential(self):
        n_stages, n_micro, mb, d = 2, 4, 3, 8
        params = _make_params(jax.random.PRNGKey(0), n_stages, d)
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
        with shd.use_mesh(make_mesh((2,), ("pod",))):
            out = pipeline_apply(_stage_fn, params, x)
        # sequential reference
        want = x
        for s in range(n_stages):
            p = jax.tree.map(lambda a, s=s: a[s], params)
            want = jax.vmap(lambda m: _stage_fn(p, m))(want)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), atol=1e-5
        )

    def test_gradients_flow(self):
        n_stages, n_micro, mb, d = 2, 2, 2, 4
        params = _make_params(jax.random.PRNGKey(2), n_stages, d)
        x = jax.random.normal(jax.random.PRNGKey(3), (n_micro, mb, d))

        def loss(params):
            with shd.use_mesh(make_mesh((2,), ("pod",))):
                return (pipeline_apply(_stage_fn, params, x) ** 2).sum()

        g = jax.grad(loss)(params)
        assert bool(jnp.isfinite(g["w"]).all())
        assert float(jnp.abs(g["w"]).sum()) > 0


def test_split_stages():
    layers = {"w": jnp.arange(12).reshape(6, 2)}
    out = split_stages(layers, 2)
    assert out["w"].shape == (2, 3, 2)
    np.testing.assert_array_equal(np.asarray(out["w"][0]),
                                  np.arange(6).reshape(3, 2))
