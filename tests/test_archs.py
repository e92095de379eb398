"""Per-architecture smoke tests: reduced config of the same family runs one
forward + one train step on CPU, asserting output shapes and no NaNs
(deliverable f).  Full configs are exercised shape-only (param counts,
dry-run compatibility is covered by launch/dryrun.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import RunConfig
from repro.models import transformer as T
from repro.train import train_step as TS

RUN = RunConfig()


def _batch(cfg, b=2, s=16, seed=0):
    k = jax.random.PRNGKey(seed)
    if cfg.embed_inputs:
        return {
            "tokens": jax.random.randint(k, (b, s), 0, cfg.vocab_size),
            "labels": jax.random.randint(k, (b, s), 0, cfg.vocab_size),
        }
    return {
        "embeds": jax.random.normal(k, (b, s, cfg.d_model)) * 0.1,
        "labels": jax.random.randint(k, (b, s), 0, cfg.vocab_size),
    }


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
class TestSmokeConfigs:
    def test_forward_step(self, name):
        cfg = configs.get_smoke(name)
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        batch = _batch(cfg)
        logits, _, _ = T.lm_apply(params, batch, cfg, RUN)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all()), f"{name}: non-finite logits"

    def test_train_step(self, name):
        cfg = configs.get_smoke(name)
        run = RUN
        state = TS.init_state(jax.random.PRNGKey(0), cfg, run)
        step = TS.make_train_step(cfg, run)
        batch = _batch(cfg)
        state, metrics = step(state, batch, jax.random.PRNGKey(1))
        assert bool(jnp.isfinite(metrics["loss"]))
        assert float(metrics["loss"]) > 0
        assert int(state["opt"]["step"]) == 1
        ok = jax.tree.reduce(
            lambda a, b: a and b,
            jax.tree.map(
                lambda x: bool(jnp.isfinite(x).all()), state["params"]
            ),
        )
        assert ok, f"{name}: non-finite params after step"


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_full_config_consistency(name):
    """Full configs: geometry sanity + analytic parameter counts near the
    advertised model size."""
    cfg = configs.get_arch(name)
    assert cfg.n_layers % len(T.group_def(cfg)) == 0
    if cfg.block == "attn" or cfg.attn_every:
        assert cfg.n_heads % cfg.n_kv_heads == 0
    n = cfg.param_count()
    expected = {
        "stablelm-3b": 2.8e9, "phi4-mini-3.8b": 3.8e9, "glm4-9b": 9e9,
        "minitron-4b": 4.2e9, "qwen2-vl-7b": 7e9, "rwkv6-7b": 7e9,
        "llama4-maverick-400b-a17b": 400e9, "qwen3-moe-30b-a3b": 30e9,
        "zamba2-2.7b": 2.7e9, "musicgen-medium": 1.5e9,
        "lfm2-8b-a1b": 8.3e9,
    }[name]
    assert 0.5 * expected < n < 1.7 * expected, (name, n, expected)


def test_active_params_llama4():
    cfg = configs.get_arch("llama4-maverick-400b-a17b")
    a = cfg.active_param_count()
    assert 10e9 < a < 25e9, a  # "A17B"


def test_active_params_qwen3():
    cfg = configs.get_arch("qwen3-moe-30b-a3b")
    a = cfg.active_param_count()
    assert 1.5e9 < a < 5e9, a  # "A3B"


def test_cells_long_context_rule():
    cells = dict()
    for a in configs.ARCH_NAMES:
        cells[a] = configs.cells(a)
    assert "long_500k" in cells["rwkv6-7b"]
    assert "long_500k" in cells["zamba2-2.7b"]
    for a in ("glm4-9b", "musicgen-medium", "qwen2-vl-7b"):
        assert "long_500k" not in cells[a]
    # 11 archs x 3 shapes + 2 long-context = 35 lowered cells; the 9
    # full-attention long_500k cells are documented skips (DESIGN.md §5)
    assert len(configs.all_cells()) == 35
