"""The ``lfm2`` cell on the CPU at toy widths: its result lines, what
decides its ``correct`` (the control and injected faults read false),
the held-expert counter against the reference's recount, and the
operation counts by hand for one layer of each kind."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import CPU_PEAK
from lfm2_tiny import NAME, config, device, spec, steer_lfm2

import readings
import run as bench_run
from chipbench import opcount_lfm2

SEED = 2**31 + 23


def _run(monkeypatch, capsys, tmp_path, trace="0", seconds="0.6"):
    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / "jax")
    assert bench_run.main(["--workload", NAME, "--seed", str(SEED),
                           "--seconds", seconds, "--trace", trace]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_result_line(monkeypatch, capsys, tmp_path):
    c = steer_lfm2(monkeypatch)
    out, lines = _run(monkeypatch, capsys, tmp_path)
    assert out["correct"] is True, out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {"max_served_gap", "held_rows_gap"}
    assert out["checks"]["held_rows_gap"]["value"] == 0
    assert set(out["metrics"]) == {m["name"] for m in c.end_to_end}
    assert any(line.startswith("compiles_in_window=0 ") for line in lines)


def test_traced_line(monkeypatch, capsys, tmp_path):
    c = steer_lfm2(monkeypatch)
    out, _ = _run(monkeypatch, capsys, tmp_path, trace="1", seconds="5")
    assert out["correct"] is True
    per_layer = {m["name"] for m in c.per_layer}
    assert set(out["metrics"]) <= per_layer
    # the counters are the program's; the CPU trace holds no TPU kernels
    pad = out["metrics"]["expert_pad_pct.lfm2_prefill1k"]["value"]
    assert 0 < pad < 100
    assert "idle_pct.lfm2_prefill1k" in out["metrics"]


def test_control_fails_program_passes(monkeypatch):
    c = steer_lfm2(monkeypatch, prompt=16, new=4)
    got = readings.read(c, SEED, 0.5)
    limit = c.config["limits"]["max_served_gap"]["limit"]
    assert got["program"]["max_served_gap"] <= limit
    assert got["program"]["held_rows_gap"] == 0
    assert got["control"]["max_served_gap"] > limit


def _patch_driver(monkeypatch, fault):
    driver = spec.driver("lfm2")
    build = driver.build

    def broken(*a, **kw):
        system = build(*a, **kw)
        fault(system)
        return system

    monkeypatch.setattr(driver, "build", broken)
    monkeypatch.setattr(spec, "driver", lambda k: driver)


def test_token_altered(monkeypatch, capsys, tmp_path):
    """A served token altered where the engine samples it."""
    steer_lfm2(monkeypatch)

    def fault(system):
        sample = system.engine._sample
        vocab = system.cfg["vocab_size"]
        system.engine._sample = lambda logits: (
            sample(logits).at[0].add(1) % vocab)

    _patch_driver(monkeypatch, fault)
    out, _ = _run(monkeypatch, capsys, tmp_path)
    assert out["correct"] is False
    assert out["checks"]["max_served_gap"]["value"] > \
        out["checks"]["max_served_gap"]["limit"]


def test_conv_state_not_carried(monkeypatch, capsys, tmp_path):
    """A decode step that hands back the conv layers' state it was given:
    the short convolution then mixes stale inputs."""
    steer_lfm2(monkeypatch)

    def fault(system):
        decode = system.engine.decode

        def stale(params, tokens, cache):
            keep = {k: jnp.copy(v["conv"]) for k, v in
                    cache["layers"].items() if "conv" in v}
            logits, new = decode(params, tokens, cache)
            for k, v in keep.items():
                new["layers"][k]["conv"] = v
            return logits, new

        system.engine.decode = stale

    _patch_driver(monkeypatch, fault)
    out, _ = _run(monkeypatch, capsys, tmp_path)
    assert out["correct"] is False


def test_pair_dropped(monkeypatch, capsys, tmp_path):
    """A held-expert layer that drops its last routed pair (as a capacity
    limit would): the program's own count moves off the recount."""
    from repro.models import moe

    steer_lfm2(monkeypatch)
    held_rows = moe.held_rows

    def dropping(sel, held, block_m):
        rows, row, is_held, token, counts = held_rows(sel, held, block_m)
        return rows, row, is_held, token, counts.at[-1].add(-1)

    monkeypatch.setattr(moe, "held_rows", dropping)
    out, _ = _run(monkeypatch, capsys, tmp_path)
    assert out["correct"] is False
    assert out["checks"]["held_rows_gap"]["value"] > 0


def test_held_rows_counter_equals_the_recount(monkeypatch):
    """The program's count of routed (token, held expert) pairs, call by
    call, against the reference's recount of the same batches."""
    c = steer_lfm2(monkeypatch)
    driver, reference = spec.driver("lfm2"), spec.reference("lfm2")
    system = driver.build(c.config, c.traffic, device.Seeds(SEED), CPU_PEAK)
    system.call([system.request({"prompt_tokens": 8, "new_tokens": 3})
                 for _ in range(4)])
    prompts, outs, rows = system.batches[-1]
    system.free_program()
    ref = reference.Replay(system.weights, c.config, "highest")
    _, routes = ref.run(np.stack(prompts), np.stack(outs))
    pairs = reference.held_pairs(routes, c.config["held_expert_ids"],
                                 c.config["num_experts_per_tok"])
    assert pairs["lo"] <= rows <= pairs["hi"]
    assert rows == pairs["count"] > 0
    # prefill 4 x 8 and 2 decode steps of 4, over 4 MoE layers, 4 choices
    # of 8 experts each; 4 of the 8 are held
    assert rows < (4 * 8 + 2 * 4) * 4 * 4


def test_counts_by_hand():
    """One layer of each kind at the published widths, by hand."""
    cfg = config()
    cfg.update(hidden_size=2048, intermediate_size=7168,
               moe_intermediate_size=1792, num_attention_heads=32,
               num_key_value_heads=8, vocab_size=65536, num_experts=8,
               num_experts_published=32)
    d, ff, eff = 2048, 7168, 1792
    shapes = dict((n, (k, m)) for n, k, m in
                  opcount_lfm2.dense_shapes(cfg))
    assert shapes["l0.in_proj"] == (d, 3 * d)        # conv: W_in, 3 gates
    assert shapes["l0.out_proj"] == (d, d)
    assert shapes["l2.qkv"] == (d, d + 2 * 8 * 64)   # GQA 32 / 8 of 64
    assert shapes["l2.o"] == (d, d)
    assert shapes["l1.down"] == (ff, d)
    assert "l3.up" not in shapes                      # MoE: the experts
    # per token: 5 conv (4 d^2 each), 1 attention (qkv + o), 2 dense
    # SwiGLUs, the head
    macs = 5 * 4 * d * d + (d * 3072 + d * d) + 2 * 3 * d * ff + d * 65536
    assert opcount_lfm2.token_macs(cfg) == macs
    peak = {"int8_ops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    w = [opcount_lfm2.Work() for _ in range(3)]
    opcount_lfm2.step(cfg, 1, 1, 0, 10, peak, *w)
    dense, experts, total = w
    assert dense.ops == 2 * macs
    assert experts.ops == 2 * 10 * 3 * d * eff       # 10 routed rows
    # attention at position 0 (1 key), router over 32 of 4 MoE layers,
    # 5 conv layers' taps and gates
    extra = 4 * d * 1 + 4 * 2 * d * 32 + 5 * (2 * 3 * d + 2 * d)
    assert total.ops == 2 * macs + experts.ops + extra


def test_diagnose_agrees_at_toy_widths(monkeypatch):
    """On the CPU the program's layers, chained and on the reference's own
    inputs, and its engine agree with the reference: the logits to the
    bit, every layer to the last bit of its fp32 sums."""
    import diagnose_lfm2

    steer_lfm2(monkeypatch)
    out = diagnose_lfm2.chain(config(), 5, 2, 16)
    last = out[-1]
    for k in ("engine_vs_reference", "chain_vs_reference"):
        assert last[k]["n_diff"] == [0, 0], (k, last[k])
    for layer in out[:-1]:
        assert max(layer["own_input"]["max_abs"]) < 1e-6, layer
