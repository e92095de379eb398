"""What decides ``correct``: the program agrees with the plain reference,
the control (the reference in bfloat16 in the program's place) fails the
limit, and a run whose timed path is broken underneath reads
``correct: false`` for each fault a serving cell can have."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import cell, device, spec, steer

import readings
import run as bench_run


def _limit(name: str, key: str) -> float:
    return cell(name).config["limits"][key]["limit"]


@pytest.mark.parametrize("name,key", [("ecg-stream", "max_logit_gap_codes"),
                                      ("lm-decode", "max_served_gap")])
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_control_fails_program_passes(name, key, seed, monkeypatch):
    steer(monkeypatch)
    c = cell(name)
    if name.startswith("ecg"):       # the pool the control is read on
        c = c.__class__(**{**c.__dict__, "traffic": {**c.traffic,
                                                     "pool_windows": 64}})
    got = readings.read(c, seed, 0.3)
    assert got["program"][key] <= _limit(name, key)
    assert got["control"][key] > _limit(name, key)


def _run(name, monkeypatch, capsys, seconds="0.3") -> dict:
    steer(monkeypatch)
    assert bench_run.main(["--workload", name, "--seed", "7", "--seconds",
                           seconds, "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _patch_driver(monkeypatch, kind, fault):
    """Break the timed path underneath: ``fault(system)`` runs right after
    each build."""
    driver = spec.driver(kind)
    build = driver.build

    def broken(*a, **kw):
        system = build(*a, **kw)
        fault(system)
        return system

    monkeypatch.setattr(driver, "build", broken)
    monkeypatch.setattr(spec, "driver", lambda k: driver)


def test_ecg_answer_altered(monkeypatch, capsys):
    """One window's logits altered where the chain produces them."""
    def fault(system):
        apply = system.apply
        system.apply = lambda x: apply(x).at[0, 1].add(0.5)

    _patch_driver(monkeypatch, "ecg", fault)
    out = _run("ecg-stream", monkeypatch, capsys)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap_codes"]["value"] > out["checks"][
        "max_logit_gap_codes"]["limit"]


def test_ecg_half_of_the_batch_left_out(monkeypatch, capsys):
    """A recording answered for only half of its windows."""
    def fault(system):
        apply = system.apply
        system.apply = lambda x: apply(x[: x.shape[0] // 2])

    _patch_driver(monkeypatch, "ecg", fault)
    assert _run("ecg-holter", monkeypatch, capsys)["correct"] is False


def test_lm_token_altered(monkeypatch, capsys):
    """A served token altered where the engine samples it."""
    def fault(system):
        sample = system.engine._sample
        vocab = system.cfg["vocab_size"]
        system.engine._sample = lambda logits: (
            sample(logits).at[0].add(1) % vocab)

    _patch_driver(monkeypatch, "lm", fault)
    out = _run("lm-decode", monkeypatch, capsys)
    assert out["correct"] is False
    assert out["checks"]["max_served_gap"]["value"] > out["checks"][
        "max_served_gap"]["limit"]


def test_lm_step_returns_its_state_unchanged(monkeypatch, capsys):
    """A decode step that hands back the cache it was given."""
    def fault(system):
        decode = system.engine.decode

        def stale(params, tokens, cache):
            keep = jax.tree.map(jnp.copy, cache)
            logits, _ = decode(params, tokens, cache)
            return logits, keep

        system.engine.decode = stale

    _patch_driver(monkeypatch, "lm", fault)
    assert _run("lm-decode", monkeypatch, capsys)["correct"] is False


def test_compile_in_window_is_not_correct(monkeypatch, capsys):
    """A shape the warm-up missed compiles inside the window: the run says
    so and is not correct."""
    def fault(system):
        apply = system.apply
        calls = iter(range(10**9))
        system.apply = lambda x: (apply(jnp.concatenate([x, x]))[:1]
                                  if next(calls) == 0 else apply(x))

    _patch_driver(monkeypatch, "ecg", fault)
    assert _run("ecg-stream", monkeypatch, capsys)["correct"] is False


def test_seeds_repeat_the_traffic(monkeypatch):
    """The same seed gives the same inputs and weights; another seed
    other ones."""
    steer(monkeypatch)
    c = cell("ecg-stream")
    driver = spec.driver("ecg")
    peak = spec.peak("cpu")
    a = driver.System(c.config, c.traffic, device.Seeds(9), peak)
    b = driver.System(c.config, c.traffic, device.Seeds(9), peak)
    d = driver.System(c.config, c.traffic, device.Seeds(10), peak)
    assert (a.pool == b.pool).all() and not (a.pool == d.pool).all()
    wa, wd = a.weights["fc1"]["w"], d.weights["fc1"]["w"]
    assert (np.asarray(wa) == np.asarray(b.weights["fc1"]["w"])).all()
    assert not (np.asarray(wa) == np.asarray(wd)).all()


def test_diagnose_lm_agrees_at_toy_widths(monkeypatch):
    """On the CPU the program, its jnp path and the reference agree to the
    bit at toy widths; the bfloat16 reference does not."""
    import diagnose_lm
    from chipbench_tiny import CPU_PEAK

    steer(monkeypatch)
    got = diagnose_lm.diagnose(cell("lm-prefill").config, 3, 2, 8, CPU_PEAK)
    for k in ("program", "program_jnp", "program_global_highest",
              "qkv_layer0_pallas", "qkv_layer0_jnp",
              "reference_global_highest"):
        assert got[k]["n_diff"] == 0, (k, got[k])
    assert got["reference_bfloat16"]["n_diff"] > 0
