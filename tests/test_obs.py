"""Tests for ``repro.obs`` (ISSUE 9): host-side tracing (nestable spans,
JSONL export), process-local metrics with percentile summaries and a
JSONL round-trip, plan-derived energy/latency accounting tied to the
paper's 276 us / 192 uJ reference point, the instrumented serve engine
(span tree, plan-cache hit/miss counters, drift probe -> exactly one
hot-swap event) with PROOF that instrumentation adds zero re-lowering
and zero jit-cache growth (``verify.retrace``), the new lint rules
(bare-print / raw-timer), and the telemetry-contract checker behind
``python -m repro.obs --serve-smoke``.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.exec as E
from repro import calib, obs
from repro.configs.base import ArchConfig, RunConfig
from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.energy import SystemModel
from repro.core.noise import NOISELESS
from repro.models import ecg as ECG
from repro.models import transformer as T
from repro.obs import energy as obs_energy
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.serve.engine import Request, ServeEngine
from repro.verify.lint import lint_source
from repro.verify.retrace import assert_no_retrace

KEY = jax.random.PRNGKey(0)
SPLIT_CFG = AnalogConfig(noise=NOISELESS, signed_input="split")


# ---------------------------------------------------------------------------
# trace: spans, nesting, events, collectors
# ---------------------------------------------------------------------------


class TestTrace:
    def test_span_outside_collector_still_times(self):
        with obs_trace.span("solo") as sp:
            pass
        assert sp.dur_us >= 0.0
        assert obs_trace.active_trace() is None

    def test_nesting_builds_slash_paths(self):
        with obs_trace.collect("t") as tr:
            with obs_trace.span("outer"):
                with obs_trace.span("inner"):
                    obs_trace.event("ping", x=1)
        assert tr.span_paths() == {"outer", "outer/inner"}
        (ev,) = tr.events_named("ping")
        assert ev["path"] == "outer/inner/ping" and ev["meta"] == {"x": 1}
        # inner span recorded before outer (close order)
        names = [e["name"] for e in tr.spans()]
        assert names == ["inner", "outer"]

    def test_span_meta_via_add(self):
        with obs_trace.collect() as tr:
            with obs_trace.span("s", a=1) as sp:
                sp.add(b=2)
        (rec,) = tr.spans("s")
        assert rec["meta"] == {"a": 1, "b": 2}
        assert rec["dur_us"] >= 0.0

    def test_collect_nests_and_restores(self):
        with obs_trace.collect("outer") as t1:
            with obs_trace.collect("inner") as t2:
                obs_trace.event("e")
                assert obs_trace.active_trace() is t2
            assert obs_trace.active_trace() is t1
        assert t2.events_named("e") and not t1.events_named("e")

    def test_begin_end_pair(self):
        tr = obs_trace.begin("driver")
        obs_trace.event("tick")
        got = obs_trace.end(tr)
        assert got is tr and tr.events_named("tick")
        assert obs_trace.active_trace() is None

    def test_jsonl_round_trip(self, tmp_path):
        with obs_trace.collect("rt") as tr:
            with obs_trace.span("a"):
                obs_trace.event("b", k="v")
        p = tmp_path / "t.jsonl"
        tr.dump_jsonl(str(p))
        recs = [json.loads(line) for line in p.read_text().splitlines()]
        assert recs[0]["rec"] == "trace" and recs[0]["name"] == "rt"
        assert {r["rec"] for r in recs[1:]} == {"span", "event"}

    def test_timeit_matches_gate_shape_and_records(self):
        calls = []

        def f():
            calls.append(1)
            return 0

        with obs_trace.collect() as tr:
            us = obs_trace.timeit(f, iters=4, warmup=2, blocks=3,
                                  label="unit")
        # warmup + blocks*iters, every call blocked
        assert len(calls) == 2 + 3 * 4
        assert us >= 0.0
        (ev,) = tr.events_named("timeit")
        assert ev["meta"]["label"] == "unit"
        assert ev["meta"]["us_per_call"] == pytest.approx(us, abs=0.001)


def _host_events(trace_dir, names):
    """``{name: [(thread line, start_ns, end_ns)]}`` of the ``/host:CPU``
    events named in ``names`` in the profile written under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append(
                        (line.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


class TestProfilerBridge:
    """Every ``obs.span`` is a host span in the profiler's trace."""

    def test_span_lands_in_profile_inside_enclosing_annotation(self, tmp_path):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.outer"):
                with obs_trace.span("obs.bridge") as sp:
                    jax.block_until_ready(jnp.ones(8) + 1)
        finally:
            jax.profiler.stop_trace()
        got = _host_events(tmp_path, ("bench.outer", "obs.bridge"))
        (outer,), (inner,) = got["bench.outer"], got["obs.bridge"]
        assert inner[0] == outer[0]                   # one host thread
        assert outer[1] <= inner[1] < inner[2] <= outer[2]
        # the span's own clock agrees with the profiler's extent
        assert sp.dur_us <= (inner[2] - inner[1]) / 1e3 + 50.0

    def test_span_without_profiler_or_collector_still_times(self):
        assert obs_trace.active_trace() is None
        assert not obs_trace.observed()
        with obs_trace.span("obs.bridge") as sp:
            jax.block_until_ready(jnp.ones(8) + 1)
        assert sp.dur_us > 0.0 and sp.path == "obs.bridge"

    def test_observed_while_collector_or_profiler_records(self, tmp_path):
        assert not obs_trace.observed()
        with obs_trace.collect():
            assert obs_trace.observed()
        assert not obs_trace.observed()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert obs_trace.observed()
        finally:
            jax.profiler.stop_trace()
        assert not obs_trace.observed()


# ---------------------------------------------------------------------------
# metrics: counters/gauges/histograms + JSONL round-trip
# ---------------------------------------------------------------------------


class TestMetrics:
    def setup_method(self):
        obs_metrics.reset_metrics()

    def test_counter_gauge(self):
        obs_metrics.counter("c").inc()
        obs_metrics.counter("c").inc(4)
        obs_metrics.gauge("g").set(2.5)
        assert obs_metrics.counter("c").value == 5
        assert obs_metrics.gauge("g").value == 2.5

    def test_histogram_percentiles(self):
        h = obs_metrics.histogram("h")
        for v in range(1, 101):                 # 1..100
            h.record(float(v))
        s = h.summary()
        assert s["count"] == 100
        assert s["p50"] == 50.0 and s["p95"] == 95.0 and s["p99"] == 99.0
        assert s["min"] == 1.0 and s["max"] == 100.0

    def test_type_collision_raises(self):
        obs_metrics.counter("x")
        with pytest.raises(TypeError):
            obs_metrics.histogram("x")

    def test_jsonl_round_trip(self, tmp_path):
        obs_metrics.counter("hits").inc(3)
        obs_metrics.gauge("uj").set(192.0)
        h = obs_metrics.histogram("lat_us")
        for v in (10.0, 20.0, 30.0):
            h.record(v)
        p = tmp_path / "m.jsonl"
        obs_metrics.export_jsonl(str(p))
        back = obs_metrics.import_jsonl(str(p))
        assert back.get("hits").value == 3
        assert back.get("uj").value == 192.0
        assert back.get("lat_us").summary() == h.summary()


# ---------------------------------------------------------------------------
# energy: compiled plans -> paper's Table-1 numbers
# ---------------------------------------------------------------------------


def _ecg_code_plan():
    cfg = ECG.ECGConfig()
    params = ECG.ecg_init(jax.random.PRNGKey(3), cfg)
    from repro.exec.lower import lower_stack

    return lower_stack(
        [params["conv"], params["fc1"], params["fc2"]],
        AnalogConfig(mode="analog_fast"),
        epilogues=["relu_shift", "relu_shift", "none"],
        flatten_outs=[True, False, False], input_domain="codes",
    )


class TestEnergy:
    def test_ecg_plan_hits_paper_latency(self):
        rep = obs_energy.energy_report(_ecg_code_plan())
        assert rep["analog_passes"] == 4        # conv, fc1 x2 chunks, fc2
        assert rep["us_per_sample"] == pytest.approx(276.0)
        assert rep["us_vs_paper"] == pytest.approx(1.0)
        # on-ASIC energy within a few percent of the paper's 192 uJ
        assert rep["uj_per_sample"] == pytest.approx(192.0, rel=0.05)

    def test_plan_works_match_expected_dispatch_semantics(self):
        # a split-encoded float-domain plan costs 2 passes per vector
        p = analog_linear_init(KEY, 256, 64, noise=NOISELESS)
        plan = E.lower(p, SPLIT_CFG)
        (w,) = obs_energy.plan_layer_works(plan)
        assert w.passes_per_vector == 2
        rep = obs_energy.energy_report(plan, model=SystemModel())
        assert rep["analog_passes"] == 4        # 2 row chunks x split pair

    def test_record_sets_gauges_and_event(self):
        obs_metrics.reset_metrics()
        with obs_trace.collect() as tr:
            rep = obs_energy.record(_ecg_code_plan(), prefix="e")
        assert obs_metrics.gauge("e.us_per_sample").value == \
            pytest.approx(rep["us_per_sample"])
        assert tr.events_named("e")
        out = obs_energy.format_report(rep, title="ecg")
        assert "276" in out and "us/sample" in out


# ---------------------------------------------------------------------------
# serve engine telemetry + drift + retrace pin
# ---------------------------------------------------------------------------


def _smoke_engine(**kw):
    cfg = ArchConfig("t-obs", "dense", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab_size=256)
    params = T.lm_init(KEY, cfg)
    run_cfg = RunConfig(analog=AnalogConfig(mode="analog_fast"))
    eng = ServeEngine(cfg, run_cfg, params, batch_size=2, max_len=32, **kw)
    return cfg, eng


def _reqs(cfg, n, uid0=0, max_new=4):
    prompt = np.arange(6) % cfg.vocab_size
    return [Request(uid0 + i, prompt, max_new) for i in range(n)]


class TestServeTelemetry:
    def test_batch_emits_span_tree_and_metrics(self):
        obs_metrics.reset_metrics()
        with obs_trace.collect("serve") as tr:
            cfg, eng = _smoke_engine()
            eng.serve(_reqs(cfg, 3))
        paths = tr.span_paths()
        assert "serve.compile" in paths
        assert "serve.compile/api.compile" in paths
        assert "serve.batch" in paths
        assert "serve.batch/serve.prefill" in paths
        assert "serve.batch/serve.decode" in paths
        # 3 requests at batch_size=2 -> 2 refill groups
        refills = tr.events_named("serve.refill")
        assert [e["meta"]["size"] for e in refills] == [2, 1]
        assert tr.events_named("serve.energy")
        reg = obs_metrics.registry()
        assert reg.get("exec.dispatches").value > 0
        assert reg.get("serve.prefill_us").summary()["count"] == 2
        assert reg.get("serve.decode_us").summary()["count"] > 0
        assert reg.get("serve.queue_us").summary()["count"] == 3
        assert reg.get("serve.request_us").summary()["count"] == 3
        occ = reg.get("serve.batch_occupancy").summary()
        assert occ["count"] == 2 and occ["max"] == 1.0 and occ["min"] == 0.5

    def test_dispatch_counter_is_trace_time_only(self):
        obs_metrics.reset_metrics()
        p = analog_linear_init(KEY, 256, 64, noise=NOISELESS)
        plan = E.lower(p, SPLIT_CFG)
        x = jax.random.normal(KEY, (4, 256)) * 0.2

        f = jax.jit(E.run)
        jax.block_until_ready(f(plan, x))
        warm = obs_metrics.counter("exec.dispatches").value
        assert warm > 0
        jax.block_until_ready(f(plan, x))       # cached replay: no bump
        assert obs_metrics.counter("exec.dispatches").value == warm

    def test_plan_cache_hit_miss_counters(self, tmp_path):
        obs_metrics.reset_metrics()
        cache = str(tmp_path / "plan.npz")
        with obs_trace.collect() as tr:
            cfg, _ = _smoke_engine(plan_cache=cache)       # miss: lowers
            _smoke_engine(plan_cache=cache)                # hit: loads
        reg = obs_metrics.registry()
        assert reg.get("serve.plan_cache.miss").value == 1
        assert reg.get("serve.plan_cache.hit").value == 1
        statuses = [e["meta"]["status"]
                    for e in tr.events_named("serve.plan_cache")]
        assert statuses == ["miss", "hit"]

    def test_forced_drift_emits_exactly_one_hot_swap(self):
        obs_metrics.reset_metrics()
        cfg = ArchConfig("t-obs-drift", "dense", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256)
        params = T.lm_init(KEY, cfg)
        run_cfg = RunConfig(analog=AnalogConfig(mode="analog_fast"))
        spec = T.lm_module_spec(cfg, params)
        chips = calib.model_chips(spec, params, KEY)
        snap = calib.calibrate_model(spec, params, KEY, chips=chips,
                                     offset_repeats=16, gain_repeats=2)
        mon = calib.DriftMonitor(chips, snap, threshold_lsb=0.5)
        eng = ServeEngine(cfg, run_cfg, params, batch_size=2, max_len=32,
                          calibration=snap, drift_monitor=mon)
        with obs_trace.collect() as tr:
            eng.serve(_reqs(cfg, 1))            # stable: probe only
            for i, c in enumerate(chips.values()):
                c.apply_drift(jax.random.fold_in(KEY, 70 + i), 2.0)
            eng.serve(_reqs(cfg, 1, uid0=1))    # drifted: probe + swap
        probes = tr.events_named("drift.probe")
        assert len(probes) == 2
        assert probes[0]["meta"]["lsb"] <= 0.5 < probes[1]["meta"]["lsb"]
        assert len(tr.events_named("drift.hot_swap")) == 1
        reg = obs_metrics.registry()
        assert reg.get("drift.hot_swap").value == 1
        assert reg.get("serve.hot_swap").value == 1
        assert reg.get("drift.lsb").summary()["count"] == 2
        assert "serve.hot_swap" in tr.span_paths()

    def test_instrumentation_adds_zero_retrace(self):
        """The acceptance pin: serving WITH an active collector does no
        lowering work and grows no jit cache vs the warm path - the
        telemetry is entirely host-side."""
        cfg, eng = _smoke_engine()
        eng.serve(_reqs(cfg, 2))                # warm every executable
        cache0 = (eng.prefill._cache_size(), eng.decode._cache_size())
        uid = [100]

        def serve_instrumented():
            with obs_trace.collect():
                uid[0] += 2
                eng.serve(_reqs(cfg, 2, uid0=uid[0]))

        diags = assert_no_retrace(serve_instrumented, replays=3,
                                  label="serve+obs")
        assert diags == ()
        assert (eng.prefill._cache_size(),
                eng.decode._cache_size()) == cache0


# ---------------------------------------------------------------------------
# compile-path instrumentation
# ---------------------------------------------------------------------------


class TestCompileSpan:
    def test_compile_records_span_and_lowerings(self):
        from repro import api

        cfg = ECG.ECGConfig()
        params = ECG.ecg_init(jax.random.PRNGKey(1), cfg)
        spec = ECG.ecg_module_spec(cfg, epilogue="relu_shift")
        with obs_trace.collect() as tr:
            api.compile(spec, params,
                        AnalogConfig(mode="analog_fast")).lower()
        (sp,) = tr.spans("api.compile")
        assert sp["meta"]["spec"] == spec.name
        assert sp["meta"]["lowerings"] >= 1

    def test_verify_diagnostics_surface_as_events(self):
        from repro import api
        from repro.api.module import LayerSpec, ModuleSpec
        from repro.verify import VerifyError

        # per-layer dims match their params (so lowering succeeds) but
        # the declared chain is broken: a emits 64, b expects 128
        pa = analog_linear_init(KEY, 256, 64, noise=NOISELESS)
        pb = analog_linear_init(KEY, 128, 32, noise=NOISELESS)
        spec = ModuleSpec(name="bad", kind="stack", layers=(
            LayerSpec("a", 256, 64), LayerSpec("b", 128, 32),
        ))
        with obs_trace.collect() as tr:
            with pytest.raises(VerifyError):
                api.compile(spec, {"a": pa, "b": pb}, SPLIT_CFG,
                            verify=True)
        evs = tr.events_named("verify.diagnostic")
        assert evs and all("rule" in e["meta"] for e in evs)


# ---------------------------------------------------------------------------
# lint rules: bare-print / raw-timer
# ---------------------------------------------------------------------------


class TestObsLintRules:
    def test_bare_print_flagged_in_repro(self):
        src = "def f():\n    print('hi')\n"
        rules = {f.rule for f in lint_source(src, "src/repro/serve/x.py")}
        assert "bare-print" in rules

    def test_allow_comment_suppresses(self):
        src = "def f():\n    print('hi')  # verify: allow-bare-print\n"
        assert not lint_source(src, "src/repro/serve/x.py")

    def test_obs_dir_and_main_and_outside_exempt(self):
        src = "print('hi')\n"
        assert not lint_source(src, "src/repro/obs/trace.py")
        assert not lint_source(src, "src/repro/verify/__main__.py")
        assert not lint_source(src, "benchmarks/run.py")

    def test_raw_timer_flagged(self):
        src = "import time\nt = time.perf_counter()\n"
        rules = {f.rule for f in lint_source(src, "src/repro/launch/t.py")}
        assert "raw-timer" in rules
        assert not lint_source(src, "examples/demo.py")


# ---------------------------------------------------------------------------
# report rendering + required-telemetry contract
# ---------------------------------------------------------------------------


class TestReport:
    def _records(self):
        obs_metrics.reset_metrics()
        with obs_trace.collect("r") as tr:
            with obs_trace.span("a"):
                obs_trace.event("ev", k=1)
            obs_metrics.counter("hits").inc(2)
            obs_metrics.histogram("lat_us").record(120.0)
        return obs_report.records_of(tr, obs_metrics.registry())

    def test_render_sections(self):
        out = obs_report.render(self._records())
        assert "spans" in out and "a" in out
        assert "hits" in out and "lat_us" in out

    def test_dump_and_load(self, tmp_path):
        obs_metrics.reset_metrics()
        with obs_trace.collect("d") as tr:
            obs_metrics.counter("c").inc()
        p = tmp_path / "run.jsonl"
        obs_report.dump_run(str(p), tr, obs_metrics.registry())
        recs = obs_report.load(str(p))
        assert any(r["rec"] == "trace" for r in recs)
        assert any(r["rec"] == "counter" and r["name"] == "c"
                   for r in recs)

    def test_required_missing(self):
        recs = self._records()
        missing = obs_report.required_missing(
            recs, span_paths=("a", "zz"), events=("ev",),
            counters=("hits", "nope"), histograms=("lat_us",),
        )
        assert "span:zz" in missing and "counter:nope" in missing
        assert len(missing) == 2
        assert obs_report.required_missing(
            recs, span_paths=("a",), events=("ev",), counters=("hits",),
            histograms=("lat_us",),
        ) == []
