"""Batched serving engine: request queue -> padded prefill -> synchronous
batched decode with per-sequence stopping.  Deliberately simple continuous-
batching-lite: requests are grouped into fixed decode slots; finished slots
are refilled between decode steps (the cache "len" is global, so refills
restart a slot's cache region - documented simplification).

Any LM of :mod:`repro.models.transformer` serves here, its cache holding
each layer's own kind of state side by side: keys and values on attention
layers, the last gated inputs ``[B, taps - 1, d]`` on short-conv layers
(LFM2), the recurrent state on RWKV and Mamba layers.  Prompts are
left-padded, so a prefill's last positions are every row's last real
tokens.  On held-expert layers (one chip's share of an expert-parallel
deployment) every routed row is computed; while a collector or a profiler
is listening (``obs.observed()``) the engine keeps each step's routed
pairs and expert row tiles on the device and reads them back once a
batch, after its last step, to count them (``lm.moe.held_rows``,
``lm.moe.row_tiles``, ``lm.moe.tile_rows``).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs.base import ArchConfig, RunConfig
from repro.distributed import sharding as shd
from repro.models import moe as M
from repro.models import transformer as T
from repro.obs import energy as obs_energy
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.serve_step import make_serve_steps


@jax.jit
def _moe_totals(stats) -> jax.Array:
    """[routed pairs, row tiles] summed over the held-expert layers of one
    step (``stats``: each layer's ``{"rows": [H], "tiles": []}``)."""
    return jnp.stack([sum(st["rows"].sum() for st in stats),
                      sum(st["tiles"] for st in stats)]).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # [S] int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # filled by the engine:
    output: Optional[np.ndarray] = None
    # stamped by serve() on admission; feeds the serve.queue_us histogram
    t_enqueue_us: Optional[float] = None


class ServeEngine:
    def __init__(self, cfg: ArchConfig, run: RunConfig, params,
                 batch_size: int = 8, max_len: int = 512,
                 greedy: bool = True, seed: int = 0,
                 prelower: bool = True, calibration=None,
                 drift_monitor=None, plan_cache: Optional[str] = None,
                 fleet=None):
        self.cfg, self.run = cfg, run
        # Serving is inference against frozen weights: compile the model
        # ONCE through the api front door (quantized effective weights,
        # chunk padding, offsets, fused QKV dispatch groups - repro.api
        # over repro.exec) so the jitted prefill/decode steps replay the
        # baked plans instead of re-deriving them per forward.  Weight
        # updates (not a serve concern) would require model.relower().
        # Plan replays default to megakernel="auto": any stack plan the
        # engine serves that is a pure code-domain chain (eligibility in
        # exec.lower.pack_megakernel) executes as ONE pallas_call with
        # VMEM-resident inter-layer codes; LM tree plans (split-encoded
        # float activations) keep the per-layer fused-split dispatch.
        # Calibration (ISSUE 4): `calibration` bakes a measured
        # CalibrationSnapshot instead of the oracle fixed pattern;
        # `drift_monitor` (repro.calib.DriftMonitor) is probed between
        # batches and, when ADC offsets drifted past its threshold,
        # hands back a refreshed snapshot that is HOT-SWAPPED into the
        # baked plans - per-layer plans AND fusion-group plans of every
        # kind (column_concat offsets concatenate, batch_concat offsets
        # stack per member; expert_stack groups have no measured device
        # and keep their bake): only chunk_offset leaves change, treedef
        # and static metadata stay identical, so the jitted
        # prefill/decode executables are reused as-is (no recompilation).
        # Plan cache (ISSUE 8): `plan_cache` names a .npz path for the
        # packed lowered artifact (repro.exec.store).  When the file
        # exists, cold start LOADS it and performs zero lowering work -
        # the int8 codes and scale tables on disk ARE the executable
        # (exec.lower.lowering_count() stays 0, pinned by tests);
        # otherwise the engine compiles as usual and writes the cache
        # for the next boot.  The cache stores the bake of THESE params:
        # after a weight update, delete the file (or pass a new path).
        # Fleet (ISSUE 10): `fleet` is a repro.fleet.FleetMonitor; its
        # probe heartbeat runs between batches next to the drift check,
        # and a dead chip triggers remap() - the spare's freshly
        # calibrated tables hot-swap into the served plans exactly like
        # a drift refresh (value-only; executables reused).
        self.model = None
        self.drift_monitor = drift_monitor
        self.fleet = fleet
        step_kw = {}
        if prelower and run.analog.mode != "digital":
            with obs_trace.span("serve.compile", model=cfg.name) as _sp:
                if plan_cache is not None and os.path.exists(plan_cache):
                    from repro.exec.store import load_plan

                    obs_metrics.counter("serve.plan_cache.hit").inc()
                    obs_trace.event("serve.plan_cache", status="hit",
                                    path=plan_cache)
                    self.model = api.CompiledModel(
                        spec=T.lm_module_spec(cfg, params), params=params,
                        run_cfg=run, lowered=load_plan(plan_cache),
                        calibration=calibration,
                    )
                    _sp.add(route="plan_cache")
                else:
                    if plan_cache is not None:
                        obs_metrics.counter("serve.plan_cache.miss").inc()
                        obs_trace.event("serve.plan_cache", status="miss",
                                        path=plan_cache)
                    self.model = api.compile(
                        T.lm_module_spec(cfg, params), params, run,
                        calibration=calibration,
                    )
                    if plan_cache is not None:
                        from repro.exec.store import save_plan

                        save_plan(plan_cache, self.model.lower())
                    _sp.add(route="lower")
                # static per-inference cost of the plans this engine serves
                obs_energy.record(self.model, prefix="serve.energy")
            params = self.model.lower()
            if shd.get_mesh() is not None:
                # plan leaves shard by the same logical axes as the
                # weights they were baked from (sharding.plan_specs_like)
                specs = self.model.sharding_specs()
                params = jax.device_put(
                    params, shd.sharding_like(specs, params)
                )
                step_kw = dict(abstract_params=params, param_specs=specs)
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        self.greedy = greedy
        self.prefill, self.decode = make_serve_steps(cfg, run, **step_kw)
        # a batch's empty cache, made by one compiled call: eager zeros
        # dispatch a primitive each, which JAX re-traces whenever its
        # primitive cache has evicted them
        self.new_cache = jax.jit(
            lambda b: T.init_lm_cache(cfg, b, max_len, dtype=jnp.float32),
            static_argnums=0)
        self.rng = jax.random.PRNGKey(seed)

    def _expert_stats(self, cache, tokens: int, pending: list) -> None:
        """While observed, keep one step's routed (token, held expert)
        pairs and the row tiles the grouped expert dispatch ran: totals
        taken on the device (the cache they sit in is donated to the
        next step), not read back yet."""
        if not self.cfg.held_experts or not obs_trace.observed():
            return
        stats = [c["moe"] for c in cache["layers"].values()
                 if isinstance(c, dict) and "moe" in c]
        if stats:
            block_m = M.tile_rows(tokens, self.cfg.top_k,
                                  self.cfg.held_experts)
            pending.append((_moe_totals(stats), block_m))

    @staticmethod
    def _count_experts(pending: list) -> None:
        """Read a batch's kept step totals back in one transfer and count
        them."""
        if not pending:
            return
        with obs_trace.span("lm.moe_stats"):
            got = jax.device_get([totals for totals, _ in pending])
        for (rows, tiles), (_, block_m) in zip(got, pending):
            obs_metrics.counter("lm.moe.held_rows").inc(int(rows))
            obs_metrics.counter("lm.moe.row_tiles").inc(int(tiles))
            obs_metrics.counter("lm.moe.tile_rows").inc(int(tiles) * block_m)

    def _sample(self, logits):
        if self.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.rng, k = jax.random.split(self.rng)
        return jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)

    def maybe_recalibrate(self) -> bool:
        """Drift-monitor hook (called between batches): probe the devices
        and, on drift, hot-swap the refreshed snapshot's offset tables
        into the served plans.  Returns True iff a swap happened."""
        if self.drift_monitor is None or self.model is None:
            return False
        snapshot = self.drift_monitor.maybe_refresh()
        if snapshot is None:
            return False
        with obs_trace.span("serve.hot_swap"):
            self.model = self.model.with_calibration(snapshot)
            swapped = self.model.lower()
            if shd.get_mesh() is not None:
                swapped = jax.device_put(
                    swapped,
                    shd.sharding_like(self.model.sharding_specs(), swapped),
                )
            self.params = swapped
        obs_metrics.counter("serve.hot_swap").inc()
        return True

    def maybe_remap(self) -> bool:
        """Fleet-health hook (called between batches): probe every chip
        and, when one died, remap its chunks onto a spare and hot-swap
        the re-gathered tables into the served plans.  Returns True iff
        a remap happened."""
        if self.fleet is None or self.model is None:
            return False
        model = self.fleet.maybe_remap(self.model)
        if model is None:
            return False
        with obs_trace.span("serve.hot_swap", reason="fleet.remap"):
            self.model = model
            swapped = self.model.lower()
            if shd.get_mesh() is not None:
                swapped = jax.device_put(
                    swapped,
                    shd.sharding_like(self.model.sharding_specs(), swapped),
                )
            self.params = swapped
        obs_metrics.counter("serve.hot_swap").inc()
        return True

    def run_batch(self, requests: list[Request]) -> list[Request]:
        """Serve one group of <= batch_size requests to completion.

        Telemetry (repro.obs, host-side only - the jitted steps are
        untouched): a ``serve.batch`` span nests ``serve.prefill`` and
        ``serve.decode`` spans; histograms ``serve.queue_us`` (admission
        -> batch start), ``serve.prefill_us``, ``serve.decode_us`` (per
        step), ``serve.request_us`` (admission -> completion) and
        ``serve.batch_occupancy`` (filled fraction of decode slots).
        The per-step decode sync replaces the host sync the following
        ``int(next_tok[i])`` read would force anyway.
        """
        assert len(requests) <= self.batch_size
        self.maybe_recalibrate()
        self.maybe_remap()
        b = len(requests)
        t_start = obs_trace.clock_us()
        for r in requests:
            if r.t_enqueue_us is not None:
                obs_metrics.histogram("serve.queue_us").record(
                    t_start - r.t_enqueue_us
                )
        obs_metrics.histogram("serve.batch_occupancy").record(
            b / self.batch_size
        )
        prompt_len = max(len(r.prompt) for r in requests)
        with obs_trace.span("serve.batch", batch=b,
                            prompt_len=prompt_len) as _bsp:
            toks = np.zeros((b, prompt_len), np.int32)
            for i, r in enumerate(requests):
                toks[i, prompt_len - len(r.prompt):] = r.prompt  # left-pad
            cache = self.new_cache(b)
            with obs_trace.span("serve.prefill", batch=b,
                                prompt_len=prompt_len) as psp:
                logits, cache = self.prefill(
                    self.params, {"tokens": jnp.asarray(toks)}, cache
                )
                next_tok = jax.block_until_ready(self._sample(logits))
            obs_metrics.histogram("serve.prefill_us").record(psp.dur_us)
            moe_stats = []
            self._expert_stats(cache, b * prompt_len, moe_stats)
            max_new = max(r.max_new_tokens for r in requests)
            outs = [[] for _ in range(b)]
            done = np.zeros(b, bool)
            steps = 0
            with obs_trace.span("serve.decode", batch=b) as dsp:
                for _ in range(max_new):
                    for i, r in enumerate(requests):
                        if not done[i]:
                            tok = int(next_tok[i])
                            outs[i].append(tok)
                            if (r.eos_id is not None and tok == r.eos_id
                                ) or len(outs[i]) >= r.max_new_tokens:
                                done[i] = True
                                obs_metrics.histogram(
                                    "serve.request_us"
                                ).record(obs_trace.clock_us() - (
                                    r.t_enqueue_us
                                    if r.t_enqueue_us is not None
                                    else t_start
                                ))
                    if done.all():
                        break
                    t_step = obs_trace.clock_us()
                    logits, cache = self.decode(
                        self.params, next_tok[:, None], cache
                    )
                    next_tok = jax.block_until_ready(self._sample(logits))
                    obs_metrics.histogram("serve.decode_us").record(
                        obs_trace.clock_us() - t_step
                    )
                    self._expert_stats(cache, b, moe_stats)
                    steps += 1
                dsp.add(steps=steps)
            self._count_experts(moe_stats)
            _bsp.add(tokens=int(sum(len(o) for o in outs)))
        for i, r in enumerate(requests):
            r.output = np.asarray(outs[i], np.int32)
        return requests

    def serve(self, requests: list[Request]) -> list[Request]:
        """Serve an arbitrary number of requests in batched groups."""
        now = obs_trace.clock_us()
        for r in requests:
            if r.t_enqueue_us is None:
                r.t_enqueue_us = now
        out = []
        for i in range(0, len(requests), self.batch_size):
            group = requests[i : i + self.batch_size]
            obs_trace.event("serve.refill", group=i // self.batch_size,
                            size=len(group))
            out.extend(self.run_batch(group))
        return out
