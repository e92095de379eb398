"""Compiled analog programs: :class:`CompiledModel` plus the canonical
single-layer :func:`apply_linear` (the function every model matmul routes
through; ``repro.core.analog.analog_linear_apply`` is its deprecation
shim).

``CompiledModel`` is the one executable object the serve engine, the train
step, eval loops and the examples consume:

    model = api.compile(spec, params, run_cfg)
    y     = model.apply(x)              # run the compiled program
    plan  = model.lower()               # AnalogPlan (stack) / lowered tree
    model = model.relower(new_params)   # re-bake after a weight update
    axes  = model.sharding_specs()      # logical-axis specs incl. plans

Lifecycle contract (unchanged from repro.exec): training calls
``compile``/``relower`` inside the differentiated step so HIL gradients
reach the float masters; serve and eval compile once and replay
``lower()``'s output through jitted steps.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig
from repro.exec.lower import lower_layer
from repro.exec.plan import AnalogPlan
from repro.exec.run import run as run_plan
from repro.exec.run import run_layer


def apply_linear(
    params: dict,
    x: jax.Array,
    cfg: AnalogConfig,
    *,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Apply one analog (or digital) linear layer: x [..., K] -> y [..., N].

    The single-layer hot path of the api: a pre-baked ``"_plan"`` entry in
    ``params`` (placed there by :func:`repro.api.compile.lower_tree`) is
    replayed directly; otherwise the layer is lowered per call with STE
    quantizers, which is exactly the HIL training scheme.  A baked plan
    whose static execution attrs disagree with the call-site config is
    ignored (per-call lowering takes over) rather than silently running
    the wrong encoding.
    """
    if cfg.mode == "digital":
        y = jnp.einsum("...k,kn->...n", x, params["w"].astype(x.dtype))
        if "b" in params:
            y = y + params["b"].astype(y.dtype)
        return y
    lp = params.get("_plan")
    if lp is not None and (
        lp.signed_input != cfg.signed_input
        or lp.chunk_rows != cfg.chunk_rows
    ):
        lp = None
    if lp is None:
        lp = lower_layer(params, cfg)
    return run_layer(lp, x, cfg, key=key)


@dataclasses.dataclass
class CompiledModel:
    """An executable analog model: declaration + params + baked plans.

    ``calibration`` records the measurement snapshot the plans were baked
    from (None = oracle fixed-pattern bake); :meth:`with_calibration`
    hot-swaps a refreshed snapshot's offset tables into the baked plans
    without recompiling them.
    """

    spec: Any                      # ModuleSpec
    params: Any                    # the float master parameter pytree
    run_cfg: Any                   # RunConfig or AnalogConfig
    lowered: Any                   # AnalogPlan | lowered tree | None (digital)
    calibration: Any = None        # CalibrationSnapshot | None (oracle)

    @property
    def acfg(self) -> AnalogConfig:
        return getattr(self.run_cfg, "analog", self.run_cfg)

    # ------------------------------------------------------------- execute
    def apply(self, *args, **kw):
        """Run the compiled program.  Stacks take ``(x, *, key=None)``
        and replay as the whole-plan single-dispatch megakernel when the
        plan and the call allow it, layer by layer otherwise
        (:func:`repro.exec.run.run`); tree specs forward to the host
        program declared by the spec (``spec.apply_fn(model, *args,
        **kw)``).  Block specs take ``(x [batch, seq, d_model], *,
        key=None)`` and replay the whole attention+MLP block - single
        ``pallas_call`` on the megakernel route, 4 dispatches per layer
        on noisy calls."""
        if self.spec.apply_fn is not None:
            return self.spec.apply_fn(self, *args, **kw)
        if self.spec.kind not in ("stack", "block"):
            raise ValueError(
                f"spec {self.spec.name!r} declares no apply_fn"
            )
        return self.run_stack(*args, **kw)

    def run_stack(self, x: jax.Array, *,
                  key: Optional[jax.Array] = None) -> jax.Array:
        """Execute the layer chain (plan replay - megakernel-routed when
        eligible - or the digital reference path with the same
        ReLU/flatten inter-layer glue)."""
        if self.lowered is not None:
            return run_plan(self.lowered, x, key=key)
        h = x
        n = len(self.spec.layers)
        for i, l in enumerate(self.spec.layers):
            if isinstance(self.params, dict) and l.name in self.params:
                p = self.params[l.name]
            else:
                p = self.params        # single-layer convenience
            h = apply_linear(p, h, self.acfg)
            if i < n - 1:
                h = jax.nn.relu(h)
            if l.flatten_out:
                # merge the position axis into features, preserving any
                # leading batch dims (same semantics as the plan executor)
                h = h.reshape(h.shape[:-2] + (-1,))
        return h

    # --------------------------------------------------------------- plans
    def lower(self):
        """The compiled artifact that jitted steps replay: the stack's
        :class:`AnalogPlan`, or the pre-lowered params tree (tree kind;
        the raw params in digital mode)."""
        if self.spec.kind == "stack":
            return self.lowered
        return self.params if self.lowered is None else self.lowered

    def relower(self, params) -> "CompiledModel":
        """Re-bake the plans for updated parameters (one weight update =
        one relower; the spec, run config and calibration are reused)."""
        from repro.api.compile import compile as _compile

        return _compile(self.spec, params, self.run_cfg,
                        calibration=self.calibration)

    def with_calibration(self, snapshot) -> "CompiledModel":
        """Hot-swap a refreshed calibration snapshot's measured tables
        into the baked plans (the drift-refresh and fleet-remap path):
        only the ``chunk_offset`` leaves - and, where the plan baked a
        measured gain table (``store.chunk_gain``) and a matching
        ``gain_table`` is present, the gain leaves - change; treedef and
        static metadata are identical, so jitted replays of
        :meth:`lower`'s output keep their compiled executables.  Stack
        plans swap by spec layer name, tree plans by dotted path
        (``api.compile.swap_calibration``)."""
        from repro.api.compile import swap_calibration
        from repro.exec.lower import plan_with_tables

        if self.lowered is None:
            return dataclasses.replace(self, calibration=snapshot)
        if isinstance(self.lowered, AnalogPlan):
            offs, gains = [], []
            for l, lp in zip(self.spec.layers, self.lowered.layers):
                rec = snapshot.layer(l.name)
                offs.append(None if rec is None else rec.chunk_offset)
                g = None if rec is None else rec.gain_table
                if (g is None or lp.store.chunk_gain is None
                        or lp.colsum is not None
                        or jnp.shape(g) != lp.store.chunk_gain.shape):
                    g = None
                gains.append(g)
            lowered = plan_with_tables(self.lowered, offs, gains)
        else:
            lowered = swap_calibration(self.lowered, snapshot)
        return dataclasses.replace(
            self, lowered=lowered, calibration=snapshot
        )

    def verify(self, *, strict: bool = False, cheap_only: bool = False):
        """Run the FULL static invariant rule set
        (:mod:`repro.verify.invariants`) over this model's spec, lowered
        artifact and baked calibration - including the non-cheap rules
        ``compile(..., verify=True)`` skips (identity drift-swap treedef
        pinning, sharding-spec coverage).  Returns the tuple of
        :class:`repro.verify.Diagnostic` records (empty = clean);
        ``strict=True`` raises :class:`repro.verify.VerifyError`
        instead."""
        from repro.verify import invariants as _inv

        diags = _inv.verify_model(self, cheap_only=cheap_only)
        if strict:
            _inv.check(diags)
        return diags

    def group_plan(self, name: str):
        """The lowered :class:`repro.exec.plan.GroupPlan` of a declared
        fusion group - the canonical replacement for reaching into the
        lowered tree by the ``"_qkv_plan"`` magic key.  ``name`` is the
        :class:`repro.api.module.GroupSpec` name (e.g.
        ``"layers.l0.attn.qkv"``).  Returns None when the group did not
        fuse under this config (column_concat under static activation
        calibration without a group-calibrated shared input LSB, or
        digital mode, which compiles no plans)."""
        from repro.api.module import group_parent

        g = self.spec.group(name)          # KeyError lists declared groups
        if self.spec.kind != "tree" or self.lowered is None:
            return None
        parent, _ = group_parent(g)
        node = self.lowered
        for part in parent.split(".") if parent else ():
            node = node[int(part)] if isinstance(
                node, (list, tuple)
            ) else node[part]
        return node.get("_groups", {}).get(g.local_name)

    # ------------------------------------------------------------ sharding
    def sharding_specs(self):
        """Logical-axis spec pytree matching :meth:`lower`'s output -
        including the baked plan leaves, so a pre-lowered tree shards over
        a mesh exactly like ordinary params (see distributed.sharding)."""
        from repro.distributed import sharding as shd

        if self.spec.kind == "stack":
            if not isinstance(self.lowered, AnalogPlan):
                return None
            axes = [l.sharding for l in self.spec.layers]
            return shd.analog_plan_specs(self.lowered, axes)
        base = self.spec.param_axes
        if base is None:
            raise ValueError(
                f"spec {self.spec.name!r} carries no param_axes"
            )
        if self.lowered is None:
            return base
        return shd.plan_specs_like(base, self.lowered)
