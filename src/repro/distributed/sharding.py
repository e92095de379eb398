"""Logical-axis sharding: named axes on every parameter/activation, resolved
against the active mesh by a rules table (MaxText-style, dependency-free).

Parallelism mapping (production mesh, see launch/mesh.py):
- ``data`` (16)  - batch DP; MoE token groups
- ``model`` (16) - TP: attention heads, FFN hidden, vocab, experts (EP),
                   analog tile grid columns
- ``pod``  (2)   - extra DP by default; pipeline stages when PP is enabled

Pre-lowered analog plans split their output columns over ``model``
(logical axis ``analog_cols``) and keep the contraction axis whole: a
column's per-chunk ADC codes depend on that column alone, so the VMM
kernels run per device on column blocks (:func:`vmm_axes`) - whole
128-row BSS-2 chunks on each device, tile-parallelism across emulated
ASICs == TP across TPU chips.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axes, in priority order.  The first mesh
# axis that (a) exists in the active mesh and (b) is not yet taken by
# another logical axis of the same spec wins; otherwise the axis is
# replicated.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),                 # sequence kept local by default (SP opt-in)
    "seq_sp": ("model",),      # sequence-parallel alternative
    # FSDP: parameter embed dims shard over the data axis (ZeRO-3 style -
    # GSPMD all-gathers params per scan group, reduce-scatters grads).
    # Activations never carry the "embed" name (they use None), so batch
    # keeps the data axis for DP.
    "embed": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "qkv": (),
    "mlp": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "capacity": (),
    "layers": (),              # stacked-scan leading axis
    "chunks": (),              # analog fpn chunk axis
    "analog_cols": ("model",),  # output columns of pre-lowered plans
    "conv": (),
    "state": (),
    # decode caches: if kv_heads cannot shard (kv < model axis), the
    # sequence axis takes the model axis instead - flash-decoding-style
    # split-KV parallelism (resolution is shape-aware, right-to-left)
    "kv_seq": ("model",),
    "stage": ("pod",),         # pipeline stages
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict[str, tuple[str, ...]] = dict(DEFAULT_RULES)


_CTX = _Ctx()


def set_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None) -> None:
    _CTX.mesh = mesh
    if rules is not None:
        _CTX.rules = dict(rules)


def get_mesh() -> Optional[Mesh]:
    return _CTX.mesh


class use_mesh:
    """Context manager: activate a mesh (and optional rule overrides)."""

    def __init__(self, mesh: Mesh, rules: Optional[dict] = None):
        self.mesh, self.rules = mesh, rules
        self._saved: tuple = ()

    def __enter__(self):
        self._saved = (_CTX.mesh, _CTX.rules)
        set_mesh(self.mesh, self.rules)
        self._mesh_ctx = self.mesh
        self._mesh_ctx.__enter__()
        return self.mesh

    def __exit__(self, *exc):
        self._mesh_ctx.__exit__(*exc)
        _CTX.mesh, _CTX.rules = self._saved
        return False


def logical_to_spec(names: Sequence[Optional[str]]) -> P:
    """Resolve logical axis names to a PartitionSpec under the active rules."""
    mesh = _CTX.mesh
    axes_in_mesh = set(mesh.axis_names) if mesh is not None else set()
    used: set[str] = set()
    out = []
    for name in names:
        resolved = None
        if name is not None:
            for cand in _CTX.rules.get(name, ()):
                if cand in axes_in_mesh and cand not in used:
                    resolved = cand
                    used.add(cand)
                    break
        out.append(resolved)
    # multi-axis entries (e.g. batch -> ("pod", "data")): collapse tuple
    return P(*out)


def logical_to_spec_multi(names: Sequence[Optional[str]]) -> P:
    """Like logical_to_spec but a logical axis may absorb *all* its candidate
    mesh axes (used for 'batch' -> ('pod', 'data') joint DP)."""
    mesh = _CTX.mesh
    axes_in_mesh = set(mesh.axis_names) if mesh is not None else set()
    used: set[str] = set()
    out = []
    for name in names:
        resolved: tuple = ()
        if name is not None:
            for cand in _CTX.rules.get(name, ()):
                if cand in axes_in_mesh and cand not in used:
                    resolved = resolved + (cand,)
                    used.add(cand)
        out.append(resolved if resolved else None)
    return P(*out)


def resolve_spec(names: Sequence[Optional[str]], shape: Sequence[int]) -> P:
    """Shape-aware resolution: dims are assigned mesh axes right-to-left
    (most-specific logical axes sit rightmost in our layouts) and an axis is
    only taken when the dim size is divisible by it - otherwise the next
    candidate (or replication) applies.  This is what makes explicit
    in_shardings legal for every assigned architecture (e.g. kv_heads=2
    cannot take the 16-way model axis, so the cache's kv_seq dim does)."""
    mesh = _CTX.mesh
    if mesh is None:
        return P()
    names = tuple(names)
    if len(names) > len(shape):       # collapsed dims (e.g. [B*S, d]): keep
        names = names[-len(shape):]   # the trailing names, drop leading
    elif len(names) < len(shape):
        names = (None,) * (len(shape) - len(names)) + names
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set[str] = set()
    out: list = [None] * len(names)
    order = range(len(names) - 1, -1, -1)
    for i in order:
        name = names[i]
        if name is None:
            continue
        dim = shape[i]
        resolved: tuple = ()
        prod = 1
        for cand in _CTX.rules.get(name, ()):
            if cand in sizes and cand not in used and dim % (
                prod * sizes[cand]
            ) == 0:
                resolved = resolved + (cand,)
                prod *= sizes[cand]
                used.add(cand)
        if resolved:
            out[i] = resolved if len(resolved) > 1 else resolved[0]
    return P(*out)


def sharding_for(names: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None
                 ) -> Optional[NamedSharding]:
    mesh = _CTX.mesh
    if mesh is None:
        return None
    if shape is None:
        return NamedSharding(mesh, logical_to_spec_multi(names))
    return NamedSharding(mesh, resolve_spec(names, shape))


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names (shape-aware); no-op
    without a mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    sh = NamedSharding(mesh, resolve_spec(names, x.shape))
    return jax.lax.with_sharding_constraint(x, sh)


_SPEC_LEAF = lambda x: isinstance(x, tuple) and all(
    isinstance(e, (str, type(None))) for e in x
)


def tree_sharding(spec_tree) -> object:
    """Map a pytree of logical-name tuples to NamedShardings (or None).
    Shape-unaware variant (kept for replicated/scalar specs)."""
    mesh = _CTX.mesh
    if mesh is None:
        return None
    return jax.tree.map(
        lambda names: sharding_for(names), spec_tree, is_leaf=_SPEC_LEAF
    )


def sharding_like(spec_tree, abstract_tree) -> object:
    """Shape-aware tree sharding: resolve each leaf's logical names against
    the matching abstract leaf's shape (divisibility-checked)."""
    mesh = _CTX.mesh
    if mesh is None:
        return None

    def one(names, leaf):
        return NamedSharding(mesh, resolve_spec(names, leaf.shape))

    return jax.tree.map(one, spec_tree, abstract_tree, is_leaf=_SPEC_LEAF)


def rules_for(run) -> dict:
    """DEFAULT_RULES specialized by the RunConfig distribution knobs."""
    rules = dict(DEFAULT_RULES)
    if not getattr(run, "fsdp", True):
        rules["embed"] = ()
    if not getattr(run, "seq_sp", True):
        rules["seq_sp"] = ()
    return rules


# --------------------------------------------------------------------------
# Pre-lowered plan leaves (repro.exec plans) as first-class shardables: a
# LayerPlan's arrays keep the stack prefix of the weight they were baked
# from and split their output columns over ``analog_cols``, the layout the
# VMM kernels run in under a mesh (:func:`vmm_axes`), so a pre-lowered
# params tree shards over the mesh like the raw params tree and a kernel
# call moves no weights.
# --------------------------------------------------------------------------
def vmm_axes(m: int, n: int):
    """Mesh axes ``(rows, cols)`` over which an analog VMM ``[M, K] x
    [K, N]`` splits on the active mesh: rows by the ``"batch"`` rule,
    output columns by ``"analog_cols"`` - the axes the plan leaves carry
    (:func:`layer_plan_specs`).  The contraction axis stays whole.  Rows
    that do not divide stay whole (every device computes them); columns
    that do not divide the ``analog_cols`` axis raise, since the weights
    were placed split."""
    mesh = _CTX.mesh
    rows, cols = resolve_spec(("batch", "analog_cols"), (m, n))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    wanted = [a for a in _CTX.rules.get("analog_cols", ())
              if sizes.get(a, 1) > 1]
    if wanted and cols is None:
        raise ValueError(
            f"an analog layer's {n} output columns do not split over the "
            f"{sizes[wanted[0]]} devices of mesh axis {wanted[0]!r}"
        )
    return rows, cols


def layer_plan_specs(lp, w_spec: Sequence[Optional[str]]):
    """Spec pytree (a LayerPlan holding logical-name tuples) for one -
    possibly scan-stacked - LayerPlan.

    ``w_spec`` is the logical spec of the master weight, e.g.
    ``("embed", "mlp")`` or ``("layers", "embed", "mlp")`` for a stacked
    layer: the trailing two names are the (in, out) axes, anything before
    them is the stack prefix shared by every baked array.  Baked arrays
    keep the prefix, leave the input axis whole and take ``analog_cols``
    for the output axis.
    """
    import dataclasses

    w_spec = tuple(w_spec)
    prefix = w_spec[:-2]
    nd = len(prefix)         # rank of the stack prefix
    cols = "analog_cols"

    def per_col(leaf):       # [*, N]-shaped leaves (gain may be scalar)
        if leaf is None:
            return None
        return prefix + (cols,) if leaf.ndim > nd else prefix

    s = lp.store
    store = dataclasses.replace(
        s,
        codes=prefix + (None, cols),
        w_scale=prefix + (None, cols),
        gain=per_col(s.gain),
        col_gain=None if s.col_gain is None else prefix + (cols,),
        row_gain=None if s.row_gain is None else prefix + (None, None),
        chunk_gain=(
            None if s.chunk_gain is None else prefix + ("chunks", cols)
        ),
        gain_map=None if s.gain_map is None else prefix + (None, cols),
    )
    return dataclasses.replace(
        lp,
        store=store,
        a_scale=prefix,
        a_scale_in=None if lp.a_scale_in is None else prefix,
        chunk_offset=(
            None if lp.chunk_offset is None else prefix + ("chunks", cols)
        ),
        colsum=None if lp.colsum is None else prefix + (cols,),
        bias=None if lp.bias is None else prefix + (cols,),
    )


def analog_plan_specs(plan, layer_axes: Sequence[Sequence[Optional[str]]]):
    """Spec pytree for a whole AnalogPlan: ``layer_axes[i]`` is the
    (in_name, out_name) pair of layer i.  The megakernel packing (when
    baked) is replicated: its row-concatenated operands interleave layers,
    so no single logical axis describes them - they are small by
    eligibility (whole-chain VMEM residency)."""
    import dataclasses

    layers = tuple(
        layer_plan_specs(lp, tuple(ax))
        for lp, ax in zip(plan.layers, layer_axes)
    )
    mega = plan.mega
    if mega is not None:
        # every data leaf gets a replicated spec - including the float-glue
        # extras (deq/bias/enc/ln), which are present exactly when the pack
        # carries mixed-domain hand-offs
        repl = {
            f: (None,) * getattr(mega, f).ndim
            for f in ("gain", "off", "deq", "bias", "enc", "ln")
            if getattr(mega, f) is not None
        }
        repl["stores"] = tuple(
            dataclasses.replace(s, **{
                f: (None,) * getattr(s, f).ndim
                for f in ("codes", "w_scale", "gain", "col_gain",
                          "row_gain", "chunk_gain", "gain_map")
                if getattr(s, f) is not None
            })
            for s in mega.stores
        )
        mega = dataclasses.replace(mega, **repl)
    block = plan.block
    if block is not None:
        block = dataclasses.replace(
            block,
            ln1=(None,) * block.ln1.ndim,
            ln2=(None,) * block.ln2.ndim,
        )
    return dataclasses.replace(plan, layers=layers, mega=mega, block=block)


def group_plan_specs(gp, parent_spec):
    """Spec pytree for one lowered fusion group
    (:class:`repro.exec.plan.GroupPlan`), derived from the members'
    master-weight specs in ``parent_spec`` (the parent node's spec dict):

    - ``column_concat``: the fused plan takes member 0's stack prefix
      and splits its concatenated output columns like any plan
      (shape-aware resolution falls back to replication when the fused
      width does not divide the mesh axis),
    - ``batch_concat``: ditto, with the member axis (replicated) spliced
      in before the (in, out) pair,
    - ``expert_stack``: the member's raw stacked-weight spec (e.g.
      ``("expert", "embed", None)``) carries the expert axis, which takes
      ``model`` only where the output columns cannot.
    """
    import dataclasses

    m0 = gp.member_names[0]
    mspec = parent_spec[m0]
    w_spec = tuple(mspec["w"]) if isinstance(mspec, dict) else tuple(mspec)
    if gp.kind == "batch_concat":
        w_spec = w_spec[:-2] + (None,) + w_spec[-2:]
    return dataclasses.replace(gp, fused=layer_plan_specs(gp.fused, w_spec))


def plan_specs_like(spec_tree, lowered_tree):
    """Augment a logical-axis spec tree with entries for the ``"_plan"`` /
    ``"_groups"`` / ``"_qkv_plan"`` leaves of a pre-lowered params tree,
    so the result matches the lowered tree's structure leaf for leaf.

    Plan axes are derived from the sibling master-weight specs: a layer's
    ``"_plan"`` inherits its own ``"w"`` spec; fusion-group plans derive
    from their members' specs (:func:`group_plan_specs`); the legacy
    ``"_qkv_plan"`` alias inherits the ``wq`` weight's spec as before.
    """
    if isinstance(lowered_tree, dict):
        out = {}
        for k, v in lowered_tree.items():
            if k == "_plan":
                out[k] = layer_plan_specs(v, spec_tree["w"])
            elif k == "_groups":
                out[k] = {
                    name: group_plan_specs(gp, spec_tree)
                    for name, gp in v.items()
                }
            elif k == "_qkv_plan":
                out[k] = layer_plan_specs(v, spec_tree["wq"]["w"])
            else:
                out[k] = plan_specs_like(spec_tree[k], v)
        return out
    if isinstance(lowered_tree, (list, tuple)) and not _SPEC_LEAF(
        lowered_tree
    ):
        return type(lowered_tree)(
            plan_specs_like(s, v) for s, v in zip(spec_tree, lowered_tree)
        )
    return spec_tree
