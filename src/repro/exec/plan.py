"""Compiled execution plans for stacks of analog layers.

The paper executes its network as a *pre-compiled schedule* of chunked
analog VMM passes on fixed synapse tiles (Fig. 4, §II-C): weights are
quantized, calibrated and placed ONCE, then inference replays the schedule.
This module is the software mirror of that split:

- :class:`WeightStore` - the packed weight state of one lowered layer:
  6-bit signed weight codes (int8, already padded to a whole number of
  128-row chunks), per-column weight LSB, the calibrated gain and the
  fixed-pattern / measured gain tables.  The fp32 effective weights
  (``w_eff``) are a DERIVED dequantized view, computed in-graph - plan
  bytes scale with what the chip actually stores (ISSUE 8).
- :class:`LayerPlan` - one analog layer after lowering: its
  :class:`WeightStore`, the dequantization scales, the frozen
  fixed-pattern chunk offsets, and the static execution attributes
  (signed encoding, epilogue, chunk geometry).
- :class:`AnalogPlan` - an ordered stack of :class:`LayerPlan` that runs
  as one jitted analog program (see :mod:`repro.exec.run`).

Both are registered JAX pytrees: the array fields are leaves (so a plan
flows through ``jax.jit`` / ``jax.grad`` / donation like any params tree
and re-running a cached executable needs NO retracing), while the
execution attributes are hashable static metadata (so two plans with the
same geometry share one compiled executable).

Lifecycle contract (ISSUE 1): ``lower()`` is called once per weight
update - the train step re-lowers every step (gradients flow through the
lowering's straight-through quantizers back to the float master weights),
while serve/eval lower once and replay the plan for every request.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig
from repro.core.hw import BSS2

# Epilogue tags (static). "none": raw accumulated ADC codes leave the
# layer and are dequantized to float. "relu_shift": ADC-fused ReLU +
# right-shift requantization to 5-bit codes (paper §II-A) - the next
# layer consumes the codes directly, no float glue in between.
EPILOGUE_NONE = "none"
EPILOGUE_RELU_SHIFT = "relu_shift"

# Fusion-group kinds (static).  A fusion group is N declared layers that
# replay as ONE analog dispatch (paper §II-D: fill the 256x512 array per
# dispatch, columns run in parallel):
#   "column_concat": same input, concatenated output columns (attention
#                    QKV) - one [K, sum(N_i)] pass.
#   "batch_concat":  same weight geometry, DIFFERENT inputs (RWKV
#                    r/k/v/g) - the member matrices sit on disjoint
#                    column blocks of one array config and every member's
#                    input batch streams through in the same pass; the
#                    emulator computes it as one vmapped member-axis
#                    dispatch (the discarded off-diagonal columns cannot
#                    affect the kept ones - ADC column independence).
#   "expert_stack":  a stacked [E, K, N] expert weight array (MoE) lowered
#                    ONCE into a per-expert plan replayed by the einsum
#                    dispatch path.
GROUP_COLUMN_CONCAT = "column_concat"
GROUP_BATCH_CONCAT = "batch_concat"
GROUP_EXPERT_STACK = "expert_stack"
GROUP_KINDS = (GROUP_COLUMN_CONCAT, GROUP_BATCH_CONCAT, GROUP_EXPERT_STACK)

# Input-domain tags (static).  Baked into AnalogPlan at lower time so the
# executor never has to GUESS whether the initial activations are already
# unsigned 5-bit event codes: "codes" skips activation quantization,
# "float" quantizes like any other float activation.  (The legacy default
# inferred this from layer 0's *output* epilogue, which mis-classifies a
# mixed plan whose first layer emits relu_shift but consumes floats.)
INPUT_CODES = "codes"
INPUT_FLOAT = "float"


def default_shift(n_chunks: int) -> int:
    """Right-shift mapping the accumulated non-negative ADC range
    ``[0, C * adc_max]`` onto the 5-bit activation range (paper §II-A:
    "applying bitwise right-shifts")."""
    full = n_chunks * BSS2.adc_max
    shift = 0
    while (full >> shift) > BSS2.a_max:
        shift += 1
    return shift


@dataclasses.dataclass(frozen=True)
class WeightStore:
    """Packed weight state of one lowered analog layer (frozen pytree):
    what the chip actually stores - 6-bit signed weight codes plus the
    calibration tables - with the fp32 effective weights as a DERIVED
    view (:attr:`w_eff`) instead of a baked array (ISSUE 8).

    Array fields (pytree leaves):
      codes:      [.., K_pad, N] quantized 6-bit weight codes, rows
                  zero-padded to a whole number of chunks.  ``int8`` in
                  a concretely-lowered plan (:meth:`packed`); float32
                  STE codes while tracing (HIL training re-lowers inside
                  ``jax.grad`` - an int8 cast would kill the
                  straight-through gradient to the float masters).
      w_scale:    [.., 1, N] per-column weight LSB.
      gain:       scalar (or per-column / per-member) calibrated analog
                  gain the executor dispatches with (NOT folded into
                  ``w_eff``).
      col_gain:   optional [.., N] per-column fixed-pattern gain
                  (rank-1 noise mode).
      row_gain:   optional [.., G, K_pad] per-row fixed-pattern gain,
                  one row-vector per column block (G = 1 for a solo
                  layer; one per member for a column_concat fusion,
                  split by ``col_blocks``).  Pad rows hold exact 1.0.
      chunk_gain: optional [.., C, N] measured per-(chunk, column) gain
                  table (calibrated bake; Weis et al. 2020).
      gain_map:   optional [.., K_pad, N] full per-synapse gain map
                  (``NoiseConfig.mode == "full"``), pad rows exact 1.0.

    Static fields (hashable aux data):
      chunk_rows: rows per analog chunk (row_gain/chunk_gain layout).
      col_blocks: per-member output widths of a column_concat fusion
                  (sums to N), or None for a single block.

    Dequantization contract (:attr:`w_eff`): multiply codes by col_gain,
    then the per-block row_gain, then the chunk-repeated chunk_gain,
    then gain_map - ELEMENTWISE in exactly this order, which reproduces
    ``repro.core.noise.effective_weight`` / the measured-bake product of
    ``exec.lower`` bit-for-bit (absent components multiply by nothing;
    present-but-padded entries are exact 1.0, and ``x * 1.0`` is exact
    in IEEE-754).
    """

    codes: jax.Array
    w_scale: jax.Array
    gain: jax.Array
    col_gain: Optional[jax.Array] = None
    row_gain: Optional[jax.Array] = None
    chunk_gain: Optional[jax.Array] = None
    gain_map: Optional[jax.Array] = None
    chunk_rows: int = BSS2.signed_rows
    col_blocks: Optional[Tuple[int, ...]] = None

    @property
    def k_pad(self) -> int:
        return self.codes.shape[-2]

    @property
    def w_eff(self) -> jax.Array:
        """The dequantized fp32 effective weights [.., K_pad, N] - the
        exact array the legacy bake stored as a leaf."""
        w = self.codes.astype(jnp.float32)
        if self.col_gain is not None:
            w = w * self.col_gain[..., None, :]
        if self.row_gain is not None:
            if self.col_blocks is None:
                w = w * self.row_gain[..., 0, :, None]
            else:
                # each column takes its block's row gain by a select over
                # a column iota, not by slicing and concatenating: the
                # blocks' edges need not fall on a mesh's column split,
                # and a concatenate across them reshards the weights
                col = jax.lax.broadcasted_iota(jnp.int32, (1, w.shape[-1]),
                                               1)
                rg, c0 = self.row_gain[..., 0, :, None], 0
                for gi, nb in enumerate(self.col_blocks[:-1]):
                    c0 += nb
                    rg = jnp.where(col >= c0,
                                   self.row_gain[..., gi + 1, :, None], rg)
                w = w * rg
        if self.chunk_gain is not None:
            w = w * jnp.repeat(self.chunk_gain, self.chunk_rows, axis=-2)
        if self.gain_map is not None:
            w = w * self.gain_map
        return w

    def packed(self) -> "WeightStore":
        """Cast concrete float codes to int8 (values are in [-63, 63] by
        the quantizer).  A no-op on traced codes - packing under a trace
        would break the STE gradient of HIL re-lowering - and on stores
        that are already packed."""
        if isinstance(self.codes, jax.core.Tracer):
            return self
        if self.codes.dtype == jnp.int8:
            return self
        return dataclasses.replace(
            self, codes=self.codes.astype(jnp.int8)
        )


jax.tree_util.register_dataclass(
    WeightStore,
    data_fields=[
        "codes", "w_scale", "gain", "col_gain", "row_gain", "chunk_gain",
        "gain_map",
    ],
    meta_fields=["chunk_rows", "col_blocks"],
)


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One lowered analog layer (frozen pytree).

    Array fields (pytree leaves):
      store:        the :class:`WeightStore` - packed int8 weight codes,
                    per-column weight LSB, calibrated gain and the
                    fixed-pattern/measured gain tables.  ``w_eff`` /
                    ``w_scale`` / ``gain`` are derived views over it
                    (the legacy leaf names, kept as properties).
      a_scale:      scalar static activation LSB (used when
                    ``act_calib == "static"``; dynamic calib recomputes
                    per call inside run()).
      a_scale_in:   optional scalar: the SHARED static input LSB of a
                    snapshot-calibrated fused dispatch group (the widest
                    member scale, so no member's range is truncated).
                    When set, static encoding - and the matching
                    dequantization - use it instead of ``a_scale`` (the
                    layer's own calibrated scale, kept for solo
                    lowering).  None: plain layer (legacy behavior).
      chunk_offset: [C, N] fixed-pattern ADC offsets or None.
      colsum:       [N] column sums of w_eff (offset-encoding correction
                    term) or None.
      bias:         [N] digital bias or None.

    Static fields (hashable aux data):
      k:            logical input width before chunk padding.
      n:            output width.
      chunk_rows:   rows per analog chunk.
      signed_input: "none" | "split" | "offset" for THIS layer.
      epilogue:     "none" | "relu_shift".
      shift:        right-shift amount for the relu_shift epilogue.
      flatten_out:  flatten trailing output dims into one feature axis
                    before the next layer (the conv->fc1 im2col glue).
    """

    store: WeightStore
    a_scale: jax.Array
    chunk_offset: Optional[jax.Array]
    colsum: Optional[jax.Array]
    bias: Optional[jax.Array]
    k: int
    n: int
    chunk_rows: int
    signed_input: str
    epilogue: str = EPILOGUE_NONE
    shift: int = 0
    flatten_out: bool = False
    a_scale_in: Optional[jax.Array] = None

    @property
    def w_eff(self) -> jax.Array:
        """Derived [.., K_pad, N] effective weights (dequantized in-graph
        from the packed store; bit-exact vs the legacy fp32 bake)."""
        return self.store.w_eff

    @property
    def w_scale(self) -> jax.Array:
        return self.store.w_scale

    @property
    def gain(self) -> jax.Array:
        return self.store.gain

    @property
    def k_pad(self) -> int:
        """Chunk-padded input width - shape queries go through here (or
        :attr:`WeightStore.codes`) so they never materialize the dequant
        view."""
        return self.store.codes.shape[-2]

    @property
    def n_chunks(self) -> int:
        return self.store.codes.shape[0] // self.chunk_rows


jax.tree_util.register_dataclass(
    LayerPlan,
    data_fields=[
        "store", "a_scale", "chunk_offset", "colsum", "bias", "a_scale_in",
    ],
    meta_fields=[
        "k", "n", "chunk_rows", "signed_input", "epilogue", "shift",
        "flatten_out",
    ],
)


@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """One lowered fusion group (frozen pytree): the fused dispatch plus
    the static member layout needed to hand each member its own result.

    Array fields (pytree leaves):
      fused: a :class:`LayerPlan` whose layout depends on ``kind``:
        - ``column_concat``: concatenated output columns
          (``[K_pad, sum(N_i)]`` - :func:`repro.exec.lower.lower_fused`),
        - ``batch_concat``: a member axis on EVERY leaf
          (``[G, K_pad, N]`` - :func:`repro.exec.lower.lower_batch_concat`;
          per-member ``a_scale``/``a_scale_in`` ride along stacked, so
          each member keeps its own input encoding),
        - ``expert_stack``: an expert axis on every leaf
          (``[E, K_pad, N]`` - :func:`repro.exec.lower.lower_expert_stack`).

    Static fields (hashable aux data):
      kind:         one of :data:`GROUP_KINDS`.
      member_names: the members' LOCAL names in the parent params node,
                    declaration order (e.g. ``("wq", "wk", "wv")``).
      member_ns:    each member's output width (column-split offsets for
                    ``column_concat``; informational otherwise).
    """

    kind: str
    fused: LayerPlan
    member_names: Tuple[str, ...]
    member_ns: Tuple[int, ...]

    @property
    def expected_dispatches(self) -> int:
        """A fusion group replays as ONE analog dispatch by construction
        (see :class:`AnalogPlan.expected_dispatches` for the counting
        contract)."""
        return 1


jax.tree_util.register_dataclass(
    GroupPlan,
    data_fields=["fused"],
    meta_fields=["kind", "member_names", "member_ns"],
)


def find_group(groups, kind: str, member_names: Tuple[str, ...]
               ) -> Optional[GroupPlan]:
    """Resolve a lowered :class:`GroupPlan` from a node's ``"_groups"``
    dict by (kind, exact member names) - how model host programs locate
    THEIR fusion group.  Matching on structure rather than the group's
    (user-chosen) name keeps consumers honest: a declared group of the
    wrong kind is never fed to the wrong replay path, and any group name
    works."""
    for gp in (groups or {}).values():
        if gp.kind == kind and gp.member_names == tuple(member_names):
            return gp
    return None


@dataclasses.dataclass(frozen=True)
class MegakernelPack:
    """Kernel-ready packing of an AnalogPlan chain for the whole-plan
    Pallas megakernel (built once by :func:`repro.exec.lower.pack_megakernel`).

    Array fields (pytree leaves):
      stores:   the per-layer :class:`WeightStore` records - shared with
                the chain's :class:`LayerPlan` leaves (same arrays, not
                copies), so the pack adds no weight bytes.  ``w_cat``
                ([sum(k_pad), n_max] effective weights, columns
                zero-padded to the common lane width, row-concatenated)
                is a derived view packed in-graph at dispatch time.
      gain:     [L, n_max] per-layer analog gains (broadcast + padded).
      off:      [sum(n_chunks), n_max] per-layer chunk offsets (zeros where
                a layer has none), chunk-concatenated.
      deq:      [L, n_max] per-layer in-kernel dequantization rows
                (``a_scale * w_scale / gain`` per column; zeros for
                code-domain hand-offs) or None for pure code chains.
      bias:     [L, n_max] per-layer digital biases (zeros where a layer
                has none) or None.
      enc:      [L, 1] per-layer static input-encoding LSBs (1.0 for
                codes-consuming layers) or None.
      ln:       [2, n_max] transformer-block RMSNorm scales (rows: ln1,
                ln2, zero-padded) or None for non-block chains.

    Static fields:
      schedule:   tuple of :class:`repro.kernels.analog_plan.MegaLayerMeta`
                  (row offsets, chunk geometry, shifts, flatten factors,
                  per-layer encode/hand-off domain tags).
      n_max:      packed lane width (max layer output, 128-aligned).
      chunk_rows: rows per analog chunk (uniform across the chain).
      block:      :class:`repro.kernels.analog_plan.BlockMeta` static
                  attention+MLP glue geometry, or None.
    """

    stores: Tuple[WeightStore, ...]
    gain: jax.Array
    off: jax.Array
    schedule: tuple
    n_max: int
    chunk_rows: int
    deq: Optional[jax.Array] = None
    bias: Optional[jax.Array] = None
    enc: Optional[jax.Array] = None
    ln: Optional[jax.Array] = None
    block: Optional[tuple] = None

    @property
    def w_cat(self) -> jax.Array:
        """Derived [sum(k_pad), n_max] packed effective weights: each
        store's dequant view column-padded to the lane width (the static
        schedule carries each layer's true ``n``) and row-concatenated -
        bit-exact vs the legacy baked leaf."""
        blocks = [
            jnp.pad(s.w_eff, ((0, 0), (0, self.n_max - meta.n)))
            for s, meta in zip(self.stores, self.schedule)
        ]
        return jnp.concatenate(blocks, axis=0)

    @property
    def extras(self):
        """The float-glue operand tuple the kernel dispatch consumes
        (``None`` for a pure code-domain pack)."""
        if self.deq is None:
            return None
        return (self.deq, self.bias, self.enc, self.ln)


jax.tree_util.register_dataclass(
    MegakernelPack,
    data_fields=["stores", "gain", "off", "deq", "bias", "enc", "ln"],
    meta_fields=["schedule", "n_max", "chunk_rows", "block"],
)


@dataclasses.dataclass(frozen=True)
class BlockGlue:
    """The digital glue of one fused attention+MLP transformer block
    (frozen pytree), attached to an :class:`AnalogPlan` lowered by
    :func:`repro.exec.lower.lower_block`.

    Array fields (pytree leaves): the two RMSNorm scales (``ln1`` before
    QKV, ``ln2`` before the MLP) - calibration-free digital parameters
    that ride along so the per-layer fallback replay and the megakernel
    repack see the same leaves.

    Static fields: the attention/MLP geometry.  ``meta`` renders it as
    the hashable :class:`repro.kernels.analog_plan.BlockMeta` the kernel
    schedule consumes.
    """

    ln1: jax.Array
    ln2: jax.Array
    n_heads: int
    n_kv_heads: int
    head_dim: int
    seq: int
    rope_theta: float
    d_ff: int
    eps: float = 1e-5

    @property
    def meta(self):
        from repro.kernels.analog_plan import BlockMeta

        return BlockMeta(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, seq=self.seq,
            rope_theta=self.rope_theta, d_ff=self.d_ff, eps=self.eps,
        )


jax.tree_util.register_dataclass(
    BlockGlue,
    data_fields=["ln1", "ln2"],
    meta_fields=[
        "n_heads", "n_kv_heads", "head_dim", "seq", "rope_theta", "d_ff",
        "eps",
    ],
)


@dataclasses.dataclass(frozen=True)
class AnalogPlan:
    """A lowered stack of analog layers plus the execution config it was
    lowered for.  ``cfg`` is static: plans lowered with different modes
    (faithful/fast, pallas on/off, ...) compile to different programs.

    ``input_domain`` ("codes" | "float" | None) states what the plan's
    INITIAL input is - baked at lower time; None (manually-built plans)
    falls back to the legacy first-layer-epilogue inference in ``run``.
    ``mega`` is the optional megakernel packing: present iff the chain is
    megakernel-eligible (see :func:`repro.exec.lower.pack_megakernel` and
    :func:`repro.exec.lower.megakernel_ineligible_reason`), consumed by
    the whole-plan Pallas kernel in ``run``.  ``block`` is the optional
    attention+MLP glue of a plan lowered by
    :func:`repro.exec.lower.lower_block`.
    """

    layers: Tuple[LayerPlan, ...]
    cfg: AnalogConfig
    mega: Optional[MegakernelPack] = None
    input_domain: Optional[str] = None
    block: Optional[BlockGlue] = None

    def __len__(self) -> int:
        return len(self.layers)

    @property
    def expects_codes(self) -> bool:
        """Does the plan's first layer consume 5-bit codes?  Explicit
        ``input_domain`` when baked, else the legacy inference (first
        layer's own hand-off format)."""
        if self.input_domain is not None:
            return self.input_domain == INPUT_CODES
        return (
            len(self.layers) > 0
            and self.layers[0].epilogue == EPILOGUE_RELU_SHIFT
        )

    @property
    def expected_dispatches(self) -> int:
        """Analog dispatches ONE deterministic layer-by-layer replay of
        this plan issues (``key=None``), derived from static metadata
        alone.  This is the ground truth dispatch-count tests assert
        against: the ``ANALOG_DISPATCHES`` counter only bumps at trace
        time, so counting a cached-jit replay observes 0 and a counter-
        only assertion can pass vacuously.  (The megakernel route issues
        exactly 1 dispatch instead.)"""
        if self.block is not None:
            # a fused attention+MLP block's canonical replay IS the
            # megakernel: one dispatch for the whole block (the
            # per-layer replay costs 4; see run._run_block_fallback)
            return 1
        # one dispatch a layer: a signed-split pair is one fused pass
        return len(self.layers)


jax.tree_util.register_dataclass(
    AnalogPlan,
    data_fields=["layers", "mega", "block"],
    meta_fields=["cfg", "input_domain"],
)
