"""The measurement side of the HIL contract: an opaque analog device.

:class:`VirtualChip` wraps one seeded fixed-pattern instance plus a
temporal readout-noise stream behind the only interface real BSS-2
hardware exposes - *write weight codes, stream event codes, read back the
per-pass ADC results* (paper Fig. 4; each VMM pass integrates ONE 128-row
chunk, the SIMD CPU sees every pass's 8-bit readout before digital
accumulation).  Calibration routines (:mod:`repro.calib.routines`) close
the loop blind: they can call :meth:`VirtualChip.measure` as often as
they like but can never peek at the ground-truth deviations - exactly the
constraint the dedicated calibration paper (Weis et al. 2020) works
under.

The hidden pattern is sampled from the *logical* (K, N) tile grid with
the same generator the oracle bake uses (:mod:`repro.core.noise`), so a
chip built from a layer's params IS that layer's chip: a plan baked from
perfect knowledge of ``params["fpn"]`` and a plan baked from measurements
on ``VirtualChip.from_params(params)`` model the same physical device.
Being logical-shape-seeded also makes every measurement independent of
how the tile grid is sharded over a host mesh (tested property).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import noise as noise_lib
from repro.core.hw import BSS2
from repro.core.noise import NoiseConfig
from repro.core.quant import ANALOG_PRECISION


def measure_readout(
    w_code: jax.Array,
    a_code: jax.Array,
    *,
    gain: float,
    fpn: dict,
    drift: jax.Array,
    key: jax.Array,
    noise: NoiseConfig,
    k: int,
    n: int,
    chunk_rows: int,
    n_chunks: int,
) -> jax.Array:
    """The pure physics of one measurement pass: code clipping, hidden
    fixed-pattern weights, chunked accumulation, offsets + drift, readout
    noise from an already-folded ``key``, saturating ADC.

    Module-level and pure in (``fpn``, ``drift``, ``key``) so a
    :class:`~repro.fleet.placement.ChipFleet` can ``jax.vmap`` it over
    stacked per-chip hidden state and stay bit-identical to sequential
    :meth:`VirtualChip.measure` calls (which route through this same
    function).
    """
    w_code = jnp.clip(
        jnp.round(jnp.asarray(w_code, jnp.float32)),
        -float(BSS2.w_max), float(BSS2.w_max),
    )
    a_code = jnp.clip(
        jnp.round(jnp.asarray(a_code, jnp.float32)),
        0.0, float(BSS2.a_max),
    )
    w_eff = noise_lib.effective_weight(w_code, fpn)
    pad = n_chunks * chunk_rows - k
    if pad:
        w_eff = jnp.pad(w_eff, ((0, pad), (0, 0)))
        a_code = jnp.pad(
            a_code, [(0, 0)] * (a_code.ndim - 1) + [(0, pad)]
        )
    batch = a_code.shape[:-1]
    a_c = a_code.reshape(batch + (n_chunks, chunk_rows))
    w_c = w_eff.reshape(n_chunks, chunk_rows, n)
    v = jnp.einsum(
        "...ck,ckn->...cn", a_c, w_c, precision=ANALOG_PRECISION,
        preferred_element_type=jnp.float32,
    ) * gain
    off = fpn.get("chunk_offset")
    v = v + (drift if off is None else off + drift)
    if noise.readout_std > 0.0 and noise.mode != "none":
        v = v + noise.readout_std * jax.random.normal(
            key, v.shape, jnp.float32
        )
    return jnp.clip(
        jnp.round(v), float(BSS2.adc_min), float(BSS2.adc_max)
    )


class VirtualChip:
    """One analog device: hidden fixed pattern, noisy measurements only.

    Construction seeds the frozen per-chip deviations; ``measure`` is the
    sole data path out.  The readout-noise stream is deterministic given
    (key, call order), so a calibration run is reproducible end to end.
    """

    def __init__(
        self,
        key: jax.Array,
        k: int,
        n: int,
        *,
        noise: NoiseConfig = NoiseConfig(),
        chunk_rows: int = BSS2.signed_rows,
        fpn: Optional[dict] = None,
    ):
        self.k = int(k)
        self.n = int(n)
        self.chunk_rows = int(chunk_rows)
        self.n_chunks = -(-self.k // self.chunk_rows)
        self.noise = noise
        k_fp, k_ro = jax.random.split(jax.random.fold_in(key, 0xCA11B))
        # hidden state: calibration routines must go through measure()
        self._fpn = (
            fpn if fpn is not None
            else noise_lib.init_fixed_pattern(
                k_fp, self.k, self.n, self.n_chunks, noise
            )
        )
        self._drift = jnp.zeros((self.n_chunks, self.n), jnp.float32)
        self._key = k_ro
        self._measurements = 0
        self._dead = False

    @classmethod
    def from_params(
        cls,
        params: dict,
        key: jax.Array,
        *,
        noise: NoiseConfig = NoiseConfig(),
        chunk_rows: int = BSS2.signed_rows,
    ) -> "VirtualChip":
        """The chip a layer's parameters were initialized against: wraps
        ``params["fpn"]`` (the layer's frozen deviations) as the hidden
        state, so measuring this chip calibrates THAT layer's device.
        ``key`` seeds only the temporal readout stream."""
        k, n = params["w"].shape
        return cls(
            key, k, n, noise=noise, chunk_rows=chunk_rows,
            fpn=dict(params.get("fpn", {})),
        )

    # ------------------------------------------------------------- interface
    @property
    def measurements(self) -> int:
        """How many measure() calls this chip has served (cost accounting
        for calibration budgets)."""
        return self._measurements

    def measure(
        self,
        w_code: jax.Array,
        a_code: jax.Array,
        *,
        gain: float = 1.0,
    ) -> jax.Array:
        """One hardware measurement: write 6-bit weight codes, stream
        5-bit event codes, return the per-chunk 8-bit ADC readings.

        w_code: [K, N] synapse codes (clipped to the representable
                +-``w_max`` - the synapse memory cannot hold more).
        a_code: [..., K] event codes (rounded + clipped to [0, a_max] -
                pulse lengths are unsigned 5-bit).
        gain:   the requested analog amplification (CapMem setting).

        Returns [..., C, N]: every chunk pass's saturating ADC readout,
        including the hidden fixed-pattern gain/offset deviations, any
        accumulated offset drift, and fresh temporal readout noise for
        every pass of every batch row.  A killed chip (:meth:`kill`)
        still answers - rail-pinned at ``adc_min`` on every column, the
        way a dead analog array reads back.
        """
        w_code = jnp.asarray(w_code, jnp.float32)
        a_code = jnp.asarray(a_code, jnp.float32)
        if w_code.shape != (self.k, self.n):
            raise ValueError(
                f"w_code shape {w_code.shape} != chip grid "
                f"({self.k}, {self.n})"
            )
        if a_code.shape[-1] != self.k:
            raise ValueError(
                f"a_code feeds {a_code.shape[-1]} rows, chip has {self.k}"
            )
        self._measurements += 1
        if self._dead:
            shape = a_code.shape[:-1] + (self.n_chunks, self.n)
            return jnp.full(shape, float(BSS2.adc_min), jnp.float32)
        key = jax.random.fold_in(self._key, self._measurements)
        return measure_readout(
            w_code, a_code, gain=gain, fpn=self._fpn, drift=self._drift,
            key=key, noise=self.noise, k=self.k, n=self.n,
            chunk_rows=self.chunk_rows, n_chunks=self.n_chunks,
        )

    # ------------------------------------------------------------ simulation
    @property
    def dead(self) -> bool:
        return self._dead

    def kill(self) -> None:
        """Simulate a chip failure: every subsequent measurement reads
        back rail-pinned ``adc_min`` codes.  The fleet health monitor
        detects this through its probe path alone (the flag is hidden
        state like everything else)."""
        self._dead = True

    def apply_drift(self, key: jax.Array, std_lsb: float) -> None:
        """Simulate thermal ADC-offset drift: perturb the hidden offsets
        by ``std_lsb`` (LSB).  Gains are stable on this timescale - the
        drift monitor only ever refreshes offsets."""
        self._drift = self._drift + noise_lib.offset_drift(
            key, (self.n_chunks, self.n), std_lsb
        )

    def oracle(self) -> dict:
        """Ground truth, for TESTS AND VALIDATION ONLY - calibration
        routines must never call this (the real chip has no such port).

        Returns the hidden per-(chunk, column) gain table (each chunk's
        row-mean of the per-synapse gain map over its *real* rows - the
        best any column-wise measurement can recover) and the current
        per-(chunk, column) offsets including drift.
        """
        gmap = noise_lib.effective_weight(
            jnp.ones((self.k, self.n), jnp.float32), self._fpn
        )
        pad = self.n_chunks * self.chunk_rows - self.k
        rows = jnp.full((self.k,), 1.0, jnp.float32)
        if pad:
            gmap = jnp.pad(gmap, ((0, pad), (0, 0)))
            rows = jnp.pad(rows, (0, pad))
        gmap = gmap.reshape(self.n_chunks, self.chunk_rows, self.n)
        counts = rows.reshape(self.n_chunks, self.chunk_rows).sum(-1)
        gain_table = gmap.sum(axis=1) / counts[:, None]
        off = self._fpn.get("chunk_offset")
        off = self._drift if off is None else off + self._drift
        return {"gain_table": gain_table, "chunk_offset": off}
