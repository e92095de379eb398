"""The paper's ECG A-fib classifier (Fig. 6) on the analog backend.

On-chip arrangement reproduced (DESIGN.md §2 for the shape reconstruction):
- conv layer: 64 taps x 2 channels = 128 signed rows, replicated 32 times
  across columns -> 32 positions x 8 output channels = 256 columns of the
  upper synapse array half; implemented as im2col + one analog matmul,
  which is *exactly* the hardware layout (weight replicas = tile columns).
- fc1: 256 -> 123, split into two 128-row chunks evaluated side by side;
  our per-chunk saturating accumulation reproduces this natively.
- fc2: 123 -> 10, followed by average pooling of 5 neurons per class
  (noise reduction; trained with max pooling instead, §III-B).
- ReLUs happen at the ADC (offset-aligned readout) followed by the 5-bit
  right-shift requantization - both emulated bit-exact.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.energy import LayerWork
from repro.core.noise import NoiseConfig


@dataclasses.dataclass(frozen=True)
class ECGConfig:
    in_channels: int = 2
    in_len: int = 126          # preprocessed samples (4033 raw / 32-pool)
    conv_taps: int = 64
    conv_stride: int = 2
    conv_channels: int = 8
    hidden: int = 123
    classes: int = 2
    class_copies: int = 5      # 10 output neurons -> 2 classes
    # The ECG reproduction uses the FULL per-synapse fixed-pattern map
    # (core.noise docstring; the rank1 factorization is the LM-scale
    # memory compromise) - requested EXPLICITLY here, not silently
    # upgraded by ecg_init.  Pass a different NoiseConfig to override.
    noise: NoiseConfig = dataclasses.field(
        default_factory=lambda: NoiseConfig(mode="full")
    )

    @property
    def conv_positions(self) -> int:
        return (self.in_len - self.conv_taps) // self.conv_stride + 1

    @property
    def conv_cols(self) -> int:
        return self.conv_positions * self.conv_channels

    def layer_works(self) -> list[LayerWork]:
        return [
            LayerWork(k=self.conv_taps * self.in_channels, n=self.conv_cols),
            LayerWork(k=self.conv_cols, n=self.hidden),
            LayerWork(k=self.hidden, n=self.classes * self.class_copies),
        ]

    def total_ops(self) -> int:
        return sum(2 * lw.macs for lw in self.layer_works())


def ecg_init(key, cfg: ECGConfig = ECGConfig()):
    ks = jax.random.split(key, 3)
    nz = cfg.noise       # the config states its mode (default: full map)
    return {
        "conv": analog_linear_init(
            ks[0], cfg.conv_taps * cfg.in_channels, cfg.conv_channels,
            noise=nz,
        ),
        "fc1": analog_linear_init(ks[1], cfg.conv_cols, cfg.hidden, noise=nz),
        "fc2": analog_linear_init(
            ks[2], cfg.hidden, cfg.classes * cfg.class_copies, noise=nz
        ),
    }


def _im2col(x, taps, stride):
    """x: [B, C, T] -> [B, positions, taps * C] (the event-address lookup
    table of the FPGA vector generator, §II-C)."""
    b, c, t = x.shape
    npos = (t - taps) // stride + 1
    idx = jnp.arange(npos)[:, None] * stride + jnp.arange(taps)[None, :]
    with jax.named_scope("ecg.im2col"):
        cols = x[:, :, idx]                  # [B, C, npos, taps]
        return cols.transpose(0, 2, 3, 1).reshape(b, npos, taps * c)


def ecg_module_spec(cfg: ECGConfig = ECGConfig(), *,
                    epilogue: str = "none"):
    """Declare the Fig.-6 CDNN once for the api front door: a stack spec
    whose compiled form runs conv->fc1->fc2 as ONE analog program.

    ``epilogue`` selects the inter-layer hand-off:
    - "none": float glue - dequantize, ReLU, re-quantize at the next layer
      (the pre-plan module-by-module semantics, bit-compatible).
    - "relu_shift": the hardware chain of paper §II-A - ReLU at the ADC +
      right-shift requantization to 5-bit codes, so the whole stack runs
      in the code domain with no float glue (and, with
      ``acfg.use_pallas`` + ``acfg.fused_epilogue``, the epilogue is
      emitted inside the Pallas kernel).  The code-domain chain also
      declares ``input_domain="codes"`` (the preprocessed 5-bit input
      activations feed the conv directly) and is therefore megakernel-
      eligible: the compiled model replays conv->fc1->fc2 as ONE analog
      dispatch (``model.apply(x, megakernel="auto")``, the default) - the
      paper's single-program inference.  The "none" float-glue chain
      keeps the legacy float input treatment (re-quantized on entry).
    """
    from repro import api

    def _apply(model, x, *, train: bool = False, key=None,
               megakernel="auto"):
        cols = _im2col(x, cfg.conv_taps, cfg.conv_stride)
        with jax.named_scope("ecg.analog_chain"):
            out = model.run_stack(cols, key=key, megakernel=megakernel)
        return _pool_class_copies(out, cfg, train)

    return api.ModuleSpec(
        name="ecg_cdnn",
        kind="stack",
        apply_fn=_apply,
        input_domain="codes" if epilogue == "relu_shift" else "float",
        layers=(
            api.LayerSpec("conv", cfg.conv_taps * cfg.in_channels,
                          cfg.conv_channels, signed_input="none",
                          epilogue=epilogue, flatten_out=True),
            api.LayerSpec("fc1", cfg.conv_cols, cfg.hidden,
                          signed_input="none", epilogue=epilogue),
            api.LayerSpec("fc2", cfg.hidden,
                          cfg.classes * cfg.class_copies,
                          signed_input="none"),
        ),
    )


def ecg_lower(params, acfg: AnalogConfig, cfg: ECGConfig = ECGConfig(), *,
              epilogue: str = "none"):
    """DEPRECATED: use ``repro.api.compile(ecg_module_spec(cfg), params,
    acfg)`` - ``CompiledModel.lower()`` returns the same AnalogPlan,
    ``CompiledModel.apply`` replaces :func:`ecg_apply_plan`.  Bit-exact
    shim over the api front door (ISSUE 2)."""
    import warnings

    warnings.warn(
        "ecg_lower is deprecated; use repro.api.compile with "
        "ecg_module_spec",
        DeprecationWarning, stacklevel=2,
    )
    from repro import api

    return api.compile(
        ecg_module_spec(cfg, epilogue=epilogue), params, acfg
    ).lower()


def _pool_class_copies(out, cfg: ECGConfig, train: bool):
    """§III-B: max pooling over the class-copy neurons during training
    (robustness); average pooling at inference (noise averaging)."""
    with jax.named_scope("ecg.class_pool"):
        out = out.reshape(out.shape[0], cfg.classes, cfg.class_copies)
        return out.max(axis=-1) if train else out.mean(axis=-1)


def ecg_apply_plan(plan, x, cfg: ECGConfig = ECGConfig(), *,
                   train: bool = False, key=None):
    """Run a lowered ECG plan: x [B, C, T] codes -> logits [B, classes].
    Lower once (per weight update), run many - the serve/eval hot path."""
    from repro.exec.run import run as run_plan

    cols = _im2col(x, cfg.conv_taps, cfg.conv_stride)
    with jax.named_scope("ecg.analog_chain"):
        out = run_plan(plan, cols, key=key)
    return _pool_class_copies(out, cfg, train)


def ecg_apply(params, x, acfg: AnalogConfig, cfg: ECGConfig = ECGConfig(), *,
              train: bool = False, key=None, epilogue: str = "none",
              calibration=None):
    """x: [B, C, T] preprocessed 5-bit activations (integer-valued float).

    Returns logits [B, classes].  Compiles through the api front door and
    runs (training re-compiles every call, which is exactly the HIL
    contract; inference call sites should ``api.compile`` once and replay
    ``CompiledModel.apply``).  ``epilogue`` selects the inter-layer chain
    (float glue vs the code-domain relu_shift hand-off - see
    :func:`ecg_module_spec`); ``calibration`` bakes a measured
    CalibrationSnapshot instead of the oracle fixed pattern.
    """
    from repro import api

    model = api.compile(ecg_module_spec(cfg, epilogue=epilogue), params,
                        acfg, calibration=calibration)
    return model.apply(x, train=train, key=key)


def ecg_loss(params, x, labels, acfg, cfg: ECGConfig = ECGConfig(),
             key=None, *, epilogue: str = "none", calibration=None):
    logits = ecg_apply(params, x, acfg, cfg, train=True, key=key,
                       epilogue=epilogue, calibration=calibration)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    acc = (logits.argmax(-1) == labels).mean()
    return nll, {"acc": acc}
