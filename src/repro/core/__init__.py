"""Core analog-inference substrate: the paper's contribution as composable
JAX operators (quantizers, noise models, saturating analog matmul, tiling,
energy model)."""
from repro.core.analog import (  # noqa: F401
    DIGITAL,
    AnalogConfig,
    analog_linear_apply,
    analog_linear_init,
    analog_matmul,
    calibrate,
)
from repro.core.hw import BSS2, BSS2Spec, TPUSpec, tpu_spec  # noqa: F401
from repro.core.noise import NOISELESS, NoiseConfig  # noqa: F401
from repro.core.partition import TileGrid, plan_model, plan_tiles  # noqa: F401
