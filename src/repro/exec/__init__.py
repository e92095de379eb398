"""Execution subsystem: compile-once/run-many plans for analog layers.

    plan  - AnalogPlan / LayerPlan frozen pytrees (the compiled schedule)
    lower - lower(params, AnalogConfig) -> AnalogPlan  (weight quantize,
            fixed-pattern bake, chunk padding, calibration - done once)
    run   - run(plan, x) -> y  (the per-call hot path: activation
            encoding, fused signed-split dispatch, ADC epilogues)

See the module docstrings for the lifecycle contract (train re-lowers
each step; serve/eval lower once and replay).
"""
from repro.exec.lower import (  # noqa: F401
    layer_with_offsets,
    lower,
    lower_batch_concat,
    lower_block,
    lower_expert_stack,
    lower_fused,
    lower_layer,
    lower_stack,
    lowering_count,
    megakernel_ineligible_reason,
    pack_megakernel,
    plan_with_offsets,
    prelower_tree,
    reset_lowering_count,
)
from repro.exec.plan import (  # noqa: F401
    EPILOGUE_NONE,
    EPILOGUE_RELU_SHIFT,
    GROUP_BATCH_CONCAT,
    GROUP_COLUMN_CONCAT,
    GROUP_EXPERT_STACK,
    GROUP_KINDS,
    INPUT_CODES,
    INPUT_FLOAT,
    AnalogPlan,
    GroupPlan,
    LayerPlan,
    BlockGlue,
    MegakernelPack,
    default_shift,
    find_group,
)
from repro.exec.run import (  # noqa: F401
    dispatch_count,
    megakernel_fallback_reason,
    reset_dispatch_count,
    run,
    run_batch_concat,
    run_expert_stack,
    run_group,
    run_layer,
    run_layers,
)
