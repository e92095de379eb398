"""Ahead-of-time compiles of the main path's Pallas kernels for one TPU
v5e chip that is described, not attached.

Interpret mode (every other kernel test) runs the kernel bodies as jnp and
accepts layouts the TPU compiler (Mosaic) refuses: blocks that break the
(8, 128) tiling, in-kernel reshapes that relabel sublanes as lanes.  These
tests run the real compiler at the shapes the chip runs - the ECG chain at
its published shape, stablelm-3b's QKV and down projections - and check
that each kernel lowers to a ``tpu_custom_call``.

The topology is described inside a fixture: only one process may load the
TPU library, so no module-import-time code touches it.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the patterns by which the chip benchmark's trace readers find the
# pre-processing kernel (``maxmin_pool_roofline``) and the dense analog
# kernels (``analog_mvm_roofline``)
from benchmarks.chip.chipbench.tracing import ANALOG_MVM, MAXMIN_POOL
from repro.kernels.analog_mvm import analog_mvm_pallas, analog_mvm_split_pallas
from repro.kernels.analog_plan import analog_plan_pallas, default_block_b
from repro.kernels.preproc import maxmin_pool_2d_pallas, preprocess_pallas


@pytest.fixture(scope="module")
def topo():
    # the TPU library logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_calls(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


# (rows, K, N): ECG fc1 at B=64; stablelm-3b QKV and down at 4 x 64 tokens
MVM_SHAPES = {
    "ecg_fc1": (64, 256, 123),
    "stablelm_qkv": (256, 2560, 7680),
    "stablelm_down": (256, 6912, 2560),
}


@pytest.mark.parametrize("split", [False, True], ids=["single", "split"])
@pytest.mark.parametrize("shape", list(MVM_SHAPES))
def test_analog_mvm_compiles(one_chip, shape, split):
    m, k, n = MVM_SHAPES[shape]
    c = k // 128
    if split:
        hlo = _hlo(analog_mvm_split_pallas, one_chip,
                   (m, k), (m, k), (k, n), (n,), (c, n))
    else:
        hlo = _hlo(analog_mvm_pallas, one_chip, (m, k), (k, n), (n,), (c, n))
    assert _kernel_calls(hlo) == 1


def _kernel_name(hlo: str) -> str:
    (call,) = [ln for ln in hlo.split("\nENTRY ", 1)[1].splitlines()
               if "tpu_custom_call" in ln]
    return call.strip().removeprefix("ROOT ").split(" = ")[0]


# (rows, K, N): LFM2-8B-A1B's in_proj over a 4 x 1024-token prefill and
# its 65,536-token head at a 4-row decode step
LFM2_SPLIT_SHAPES = {
    "in_proj_prefill": (4096, 2048, 6144),
    "head_decode": (4, 2048, 65536),
}


@pytest.mark.parametrize("shape", list(LFM2_SPLIT_SHAPES))
def test_lfm2_split_kernel_compiles(one_chip, shape):
    """The dense signed-split kernel at the ``lfm2-prefill1k`` cell's
    shapes, its three-pass contraction included, named as the chip
    benchmark's ``analog_mvm_roofline`` reader finds it."""
    m, k, n = LFM2_SPLIT_SHAPES[shape]
    hlo = _hlo(analog_mvm_split_pallas, one_chip,
               (m, k), (m, k), (k, n), (n,), (k // 128, n))
    assert _kernel_calls(hlo) == 1
    name = _kernel_name(hlo)
    assert ANALOG_MVM.match(name), name


@pytest.fixture(scope="module")
def ecg_pack():
    """The packed ECG megakernel operands at the published shape (built
    on the CPU: only their shapes go to the compiler)."""
    from repro import api
    from repro.core.analog import AnalogConfig
    from repro.models.ecg import ECGConfig, ecg_init, ecg_module_spec

    cfg = ECGConfig()
    model = api.compile(ecg_module_spec(cfg, epilogue="relu_shift"),
                        ecg_init(jax.random.PRNGKey(0), cfg),
                        AnalogConfig(use_pallas=True))
    plan = model.lower()
    assert plan.mega is not None and plan.mega.extras is None
    return plan


@pytest.mark.parametrize("batch", [1, 256])
def test_ecg_megakernel_compiles(one_chip, ecg_pack, batch):
    mega = ecg_pack.mega
    m0 = mega.schedule[0].m_mult
    fn = functools.partial(
        analog_plan_pallas, schedule=mega.schedule,
        chunk_rows=mega.chunk_rows, block_b=default_block_b(batch, m0),
    )
    hlo = _hlo(fn, one_chip, (batch * m0, ecg_pack.layers[0].k_pad),
               mega.w_cat.shape, mega.gain.shape, mega.off.shape)
    assert _kernel_calls(hlo) == 1


def test_maxmin_pool_compiles(one_chip):
    # 256 two-channel ECG windows: 4032 derivative samples per channel
    hlo = _hlo(maxmin_pool_2d_pallas, one_chip, (512, 4032))
    assert _kernel_calls(hlo) == 1


@pytest.mark.parametrize("batch", [6426, 1], ids=["holter", "window"])
def test_fused_preprocess_reads_raw_samples_as_they_arrive(one_chip, batch):
    """A 24-hour recording (6,426 two-channel windows of 4,033 samples)
    and one window: one kernel, named for the trace reader, takes the raw
    parameter itself, and no copy, pad or transpose touches it first."""
    hlo = _hlo(functools.partial(preprocess_pallas, window=32,
                                 quant_shift=4, interpret=False),
               one_chip, (batch, 2, 4033))
    assert _kernel_calls(hlo) == 1
    entry = hlo.split("\nENTRY ", 1)[1]
    raw = re.search(r"(%[\w.]+) = f32\[\d+,2,4033\]\S* parameter\(0\)",
                    entry).group(1)
    (call,) = [ln for ln in entry.splitlines() if "tpu_custom_call" in ln]
    name = call.strip().removeprefix("ROOT ").split(" = ")[0]
    assert MAXMIN_POOL.match(name), name
    assert f"custom-call({raw})" in call
    relayout = re.compile(r" (copy|pad|transpose)\(([^)]*)\)")
    for line in entry.splitlines():
        for _, operands in relayout.findall(line):
            assert raw not in re.split(r",\s*", operands), line


# (rows, K, N, block_m): LFM2-8B-A1B's held experts (8 of 32, width 1792)
# over a 4 x 1024-token prefill's worst case (every pair held, a partial
# tile per expert) and a 4-row decode step
EXPERT_SHAPES = {
    "prefill_up": (136 * 128, 2048, 1792, 128),
    "prefill_down": (136 * 128, 1792, 2048, 128),
    "decode_up": (10 * 8, 2048, 1792, 8),
}


@pytest.mark.parametrize("shape", list(EXPERT_SHAPES))
def test_expert_mvm_compiles(one_chip, shape):
    """The grouped held-expert kernel, named as the chip benchmark's
    ``expert_mvm_roofline`` reader finds it."""
    import pathlib
    import sys

    from repro.kernels.analog_mvm import expert_mvm_pallas

    bench = pathlib.Path(__file__).resolve().parents[1] / "benchmarks/chip"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))     # the readers import chipbench
    from chipbench import spec

    r, k, n, bm = EXPERT_SHAPES[shape]
    e, c, g = 8, k // 128, r // bm
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((r, k), (r, k), (e, k, n), (e, n), (e, c, n))]
    args += [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
             for s in ((g,), (1,))]
    fn = functools.partial(expert_mvm_pallas, block_m=bm)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert _kernel_calls(hlo) == 1
    name = _kernel_name(hlo)
    reader = spec.reader("expert_mvm_roofline.lfm2_prefill1k")
    assert reader.EXPERT_MVM.match(name), name
