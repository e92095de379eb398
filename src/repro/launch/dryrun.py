import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (architecture x input-shape)
# cell on the production meshes and extract the roofline terms.
#
# The XLA_FLAGS line above MUST run before any jax import (jax locks the
# device count on first init), hence no module docstring above it.
#
# Usage:
#     PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b \
#         --shape train_4k --mesh single --mode digital
#     PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
#
# Per cell this writes experiments/dryrun/<cell>.json containing
# memory_analysis, cost_analysis, and the parsed per-collective byte counts
# (the inputs to EXPERIMENTS.md §Dry-run and §Roofline).

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import RunConfig, SHAPES
from repro.core.analog import AnalogConfig
from repro.core.hw import V5E_KIND
from repro.core.noise import NoiseConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.obs import trace as obs_trace
from repro.serve import serve_step as SS
from repro.train import train_step as TS

OUT_DIR = "experiments/dryrun"


# ------------------------------------------------------------ input specs
def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def input_specs(arch: str, shape: str, run: RunConfig,
                kv_dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins for every input of the lowered step
    (weak-type-correct, shardable, no device allocation)."""
    cfg = configs.get_arch(arch)
    sh = SHAPES[shape]
    b, s = sh.global_batch, sh.seq_len
    i32 = jnp.int32

    def tokens_or_embeds(batch, seqlen):
        if cfg.embed_inputs:
            return {"tokens": jax.ShapeDtypeStruct((batch, seqlen), i32)}
        return {"embeds": jax.ShapeDtypeStruct(
            (batch, seqlen, cfg.d_model), jnp.bfloat16)}

    if sh.kind == "train":
        state = jax.eval_shape(
            lambda k: TS.init_state(k, cfg, run), jax.random.PRNGKey(0)
        )
        batch = {
            **tokens_or_embeds(b, s),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return cfg, sh, (state, batch, rng)

    params = jax.eval_shape(lambda k: T.lm_init(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: T.init_lm_cache(cfg, b, s, dtype=kv_dtype)
    )
    if sh.kind == "prefill":
        batch = tokens_or_embeds(b, s)
        return cfg, sh, (params, batch, cache)
    # decode: one new token against a seq_len-deep cache
    tok = (
        jax.ShapeDtypeStruct((b, 1), i32)
        if cfg.embed_inputs
        else jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)
    )
    return cfg, sh, (params, tok, cache)


# -------------------------------------------------------- collective parse
_COLL_RE = re.compile(
    r"(\w+)\[([\d,]*)\][^=]*\b"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2,
}
# bytes actually moved per device, as a multiple of the result buffer
_COLL_FACTOR = {
    "all-reduce": 2.0,          # reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device collective bytes from post-SPMD HLO."""
    per_op: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "fusion" in line and "calls=" in line:
            continue
        m = _COLL_RE.search(line)
        if not m or "-start" in line and "-done" in line:
            continue
        # only count op definitions, not operands referencing them
        stripped = line.strip()
        if not (
            stripped.startswith("%")
            or stripped.startswith("ROOT")
            or re.match(r"^[\w.\-]+ = ", stripped)
        ):
            continue
        op = m.group(3)
        if f" {op}(" not in line and f" {op}-start(" not in line:
            continue
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes = n * _DTYPE_BYTES[dtype] * _COLL_FACTOR[op]
        per_op[op] = per_op.get(op, 0.0) + nbytes
        counts[op] = counts.get(op, 0) + 1
    return {
        "bytes_per_op": per_op,
        "counts": counts,
        "total_bytes": sum(per_op.values()),
    }


# ------------------------------------------------------------------ runner
def run_cell(arch: str, shape: str, mesh_kind: str, mode: str,
             out_dir: str = OUT_DIR, tag: str = "", signed: str = "split",
             **run_overrides) -> dict:
    acfg = (
        AnalogConfig(mode=mode, noise=NoiseConfig(mode="rank1"),
                     signed_input=signed)
        if mode != "digital"
        else RunConfig().analog
    )
    # bf16-param archs (the 400B MoE) also keep Adam moments in bf16 so the
    # 256-chip pod fits 16 GB HBM/chip (DESIGN.md §6.7)
    optim_dtype = run_overrides.pop("optim_dtype", None) or (
        "bfloat16"
        if configs.get_arch(arch).param_dtype == "bfloat16"
        else "float32"
    )
    kv_dtype = jnp.int8 if run_overrides.pop("kv_int8", False) \
        else jnp.bfloat16
    run = RunConfig(analog=acfg, optim_dtype=optim_dtype, **run_overrides)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    with shd.use_mesh(mesh, rules=shd.rules_for(run)):
        cfg, sh, args = input_specs(arch, shape, run, kv_dtype)
        if sh.kind == "train":
            step = TS.make_train_step(
                cfg, run, abstract_state=args[0], abstract_batch=args[1]
            )
        elif sh.kind == "prefill":
            step, _ = SS.make_serve_steps(
                cfg, run, abstract_params=args[0], abstract_cache=args[2]
            )
        else:
            _, step = SS.make_serve_steps(
                cfg, run, abstract_params=args[0], abstract_cache=args[2]
            )
        lowered = step.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "mode": mode,
        "kind": sh.kind,
        "n_devices": mesh.devices.size,
        # the chip the CPU-lowered program is sized for (roofline peaks)
        "target_device_kind": V5E_KIND,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            k: getattr(mem, k, None)
            for k in (
                "temp_size_in_bytes",
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        },
        "cost": {
            k: cost.get(k)
            for k in ("flops", "bytes accessed", "transcendentals")
            if isinstance(cost, dict)
        } if isinstance(cost, dict) else {"raw": str(cost)},
        "collectives": coll,
        "hlo_lines": hlo.count("\n"),
    }
    result["tag"] = tag
    os.makedirs(out_dir, exist_ok=True)
    cell = f"{arch}__{shape}__{mesh_kind}__{mode}"
    if tag:
        cell += "__" + tag
    with open(os.path.join(out_dir, cell + ".json"), "w") as f:
        json.dump(result, f, indent=2, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--mode", default="digital",
                    choices=["digital", "analog_faithful", "analog_fast"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--tag", default="", help="suffix for variant artifacts")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-seq-sp", action="store_true")
    ap.add_argument("--moe-dispatch", default="shard_map",
                    choices=["gspmd_ep", "shard_map"])
    ap.add_argument("--optim-bf16", action="store_true")
    ap.add_argument("--signed", default="split",
                    choices=["split", "offset", "none"])
    ap.add_argument("--kv-int8", action="store_true")
    args = ap.parse_args()
    overrides = dict(fsdp=not args.no_fsdp, seq_sp=not args.no_seq_sp,
                     moe_dispatch=args.moe_dispatch, kv_int8=args.kv_int8)
    if args.optim_bf16:
        overrides["optim_dtype"] = "bfloat16"

    if args.all:
        cells = configs.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch, shape in cells:
        for mesh_kind in meshes:
            tag = f"{arch} x {shape} x {mesh_kind} x {args.mode}"
            try:
                r = run_cell(arch, shape, mesh_kind, args.mode, args.out,
                             tag=args.tag, signed=args.signed, **overrides)
                obs_trace.log(
                    f"[OK] {tag}: compile={r['compile_s']}s "
                    f"args/dev={r['memory']['argument_size_in_bytes']/2**30:.2f}GiB "
                    f"temp/dev={r['memory']['temp_size_in_bytes']/2**30:.2f}GiB "
                    f"flops={r['cost'].get('flops')} "
                    f"coll={r['collectives']['total_bytes']:.3g}B",
                )
            except Exception as e:  # noqa: BLE001
                failures.append(tag)
                obs_trace.log(f"[FAIL] {tag}: {e}")
                traceback.print_exc()
    if failures:
        obs_trace.log(f"\n{len(failures)} FAILURES:\n" + "\n".join(failures))
        raise SystemExit(1)
    obs_trace.log("\nall cells compiled")


if __name__ == "__main__":
    main()
