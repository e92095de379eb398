"""Batched serving with the analog backend: prefill + decode engine.

    PYTHONPATH=src python examples/serve_batch.py --arch stablelm-3b \
        --requests 12 --max-new 16 [--mode analog_fast] [--mesh]

Demonstrates the inference-engine substrate (the `decode_*` dry-run cells
at smoke scale): request batching, left-padded prefill, per-sequence
stopping, greedy/categorical sampling - with the model's parameter
matmuls on emulated analog tiles if requested.  The engine goes through
the `repro.api` front door: the model is compiled ONCE (attention QKV
fused into one dispatch group) and the jitted steps replay the baked
plans - also under an active mesh (``--mesh``), where the plan leaves
shard by the same logical axes as the weights they were baked from.
"""
import argparse
import contextlib

import numpy as np

from repro import configs, obs
from repro.configs.base import RunConfig
from repro.core.analog import AnalogConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

import jax


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--mode", default="digital",
                    choices=["digital", "analog_faithful", "analog_fast"])
    ap.add_argument("--mesh", action="store_true",
                    help="serve under a (data, model) host mesh with "
                         "sharded pre-lowered plans")
    a = ap.parse_args(argv)

    cfg = configs.get_smoke(a.arch)
    if not cfg.embed_inputs:
        raise SystemExit(f"{a.arch} backbone takes frontend embeddings - "
                         "pick a token-input arch for this example")
    run = RunConfig(analog=AnalogConfig(mode=a.mode)) if a.mode != "digital" \
        else RunConfig()
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    mesh_ctx = contextlib.nullcontext()
    if a.mesh:
        n = len(jax.devices())
        mesh_ctx = shd.use_mesh(make_mesh((n, 1), ("data", "model")))
    rng = np.random.default_rng(0)
    reqs = [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size, rng.integers(4, 12)),
                max_new_tokens=a.max_new)
        for i in range(a.requests)
    ]
    obs.reset_metrics()
    with obs.collect("serve-batch") as tr, mesh_ctx:
        engine = ServeEngine(cfg, run, params, batch_size=a.batch,
                             max_len=128)
        with obs.span("serve.all") as sp:
            done = engine.serve(reqs)
        dt = sp.dur_us / 1e6
    total_new = sum(len(r.output) for r in done)
    dev = jax.devices()[0]
    print(f"arch={a.arch} mode={a.mode}: served {len(done)} requests, "
          f"{total_new} tokens in {dt:.1f}s "
          f"({total_new / dt:.1f} tok/s on {len(jax.devices())}x "
          f"{dev.platform} {dev.device_kind})")
    for r in done[:4]:
        print(f"  req {r.uid}: prompt[:6]={r.prompt[:6].tolist()} -> "
              f"out[:8]={r.output[:8].tolist()}")
    print("\n=== end-of-run obs report ===")
    print(obs.report.render(
        obs.report.records_of(tr, obs.metrics.registry())
    ))


if __name__ == "__main__":
    main()
