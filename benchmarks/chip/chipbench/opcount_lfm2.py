"""Operations and bytes of configurations of kind ``lfm2``, counted from
shapes and from the routed rows the program reports (see
:mod:`chipbench.opcount` for the conventions: an analog VMM of ``M``
rows is ``2 * M * K * N`` operations; its bytes are the int8 weight
codes, one byte per input code, the fp32 tables and the fp32 outputs).

- ``analog_mvm`` (the dense signed-split kernel): per call, every conv
  layer's ``W_in`` (d -> 3d) and ``W_out`` (d -> d), the attention
  layer's q/k/v (counted as one d -> (nq + 2 nkv) layer: one read of the
  input codes) and ``W_o``, the dense layers' up, gate and down, at every
  row; the head at each row's last position only.
- ``expert_mvm`` (the grouped kernel of the held experts): ``2 * rows *
  K * N`` for each expert matrix over the routed rows (the program's
  count, not the padded tiles); bytes: every held expert's codes and
  tables once per MoE layer and call (a decode step's few rows may touch
  fewer experts), the routed rows' input codes and fp32 outputs.
- the whole step (``mfu``) adds causal attention (4 * nq * head_dim per
  (query, key) pair), the router (``2 * d * E`` a token), and the short
  convolution with its two gates (``2 * taps * d + 2 * d`` a token).
"""
from __future__ import annotations

from chipbench.opcount import Work, mvm_bytes, mvm_ops


def kinds(cfg: dict) -> list:
    return [("attn" if t == "full_attention" else "conv",
             "mlp" if i < cfg["num_dense_layers"] else "moe")
            for i, t in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def dense_shapes(cfg: dict) -> list:
    """(name, K, N) of the dense kernel's layers in one pass of every
    layer, the head excluded."""
    d = cfg["hidden_size"]
    nq = d
    nkv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    ff = cfg["intermediate_size"]
    out = []
    for i, (mixer, f) in enumerate(kinds(cfg)):
        if mixer == "conv":
            out += [(f"l{i}.in_proj", d, 3 * d), (f"l{i}.out_proj", d, d)]
        else:
            out += [(f"l{i}.qkv", d, nq + 2 * nkv), (f"l{i}.o", nq, d)]
        if f == "mlp":
            out += [(f"l{i}.up", d, ff), (f"l{i}.gate", d, ff),
                    (f"l{i}.down", ff, d)]
    return out


def expert_shapes(cfg: dict) -> list:
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return [("up", d, ff), ("gate", d, ff), ("down", ff, d)]


def moe_layers(cfg: dict) -> int:
    return sum(f == "moe" for _, f in kinds(cfg))


def token_macs(cfg: dict) -> int:
    """Multiply-accumulates of one token's dense analog layers and the
    head (what every token pays, experts aside)."""
    return (sum(k * n for _, k, n in dense_shapes(cfg))
            + cfg["hidden_size"] * cfg["vocab_size"])


def step(cfg: dict, batch: int, q_len: int, start: int, held_rows: int,
         peak: dict, dense: Work, experts: Work, total: Work) -> None:
    """Count one prefill (``start = 0``, ``q_len`` prompt tokens) or one
    decode step (``q_len = 1`` at position ``start``) of ``batch`` rows
    that routed ``held_rows`` (token, held expert) pairs over every MoE
    layer."""
    m = batch * q_len
    d = cfg["hidden_size"]
    ops = 0.0
    for _, k, n in dense_shapes(cfg):
        o = mvm_ops(m, k, n)
        dense.add(o, mvm_bytes(m, k, n), peak)
        ops += o
    o = mvm_ops(batch, d, cfg["vocab_size"])
    dense.add(o, mvm_bytes(batch, d, cfg["vocab_size"]), peak)
    ops += o
    n_moe = moe_layers(cfg)
    if n_moe:
        h = cfg["num_experts"]
        o = sum(mvm_ops(held_rows, k, n) for _, k, n in expert_shapes(cfg))
        nbytes = n_moe * sum(h * mvm_bytes(0, k, n)
                             for _, k, n in expert_shapes(cfg))
        nbytes += sum(held_rows * k + 4.0 * held_rows * n
                      for _, k, n in expert_shapes(cfg))
        experts.add(o, nbytes, peak)
        ops += o
    # attention: queries start..start+q_len-1 over keys 0..position
    keys = sum(p + 1 for p in range(start, start + q_len))
    n_attn = sum(mx == "attn" for mx, _ in kinds(cfg))
    ops += batch * n_attn * 4.0 * d * keys
    # router, short convolution and gates
    ops += m * n_moe * 2.0 * d * cfg["num_experts_published"]
    n_conv = sum(mx == "conv" for mx, _ in kinds(cfg))
    ops += m * n_conv * (2.0 * cfg["conv_L_cache"] * d + 2.0 * d)
    total.ops += ops
    total.calls += 1
