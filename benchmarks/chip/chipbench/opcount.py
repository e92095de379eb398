"""Operations and bytes the algorithm needs, counted from shapes.

Whatever implements the work, these counts stay the same, so a share of a
peak computed from them cannot rise by doing more work:

- an analog vector-matrix multiplication of ``M`` rows, ``K`` inputs and
  ``N`` outputs needs ``2 * M * K * N`` operations: the two passes of a
  signed-split input count once, as one signed product;
- its bytes are the int8 weight codes (``K * N``), one byte per input
  activation code (``M * K``: a 5-bit magnitude and its sign), the gain
  and offset tables in fp32 (per-column weight LSB and gain, per-row
  gain, per-chunk offsets, or the whole per-synapse gain map where the
  configuration has one), and the fp32 outputs (``M * N * 4``);
- the max-min pooling of the ECG pre-processing reads the raw fp32
  samples once and writes one fp32 value per 32 samples.

The share of a roofline is the least time the chip could take, the larger
of operations over the int8 peak and bytes over the memory bandwidth
(analog arithmetic is exact in int8: 5-bit by 6-bit codes), divided by
the time measured.
"""
from __future__ import annotations

import dataclasses

CHUNK_ROWS = 128


def chunks(k: int, chunk_rows: int = CHUNK_ROWS) -> int:
    return -(-k // chunk_rows)


@dataclasses.dataclass
class Work:
    """Operations and least time of a set of calls of one kernel."""

    ops: float = 0.0
    min_s: float = 0.0
    calls: int = 0

    def add(self, ops: float, nbytes: float, peak: dict, n: int = 1):
        self.ops += n * ops
        self.min_s += n * max(ops / peak["int8_ops_per_s"],
                              nbytes / peak["hbm_bytes_per_s"])
        self.calls += n


def mvm_ops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def mvm_bytes(m: int, k: int, n: int, *, gain_map: bool = False,
              chunk_rows: int = CHUNK_ROWS) -> float:
    tables = 4 * (2 * n + chunks(k, chunk_rows) * n)   # LSB, gain, offsets
    tables += 4 * (k * n if gain_map else k + n)       # synapse/row+col gain
    return k * n + m * k + tables + 4.0 * m * n


# ------------------------------------------------------------------- ECG
def ecg_layers(cfg: dict) -> list:
    """(rows per window, K, N) of the conv, fc1 and fc2 analog layers."""
    positions = (cfg["in_len"] - cfg["conv_taps"]) // cfg["conv_stride"] + 1
    conv_cols = positions * cfg["conv_channels"]
    return [
        (positions, cfg["conv_taps"] * cfg["in_channels"],
         cfg["conv_channels"]),
        (1, conv_cols, cfg["hidden"]),
        (1, cfg["hidden"], cfg["classes"] * cfg["class_copies"]),
    ]


def ecg_window_ops(cfg: dict) -> int:
    """Operations of one window through the three analog layers (the paper
    gives 132 k; ``ECGConfig().total_ops()`` counts 130,972)."""
    return int(sum(mvm_ops(r, k, n) for r, k, n in ecg_layers(cfg)))


def ecg_chain_bytes(cfg: dict, windows: int) -> float:
    """One call of the whole-chain kernel over ``windows`` windows: the
    input codes once (not the im2col copies), every layer's codes and
    tables once, the final fp32 outputs."""
    layers = ecg_layers(cfg)
    gain_map = cfg["noise"]["mode"] == "full"
    total = windows * cfg["in_channels"] * cfg["in_len"]      # input codes
    for _, k, n in layers:
        total += mvm_bytes(0, k, n, gain_map=gain_map)
    total += 4.0 * windows * layers[-1][2]
    return total


def maxmin_bytes(windows: int, channels: int, samples: int,
                 pool: int = 32) -> float:
    """The pooling kernel reads the derivative (``samples - 1`` values per
    channel, cut to whole pools) and writes one value per pool."""
    t = ((samples - 1) // pool) * pool
    return 4.0 * windows * channels * (t + t // pool)


def maxmin_ops(windows: int, channels: int, samples: int,
               pool: int = 32) -> float:
    """A max, a min and a difference per pooled sample."""
    t = ((samples - 1) // pool) * pool
    return 2.0 * windows * channels * t


# -------------------------------------------------------------------- LM
def lm_layer_shapes(cfg: dict) -> list:
    """(name, K, N) of the analog layers of one transformer layer."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    return [("qkv", d, d + 2 * kv), ("o", d, d), ("up", d, ff),
            ("gate", d, ff), ("down", ff, d)]


def lm_head_shape(cfg: dict) -> tuple:
    return ("head", cfg["hidden_size"], cfg["vocab_size"])


def lm_token_macs(cfg: dict) -> int:
    """Multiply-accumulates of one token through every analog layer and the
    head (446.0 M for stablelm-3b at 4 layers)."""
    per_layer = sum(k * n for _, k, n in lm_layer_shapes(cfg))
    _, k, n = lm_head_shape(cfg)
    return per_layer * cfg["num_hidden_layers"] + k * n


def lm_attention_ops(cfg: dict, q_positions, context: int) -> float:
    """Scores and mixing of causal attention for queries at
    ``q_positions`` (absolute) over keys ``0..position``: 2 ops per
    multiply-add, two products, every layer."""
    d = cfg["num_attention_heads"] * (cfg["hidden_size"]
                                      // cfg["num_attention_heads"])
    keys = sum(p + 1 for p in q_positions)
    return 4.0 * d * keys * cfg["num_hidden_layers"]


def lm_step(cfg: dict, batch: int, q_len: int, start: int, peak: dict,
            mvm: Work, total: Work) -> None:
    """Count one prefill (``start = 0``, ``q_len`` prompt tokens) or one
    decode step (``q_len = 1`` at position ``start``) of ``batch`` rows:
    the analog layers into ``mvm`` (every layer at every row; the head
    only at each row's last position, the one the step needs), and the
    whole step's operations, attention included, into ``total``."""
    m = batch * q_len
    ops = 0.0
    for _, k, n in lm_layer_shapes(cfg):
        o = mvm_ops(m, k, n)
        mvm.add(o, mvm_bytes(m, k, n), peak, cfg["num_hidden_layers"])
        ops += o * cfg["num_hidden_layers"]
    _, k, n = lm_head_shape(cfg)
    o = mvm_ops(batch, k, n)
    mvm.add(o, mvm_bytes(batch, k, n), peak)
    ops += o
    ops += batch * lm_attention_ops(cfg, range(start, start + q_len),
                                    start + q_len)
    total.ops += ops
    total.calls += 1
