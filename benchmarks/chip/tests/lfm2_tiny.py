"""A tiny ``lfm2`` cell for CPU tests: the benchmark's configuration and
traffic with every width cut to toy size (the same 6-layer pattern, 4 of
8 experts held), run through the same driver, reference and harness."""
from __future__ import annotations

import json

from chipbench_tiny import BENCH, CPU_PEAK, device, spec, steer  # noqa: F401

NAME = "lfm2-prefill1k"
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=64,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
            num_experts_published=8, num_experts=4,
            held_expert_ids=[0, 1, 2, 3])


def config() -> dict:
    """The configuration at toy widths.  The head's logits spread with
    the square root of the hidden size (its master weight is the
    embedding table, transposed), so the served-gap limit shrinks by the
    same factor."""
    with open(BENCH / "configs" / "lfm2-8b-a1b-6l.json") as f:
        cfg = json.load(f)
    scale = (TINY["hidden_size"] / cfg["hidden_size"]) ** 0.5
    gap = cfg["limits"]["max_served_gap"]
    limits = {**cfg["limits"],
              "max_served_gap": {**gap, "limit": gap["limit"] * scale}}
    return {**cfg, **TINY, "limits": limits}


def cell(prompt: int = 8, new: int = 3) -> spec.Cell:
    with open(BENCH / "traffic" / "prefill1k.json") as f:
        traffic = json.load(f)
    traffic.update(request={"prompt_tokens": prompt, "new_tokens": new},
                   max_len=prompt + new, check={"batches": 2},
                   trace_seconds=0.3)
    bench = spec.load_benchmark()
    listed = NAME in {w["name"] for w in bench["workloads"]}

    def reports(metrics):
        return tuple(m for m in metrics
                     if listed and spec._reports(m, NAME))

    return spec.Cell(name=NAME, chips=1, config=config(), traffic=traffic,
                     end_to_end=reports(bench["end_to_end"]),
                     per_layer=reports(bench["per_layer"]))


def steer_lfm2(monkeypatch, **sizes):
    """:func:`chipbench_tiny.steer`, with the tiny ``lfm2`` cell."""
    steer(monkeypatch)
    tiny_cell = cell(**sizes)
    find = spec.find_cell

    def find_lfm2(name, bench):
        return tiny_cell if name == NAME else find(name, bench)

    monkeypatch.setattr(spec, "find_cell", find_lfm2)
    return tiny_cell
