"""Cells at a size a CPU test can hold: the benchmark's own cells with
their configurations cut in width and depth, and short sequences."""
from __future__ import annotations

import dataclasses

from bench.lib import spec

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256}

# limits that differ at this size.  HiFT's sound tiny run reads a loss gap
# of 2.0e-4 and a change gap of 1.3e-3 (weights of scale 1/8 and bf16
# compute on the CPU), above the chip's limits; its faults read 2.2e-2 and
# 0.24-0.41 (half a batch, a fresh or an unchanged revisit), the control
# 3.5e-3 and 1.0.
TINY_LIMITS = {"internlm2-l16-hift-b8s512": {"loss_gap": 1e-3,
                                             "change_norm_gap": 0.01}}


def tiny_cell(name: str, **workload) -> spec.Cell:
    return tiny(spec.cell(name), **workload)


def tiny(cell: spec.Cell, **workload) -> spec.Cell:
    """``cell`` cut to the tiny size, its family and files as they are."""
    mix = {**cell.traffic, "batch": 4, "seq": 32}
    limits = {**cell.workload["limits"], **TINY_LIMITS.get(cell.name, {})}
    return dataclasses.replace(
        cell, config={**cell.config, **TINY}, traffic=mix,
        workload={**cell.workload, "limits": limits, **workload})
