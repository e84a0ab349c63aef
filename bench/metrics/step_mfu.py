"""Whole-step share of the chip's bf16 peak: the operations the window's
steps require (the family's ``step_flops``: forward, activation gradients
at and above each step's cut, weight gradients of the trained group,
causal attention, no recomputation) over window time times the peak."""
from bench.lib.peaks import peaks


def read(facts):
    c, mix = facts["config"], facts["traffic"]
    ops = sum(facts["family"].step_flops(c, mix["batch"], mix["seq"], g)
              for g in facts["window_groups"])
    if not ops:
        return None
    peak = peaks(facts["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (facts["window_s"] * peak)
