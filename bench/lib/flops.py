"""Operations and bytes that a training step needs, from shapes alone.

Counts what the algorithm requires, not what the program happens to run:
the forward pass; activation gradients only for the layers at or above
the backward cut; weight gradients only for the group being trained;
causal attention (half the score matrix); no recomputation under remat.
An embedding lookup and its gradient are gathers and scatters: no matmul
operations.  A multiply-add counts as 2 operations.

``c`` is a configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "h": h, "kv": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // h, "ff": c["intermediate_size"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"],
            "tied": bool(c.get("tie_word_embeddings", False))}


def layer_matmul_params(c: dict) -> int:
    """Weights one token meets in one block's matmuls: q, k, v, o and the
    three SwiGLU projections."""
    x = dims(c)
    return (x["d"] * x["h"] * x["hd"] * 2 + x["d"] * x["kv"] * x["hd"] * 2
            + 3 * x["d"] * x["ff"])


def attention_forward(c: dict, batch: int, seq: int) -> int:
    """One layer's causal attention forward: QK^T and PV over the
    seq * (seq + 1) / 2 query-key pairs a causal mask keeps."""
    x = dims(c)
    return 4 * batch * x["h"] * x["hd"] * seq * (seq + 1) // 2


def units(c: dict) -> list:
    """HiFT's units, bottom to top: ``"embed"``, ``("layer", i)``,
    ``"head"``."""
    return ["embed"] + [("layer", i) for i in range(dims(c)["L"])] + ["head"]


def groups(c: dict, m: int) -> list:
    """Contiguous groups of ``m`` units (HiFT's partition)."""
    u = units(c)
    return [u[i:i + m] for i in range(0, len(u), m)]


def step_flops(c: dict, batch: int, seq: int, group=None) -> int:
    """Required operations of one training step.  ``group`` is a list of
    units (see :func:`units`); ``None`` trains every unit (FPFT)."""
    x = dims(c)
    t = batch * seq
    group = units(c) if group is None else list(group)
    p_l = layer_matmul_params(c)
    attn = attention_forward(c, batch, seq)
    head_mm = 2 * x["d"] * x["V"] * t
    layer_mm = 2 * p_l * t
    forward = x["L"] * (layer_mm + attn) + head_mm
    if "embed" in group:
        cut = 0
    else:
        layers = [u[1] for u in group if isinstance(u, tuple)]
        cut = min(layers) if layers else x["L"]
    # the loss's gradient always reaches the final hidden state
    act = head_mm + (x["L"] - cut) * (layer_mm + 2 * attn)
    weight = sum(layer_mm for u in group if isinstance(u, tuple))
    head_weight_trained = ("embed" in group) if x["tied"] else ("head" in group)
    if head_weight_trained:
        weight += head_mm
    return forward + act + weight
