"""Operations and bytes from shapes, against hand arithmetic."""
import pytest

from bench.lib import spec
from bench.lib.model import groups
from bench.lib.peaks import PEAKS, peaks

F = spec.family("dense")

B, S = 8, 512
T = B * S


@pytest.fixture
def internlm():
    return spec.config("internlm2_1_8b-l16")


@pytest.fixture
def qwen():
    return spec.config("qwen2_0_5b")


def _attn(c):
    return 4 * B * c["num_attention_heads"] * 128 * S * (S + 1) // 2 \
        if c["hidden_size"] == 2048 else \
        4 * B * c["num_attention_heads"] * 64 * S * (S + 1) // 2


def test_layer_matmul_params(internlm, qwen):
    # q and o 2048x2048, k and v 2048x1024, three 2048x8192 projections
    assert F.layer_matmul_params(internlm) == (
        2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192)
    assert F.layer_matmul_params(qwen) == (
        2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864)


def test_top_group_trains_the_head_only(internlm):
    n_l = F.layer_matmul_params(internlm)
    head = 2 * 2048 * 92544 * T
    fwd = 16 * (2 * n_l * T + _attn(internlm)) + head
    # backward: the loss's gradient into the final hidden state (for the
    # norm's scale) and the head weight's gradient; no block is crossed
    assert F.step_flops(internlm, B, S, [("head", None)]) == fwd + head + head


def test_layer_group_crosses_the_blocks_above_its_cut(internlm):
    n_l = F.layer_matmul_params(internlm)
    head = 2 * 2048 * 92544 * T
    a = _attn(internlm)
    fwd = 16 * (2 * n_l * T + a) + head
    act = head + (16 - 5) * (2 * n_l * T + 2 * a)
    assert F.step_flops(internlm, B, S, [("layers", 5)]) == \
        fwd + act + 2 * n_l * T


def test_embed_group_crosses_every_block_and_has_no_matmul_weight(internlm):
    n_l = F.layer_matmul_params(internlm)
    head = 2 * 2048 * 92544 * T
    a = _attn(internlm)
    fwd = 16 * (2 * n_l * T + a) + head
    act = head + 16 * (2 * n_l * T + 2 * a)
    assert F.step_flops(internlm, B, S, [("embed", None)]) == fwd + act


def test_fpft_is_6nt_plus_attention(qwen, internlm):
    for c in (qwen, internlm):
        n = c["num_hidden_layers"] * F.layer_matmul_params(c) \
            + c["hidden_size"] * c["vocab_size"]
        want = 6 * n * T + 3 * c["num_hidden_layers"] * _attn(c)
        assert F.step_flops(c, B, S) == want


def test_groups_partition_the_units(internlm):
    gs = groups(F.units(internlm), 1)
    assert len(gs) == 18 and gs[0] == [("embed", None)] \
        and gs[-1] == [("head", None)]
    assert groups(F.units(internlm), 6)[1] == [("layers", i)
                                               for i in range(5, 11)]


def test_peaks_table_names_its_source_and_refuses_unknown_kinds():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert all(p["source"] for p in PEAKS.values())
    with pytest.raises(KeyError):
        peaks("cpu")
