"""Operations and bytes of a sparse (mixture-of-experts) decoder, from
shapes, beside ``flops.py`` and by its rules: two operations a
multiply-accumulate, matrix work only, recomputation not counted in a token's
training cost."""

from __future__ import annotations

from benchmarks import flops


def expert_layer_forward_flops(embed: int, experts: int, width: int,
                               top_k: int) -> int:
    """Forward operations of one expert layer for ONE TOKEN: the router's
    ``[embed, experts]`` matrix and ``top_k`` gated experts of three
    ``embed x width`` matrices each."""
    return 2 * embed * experts + top_k * 3 * 2 * embed * width


def moe_train_flops(seq_len: int, layers: int, embed: int, heads: int,
                    kv_heads: int, head_dim: int, experts: int, width: int,
                    top_k: int, vocab: int) -> int:
    """Forward and backward for one token of a decoder whose every block's
    feed-forward is an expert layer: projections and attention as
    ``flops.gpt_layer_forward_flops`` counts them (with no dense MLP), the
    expert layer, the head. The embedding is a gather."""
    attention = flops.gpt_layer_forward_flops(seq_len, embed, heads, kv_heads,
                                              head_dim, mlp=0)
    fwd = layers * (attention + expert_layer_forward_flops(
        embed, experts, width, top_k)) + 2 * embed * vocab
    return 3 * fwd


def grouped_matmul_pass_cost(rows: int, embed: int, width: int, experts: int,
                             itemsize: int = 2) -> dict:
    """What one pass through an expert layer's three grouped matmuls needs at
    least: every row (a token-expert pair) through its expert's gate, up and
    down matrices; each expert's three matrices read once, the rows read
    once and written once. The backward pass is two such passes (the
    gradient of the rows, the gradient of the matrices)."""
    return {"ops": 3 * 2 * rows * embed * width,
            "bytes": itemsize * (3 * experts * embed * width
                                 + 2 * rows * embed)}
