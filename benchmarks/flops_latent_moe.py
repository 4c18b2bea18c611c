"""Operations and bytes of a decoder whose blocks are one sublayer each (a
Mamba-2 mixer, a grouped-query attention mixer or an expert feed-forward
whose un-gated experts run in a latent narrower than the stream), from
shapes, beside ``flops.py`` and by its rules: two operations a
multiply-accumulate, matrix work only, recomputation not counted in a
token's training cost. What differs from ``flops_moe`` and ``flops_ssm`` is
here: two matrices an expert at the latent's width, the two projections
around them, a shared expert of two matrices on the stream; a mixer's block
has no feed-forward and an expert block no mixer."""

from __future__ import annotations

from benchmarks import flops_ssm


def latent_expert_block_forward_flops(embed: int, latent: int, router: int,
                                      width: int, top_k: int, held: int,
                                      shared_width: int) -> float:
    """Forward operations of one expert block for ONE TOKEN: the router's
    ``[embed, router]`` matrix on the stream, the projection to the latent
    and the one back, **the held share** ``top_k held / router`` of a
    token's un-gated experts (two ``latent x width`` matrices each; what an
    even routing sends to the experts held here, so that a utilization
    cannot read over what the chip did) and the shared expert's two ``embed
    x shared_width`` matrices."""
    return (2 * embed * router + 2 * 2 * embed * latent
            + top_k * held / router * 2 * 2 * latent * width
            + 2 * 2 * embed * shared_width)


def latent_moe_hybrid_train_flops(seq_len: int, pattern: str, embed: int,
                                  heads: int, kv_heads: int, head_dim: int,
                                  vocab: int, ssm: dict,
                                  experts: dict) -> float:
    """Forward and backward for one token of a decoder whose block ``i`` is
    ``pattern[i]``: ``M`` a Mamba-2 mixer (``ssm``:
    ``flops_ssm.ssm_mixer_forward_flops``'s sizes), ``*`` an attention mixer
    (projections and attention as ``flops.gpt_layer_forward_flops`` counts
    them, no feed-forward), ``E`` an expert block (``experts``:
    ``latent_expert_block_forward_flops``'s sizes); the head is one ``embed
    x vocab`` product, the embedding a gather."""
    blocks = {
        "M": flops_ssm.ssm_mixer_forward_flops(embed, **ssm),
        "*": flops_ssm.attention_mixer_forward_flops(seq_len, embed, heads,
                                                     kv_heads, head_dim),
        "E": latent_expert_block_forward_flops(embed, **experts)}
    return 3 * (sum(blocks[kind] for kind in pattern) + 2 * embed * vocab)


def grouped_matmul_pass_cost(rows: int, latent: int, width: int,
                             experts: int, itemsize: int = 2) -> dict:
    """What one pass through an un-gated expert layer's two grouped matmuls
    needs at least: every row (a token-expert pair, ``latent`` wide) through
    its expert's up and down matrices; each expert's two matrices read once,
    the rows read once and written once. The backward pass is two such
    passes (the gradient of the rows, the gradient of the matrices)."""
    return {"ops": 2 * 2 * rows * latent * width,
            "bytes": itemsize * (2 * experts * latent * width
                                 + 2 * rows * latent)}
