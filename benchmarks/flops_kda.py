"""Operations and bytes of Kimi delta attention (KDA: the delta rule with a
decay a key channel, chunked, its decayed products made by sub-blocks), and a
token's training cost in a decoder of KDA and latent-attention (MLA) mixers
over dense and expert feed-forwards, beside ``flops.py`` and by its rules:
two operations a multiply-accumulate, matrix work only (the convolution's
taps, the decays, the norms, the gates and the running sums are elementwise
or the implementation's own and not counted), recomputation not counted in a
token's training cost. They count the work, not the implementation."""

from __future__ import annotations

from benchmarks import flops_mla


def scan_forward_flops(heads: int, key_dim: int, value_dim: int,
                       chunk: int) -> int:
    """The chunked form's products for ONE TOKEN of a sequence the chunk
    divides, what is masked not counted (a triangular ``[chunk, chunk]``
    product is half a dense one), as ``flops_gdn.scan_forward_flops`` counts
    the scalar form with a key head a value head: the lower halves of the
    decayed ``K K^T`` and ``Q K^T`` (``chunk key_dim`` each: a vector decay
    is scales on the operands, the same products), the unit lower triangular
    inverse (``chunk^3 / 3`` a chunk), ``T`` applied to the values and to
    the keys, the masked ``Q K^T`` applied to the corrected values, and
    three products with the ``[key_dim, value_dim]`` state."""
    return heads * (2 * chunk * key_dim + chunk * chunk // 3
                    + 2 * chunk * value_dim + chunk * key_dim
                    + 6 * key_dim * value_dim)


def kda_mixer_forward_flops(embed: int, heads: int, key_dim: int,
                            value_dim: int, chunk: int) -> int:
    """Forward operations of one KDA mixer for ONE TOKEN: the projections to
    ``q``, ``k``, ``v``, to the gate's ``heads key_dim`` channels (full
    rank), to ``beta`` and the output gate (one a head each), the scan, the
    output projection."""
    key_inner, value_inner = heads * key_dim, heads * value_dim
    return 2 * embed * (3 * key_inner + value_inner + 2 * heads) \
        + scan_forward_flops(heads, key_dim, value_dim, chunk) \
        + 2 * value_inner * embed


def kda_mla_moe_train_flops(seq_len: int, kinds, dense_layers: int,
                            embed: int, kda: dict, mla: dict, mlp: int,
                            experts: dict, vocab: int) -> int:
    """Forward and backward for one token of a decoder whose layer ``i`` has
    the mixer ``kinds[i]`` (``"kda"`` or ``"mla"``), whose first
    ``dense_layers`` feed-forwards are SiLU-gated of width ``mlp`` and the
    rest expert blocks (``experts`` as ``flops_mla.mla_moe_train_flops``
    takes it). ``kda``: :func:`kda_mixer_forward_flops`'s keywords but
    ``embed``; ``mla``: ``flops_mla.mla_mixer_forward_flops``'s, its
    head-wise gate one ``embed x heads`` product more."""
    e = experts
    mixers = {
        "kda": kda_mixer_forward_flops(embed, **kda),
        "mla": flops_mla.mla_mixer_forward_flops(seq_len, embed, **mla)
        + 2 * embed * mla["heads"]}
    block = 2 * embed * e["router"] \
        + 3 * 2 * embed * e["width"] * e["top_k"] * e["held"] // e["router"] \
        + 3 * 2 * embed * e["shared_width"]
    fwd = sum(mixers[kind] for kind in kinds) \
        + dense_layers * 3 * 2 * embed * mlp \
        + (len(kinds) - dense_layers) * block + 2 * embed * vocab
    return 3 * fwd


def scan_pass_cost(tokens: int, heads: int, key_dim: int, value_dim: int,
                   chunk: int, itemsize: int = 2) -> dict:
    """What one pass of the chunked scan over ``tokens`` tokens needs at
    least: its products; ``q``, ``k``, ``v`` read once and ``o`` written
    once in the compute type, the log decay (a key channel) and ``beta``
    read once in float32. The decays, ``T``, the chunk states and whatever
    an implementation writes between the products are its own. The backward
    pass is two such passes."""
    return {"ops": tokens * scan_forward_flops(heads, key_dim, value_dim,
                                               chunk),
            "bytes": tokens * heads * (
                itemsize * 2 * (key_dim + value_dim) + 4 * (key_dim + 1))}
