"""Operations and bytes of a decoder whose layers are gated-delta-rule linear
attention or gated softmax attention, each followed by an expert block with
a shared expert, from shapes, beside ``flops.py`` and by its rules: two
operations a multiply-accumulate, matrix work only (the convolution's taps,
the decays, the norms and the gates are elementwise and not counted),
recomputation not counted in a token's training cost."""

from __future__ import annotations

from benchmarks import flops


def scan_forward_flops(key_heads: int, value_heads: int, key_dim: int,
                       value_dim: int, chunk: int) -> int:
    """The chunked (WY) form's products for ONE TOKEN of a sequence the chunk
    divides, what is masked not counted (a triangular ``[chunk, chunk]``
    product is half a dense one). A key head: the lower halves of ``K K^T``
    and ``Q K^T`` (``chunk key_dim`` each). A value head: the unit lower
    triangular inverse (``chunk^3 / 3`` a chunk), ``T`` applied to the
    values and to the keys (``chunk value_dim``, ``chunk key_dim``), the
    masked ``Q K^T`` applied to the corrected values (``chunk value_dim``),
    and three products with the ``[key_dim, value_dim]`` state: the
    correction, the read and the update (``2 key_dim value_dim`` each)."""
    return key_heads * 2 * chunk * key_dim + value_heads * (
        chunk * chunk // 3 + 2 * chunk * value_dim + chunk * key_dim
        + 6 * key_dim * value_dim)


def gdn_mixer_forward_flops(embed: int, key_heads: int, value_heads: int,
                            key_dim: int, value_dim: int, chunk: int) -> int:
    """Forward operations of one linear-attention mixer for ONE TOKEN: the
    projections to ``q``, ``k``, ``v``, ``z`` and to ``b``, ``a``, the scan,
    the output projection."""
    key_inner, value_inner = key_heads * key_dim, value_heads * value_dim
    return 2 * embed * (2 * key_inner + 2 * value_inner + 2 * value_heads) \
        + scan_forward_flops(key_heads, value_heads, key_dim, value_dim,
                             chunk) + 2 * value_inner * embed


def gated_attention_mixer_forward_flops(seq_len: int, embed: int, heads: int,
                                        kv_heads: int, head_dim: int) -> int:
    """As ``flops.gpt_layer_forward_flops`` counts projections and attention
    with no feed-forward, plus the query projection's second half (the
    output gate)."""
    return flops.gpt_layer_forward_flops(
        seq_len, embed, heads, kv_heads, head_dim, mlp=0) \
        + 2 * embed * heads * head_dim


def expert_block_forward_flops(embed: int, router: int, width: int,
                               top_k: int, held: int,
                               shared_width: int) -> float:
    """Forward operations of one expert block for ONE TOKEN on a rank that
    holds ``held`` of the router's ``router`` experts: the whole router, the
    token's ``top_k held / router`` held experts on average (an even
    routing's share: the rows a step really multiplies vary with the
    routing) of three ``embed x width`` matrices each, and the shared
    expert's three matrices and gate."""
    return 2 * embed * router + top_k * held / router * 6 * embed * width \
        + (6 * embed * shared_width + 2 * embed if shared_width else 0)


def linear_moe_train_flops(seq_len: int, kinds, embed: int, heads: int,
                           kv_heads: int, head_dim: int, vocab: int,
                           gdn: dict, experts: dict) -> float:
    """Forward and backward for one token of a decoder whose layer ``i`` has
    the mixer ``kinds[i]`` (``"attention"``, gated, or ``"gdn"``) and an
    expert block; ``gdn`` holds the linear mixer's ``key_heads``,
    ``value_heads``, ``key_dim``, ``value_dim`` and ``chunk``, ``experts``
    :func:`expert_block_forward_flops`'s keywords but ``embed``. The head is
    one ``embed x vocab`` product; the embedding is a gather."""
    mixers = {
        "attention": gated_attention_mixer_forward_flops(
            seq_len, embed, heads, kv_heads, head_dim),
        "gdn": gdn_mixer_forward_flops(embed, **gdn)}
    block = expert_block_forward_flops(embed, **experts)
    fwd = sum(mixers[kind] + block for kind in kinds) + 2 * embed * vocab
    return 3 * fwd


def scan_pass_cost(tokens: int, key_heads: int, value_heads: int,
                   key_dim: int, value_dim: int, chunk: int,
                   itemsize: int = 2) -> dict:
    """What one pass of the chunked scan over ``tokens`` tokens needs at
    least: its products; ``q`` and ``k`` read once at the key heads, ``v``
    read once and ``o`` written once at the value heads in the compute type,
    the log decay and ``beta`` read once in float32. The decays, ``T``, the
    chunk states and whatever an implementation writes between the products
    are its own. The backward pass is two such passes."""
    return {"ops": tokens * scan_forward_flops(key_heads, value_heads,
                                               key_dim, value_dim, chunk),
            "bytes": tokens * (itemsize * (2 * key_heads * key_dim
                                           + 2 * value_heads * value_dim)
                               + 2 * 4 * value_heads)}
