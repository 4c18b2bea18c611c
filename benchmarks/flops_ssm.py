"""Operations and bytes of a decoder whose layers are Mamba-2 state-space
mixers or grouped-query attention, from shapes, beside ``flops.py`` and by
its rules: two operations a multiply-accumulate, matrix work only (the
convolution's taps, the decays and the gated norm are elementwise and not
counted), recomputation not counted in a token's training cost."""

from __future__ import annotations

from benchmarks import flops


def scan_forward_flops(heads: int, head_dim: int, state: int, groups: int,
                       chunk: int) -> int:
    """The chunked scan's four products for ONE TOKEN of a sequence the
    chunk divides: ``C B^T`` over the chunk's ``chunk`` tokens once a group
    (2 chunk state); the weighted product applied to ``x`` (2 chunk
    head_dim a head); the chunk's own state ``x^T B`` and the entering
    state through ``C`` (2 head_dim state a head each)."""
    return 2 * chunk * state * groups \
        + heads * (2 * chunk * head_dim + 4 * head_dim * state)


def ssm_mixer_forward_flops(embed: int, heads: int, head_dim: int,
                            state: int, groups: int, chunk: int) -> int:
    """Forward operations of one state-space mixer for ONE TOKEN: the input
    projection to ``z``, ``x``, ``B``, ``C`` and ``dt``, the scan, the
    output projection."""
    inner = heads * head_dim
    in_proj = 2 * embed * (2 * inner + 2 * groups * state + heads)
    return in_proj + scan_forward_flops(heads, head_dim, state, groups,
                                        chunk) + 2 * inner * embed


def attention_mixer_forward_flops(seq_len: int, embed: int, heads: int,
                                  kv_heads: int, head_dim: int) -> int:
    """As ``flops.gpt_layer_forward_flops`` counts projections and attention,
    with no feed-forward."""
    return flops.gpt_layer_forward_flops(seq_len, embed, heads, kv_heads,
                                         head_dim, mlp=0)


def hybrid_train_flops(seq_len: int, kinds, embed: int, heads: int,
                       kv_heads: int, head_dim: int, mlp: int, vocab: int,
                       ssm: dict) -> int:
    """Forward and backward for one token of a decoder whose layer ``i`` has
    the mixer ``kinds[i]`` (``"attention"`` or ``"ssm"``) and a gated
    feed-forward of three ``embed x mlp`` matrices; ``ssm`` holds the
    state-space mixer's ``heads``, ``head_dim``, ``state``, ``groups`` and
    ``chunk``. The head is one ``embed x vocab`` product whether or not it
    is tied; the embedding is a gather."""
    mixers = {
        "attention": attention_mixer_forward_flops(seq_len, embed, heads,
                                                   kv_heads, head_dim),
        "ssm": ssm_mixer_forward_flops(embed, **ssm)}
    fwd = sum(mixers[kind] + 6 * embed * mlp for kind in kinds) \
        + 2 * embed * vocab
    return 3 * fwd


def scan_pass_cost(tokens: int, heads: int, head_dim: int, state: int,
                   groups: int, chunk: int, itemsize: int = 2) -> dict:
    """What one pass of the chunked scan over ``tokens`` tokens needs at
    least: its four products; ``x``, ``B`` and ``C`` read once and ``y``
    written once in the compute type, ``dt`` read once in float32. The
    decays, the chunk states and whatever an implementation writes between
    the products are its own. The backward pass is two such passes."""
    return {"ops": tokens * scan_forward_flops(heads, head_dim, state,
                                               groups, chunk),
            "bytes": tokens * (itemsize * (2 * heads * head_dim
                                           + 2 * groups * state)
                               + 4 * heads)}
