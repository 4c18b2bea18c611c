"""Operations of a dense decoder whose layers are gated-delta-rule linear
attention or full softmax attention, each followed by a SiLU-gated
feed-forward, from shapes, beside ``flops.py`` and by its rules: two
operations a multiply-accumulate, matrix work only (the convolution's taps,
the decays, the norms and the gates are elementwise and not counted),
recomputation not counted in a token's training cost. The linear mixer and
its scan are ``flops_gdn.py``'s, at the head sizes the configuration
publishes: lanes a kernel pads a head with are its cost, not the
algorithm's."""

from __future__ import annotations

from benchmarks import flops, flops_gdn


def gated_ff_forward_flops(embed: int, mlp: int) -> int:
    """Forward operations of one SiLU-gated feed-forward for ONE TOKEN: the
    gate, up and down matrices."""
    return 6 * embed * mlp


def linear_forward_flops(seq_len: int, kinds, embed: int, heads: int,
                         kv_heads: int, head_dim: int, mlp: int, vocab: int,
                         gdn: dict) -> int:
    """Forward operations for ONE TOKEN of a decoder whose layer ``i`` has
    the mixer ``kinds[i]`` (``"attention"``: the four projections and the
    score and value products over the ``(seq_len + 1) / 2`` keys a token
    sees on average; ``"gdn"``: ``flops_gdn.gdn_mixer_forward_flops``) and a
    gated feed-forward; ``gdn`` holds the linear mixer's ``key_heads``,
    ``value_heads``, ``key_dim``, ``value_dim`` and ``chunk``. The head is
    one ``embed x vocab`` product; the embedding is a gather."""
    mixers = {
        "attention": flops.gpt_layer_forward_flops(
            seq_len, embed, heads, kv_heads, head_dim, mlp=0),
        "gdn": flops_gdn.gdn_mixer_forward_flops(embed, **gdn)}
    return sum(mixers[kind] + gated_ff_forward_flops(embed, mlp)
               for kind in kinds) + 2 * embed * vocab


def linear_train_flops(seq_len: int, kinds, embed: int, heads: int,
                       kv_heads: int, head_dim: int, mlp: int, vocab: int,
                       gdn: dict) -> int:
    """Forward and backward for one token."""
    return 3 * linear_forward_flops(seq_len, kinds, embed, heads, kv_heads,
                                    head_dim, mlp, vocab, gdn)
