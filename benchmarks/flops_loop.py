"""A **looped** decoder's training operations a data token (a stack of
sandwich-normed attention blocks with gated feed-forwards run ``passes``
times a step on the same parameters, a head and an exit gate after every
pass), beside ``flops.py`` and by its rules: two operations a
multiply-accumulate, matrix work only, recomputation not counted. **A sample
is a data token**: its ``passes`` trips through the stack, and as many
through the head, are what the model costs, counted into the one token it
trains on."""

from __future__ import annotations

from benchmarks import flops


def loop_pass_forward_flops(seq_len: int, layers: int, embed: int,
                            heads: int, kv_heads: int, head_dim: int,
                            mlp: int, vocab: int) -> int:
    """Forward operations of ONE PASS for one token of a causal sequence of
    ``seq_len``: every layer's projections and causal attention as
    ``flops.gpt_layer_forward_flops`` has them with the gated feed-forward's
    **three** matrices (``6 E M`` a token, where that function counts two:
    its ``mlp`` is given as 0 and the three are added here), the head's
    product and the exit gate's ``2 E``."""
    layer = flops.gpt_layer_forward_flops(seq_len, embed, heads, kv_heads,
                                          head_dim, mlp=0) + 6 * embed * mlp
    return layers * layer + 2 * embed * vocab + 2 * embed


def loop_train_flops(seq_len: int, layers: int, passes: int, embed: int,
                     heads: int, kv_heads: int, head_dim: int, mlp: int,
                     vocab: int) -> int:
    """Forward and backward for ONE DATA TOKEN: ``passes`` passes, three
    times the forward count as ``flops.gpt_train_flops``. The embedding is a
    gather."""
    return 3 * passes * loop_pass_forward_flops(
        seq_len, layers, embed, heads, kv_heads, head_dim, mlp, vocab)
