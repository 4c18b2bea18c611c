"""Operations and bytes of a decoder whose every layer is a CCA attention
sublayer (attention in a compressed latent, q and k mixed by two stacked
causal convolutions) and an expert sublayer under an MLP router, from
shapes, beside ``flops.py`` and by its rules: two operations a
multiply-accumulate, matrix work only (with it both convolutions, whose taps
are products of weights and activations; the means, the L2 norms, the rotary
embedding, the residual scaling and every activation are elementwise and not
counted), recomputation not counted in a token's training cost. They count
the work, not the implementation: a kernel that fuses or splits the mix
changes none of them."""

from __future__ import annotations

from benchmarks import flops


def latent(heads: int, kv_heads: int, head_dim: int) -> int:
    """The channels the convolutions mix: q and k side by side."""
    return (heads + kv_heads) * head_dim


def conv_forward_flops(heads: int, kv_heads: int, head_dim: int,
                       taps: tuple) -> int:
    """Both convolutions for ONE TOKEN: the depthwise stage's ``taps[0]``
    products a channel, the grouped stage's ``taps[1]`` matrices of
    ``head_dim x head_dim`` a head."""
    return 2 * taps[0] * latent(heads, kv_heads, head_dim) \
        + 2 * taps[1] * (heads + kv_heads) * head_dim * head_dim


def cca_mixer_forward_flops(seq_len: int, embed: int, heads: int,
                            kv_heads: int, head_dim: int, taps: tuple) -> int:
    """Forward operations of one CCA mixer for ONE TOKEN of a causal
    sequence: the projections to the latent (q and k) and to the value (its
    two halves together as wide as k), both convolutions, the score and
    value products over the ``(seq_len + 1) / 2`` keys a token sees on
    average, the output projection."""
    proj = 2 * embed * (heads + 2 * kv_heads) * head_dim \
        + 2 * heads * head_dim * embed
    attn = flops.causal_pairs(seq_len) * 4 * heads * head_dim // seq_len
    return proj + conv_forward_flops(heads, kv_heads, head_dim, taps) + attn


def router_mlp_forward_flops(embed: int, router_dim: int,
                             experts: int) -> int:
    """Forward operations of the MLP router for ONE TOKEN: the
    down-projection, two square layers and the matrix to the experts (the
    state from the layer before is a product a channel: elementwise)."""
    return 2 * embed * router_dim + 2 * 2 * router_dim * router_dim \
        + 2 * router_dim * experts


def cca_moe_train_flops(seq_len: int, layers: int, embed: int, heads: int,
                        kv_heads: int, head_dim: int, taps: tuple,
                        router_dim: int, experts: dict, vocab: int) -> int:
    """Forward and backward for one token of ``layers`` layers of a CCA
    mixer and an expert sublayer: ``experts`` holds ``router`` (its width),
    ``width``, ``top_k`` and ``held`` (a token's ``top_k`` experts are held
    here with probability ``held / router`` each under an even routing, and
    only those are multiplied). The head is one ``embed x vocab`` product;
    the embedding is a gather."""
    e = experts
    block = router_mlp_forward_flops(embed, router_dim, e["router"]) \
        + 3 * 2 * embed * e["width"] * e["top_k"] * e["held"] // e["router"]
    mixer = cca_mixer_forward_flops(seq_len, embed, heads, kv_heads,
                                    head_dim, taps)
    return 3 * (layers * (mixer + block) + 2 * embed * vocab)


def mix_pass_cost(tokens: int, heads: int, kv_heads: int, head_dim: int,
                  taps: tuple, itemsize: int = 2) -> dict:
    """What one pass over ``tokens`` tokens through a CCA mixer's mix (what
    lies between the latent projections and attention) needs at least: the
    latent ``u = [q0 | k0]`` read once and ``q``, ``k`` and ``v`` written
    once (``v`` as wide as ``k``), in the compute dtype; the grouped
    stage's products (the depthwise stage, the means, the norms and the
    rotary embedding ride the same pass over memory). A step under full
    recomputation makes three such passes a layer: forward, again, and a
    backward pass that moves as much the other way."""
    wide = latent(heads, kv_heads, head_dim)
    return {"ops": tokens * 2 * taps[1] * (heads + kv_heads)
            * head_dim * head_dim,
            "bytes": tokens * itemsize * (2 * wide + kv_heads * head_dim)}
