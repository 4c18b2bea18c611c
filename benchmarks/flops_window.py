"""Pairs, operations, bytes and tiles of attention under a causal band (a
sliding window), and a token's training cost in a decoder that mixes window
and full attention layers, dense and expert feed-forwards, beside
``flops.py`` and by its rules: two operations a multiply-accumulate, matrix
work only, recomputation not counted in a token's training cost."""

from __future__ import annotations

from benchmarks import flops


def band_pairs(seq_len: int, window=None) -> int:
    """Query-key pairs one sequence keeps under ``0 <= i - j < window`` (a
    query sees itself and the ``window - 1`` keys before it): the first
    ``window`` queries the causal triangle's ``W (W + 1) / 2``, each later
    one ``window``. No window, or one of the sequence's length or more, is
    the causal mask."""
    if window is None or window >= seq_len:
        return flops.causal_pairs(seq_len)
    return window * (window + 1) // 2 + (seq_len - window) * window


def flash_forward_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                       head_dim: int, window=None, itemsize: int = 2) -> dict:
    """``flops.flash_forward_cost`` with the band's pairs: two products a
    kept pair; q, k, v and o read or written once whatever the band."""
    cost = flops.flash_forward_cost(batch, seq_len, heads, kv_heads,
                                    head_dim, itemsize)
    return {"ops": 4 * head_dim * batch * heads * band_pairs(seq_len, window),
            "bytes": cost["bytes"]}


def flash_backward_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                        head_dim: int, window=None,
                        itemsize: int = 2) -> dict:
    """``flops.flash_backward_cost`` with the band's pairs: five products a
    kept pair."""
    cost = flops.flash_backward_cost(batch, seq_len, heads, kv_heads,
                                     head_dim, itemsize)
    return {"ops": 10 * head_dim * batch * heads
            * band_pairs(seq_len, window), "bytes": cost["bytes"]}


def band_tiles(seq_len: int, window, block_q: int, block_k: int) -> tuple:
    """``(tiles a band's grid computes, tiles the causal triangle's does)``
    for a sequence already a multiple of both blocks: a tile is computed
    where it holds a kept pair, so where its last query row reaches its
    first key and, under a window, its first query row still sees its last
    key."""
    kept = causal = 0
    for i in range(seq_len // block_q):
        for j in range(seq_len // block_k):
            if (i + 1) * block_q - 1 < j * block_k:
                continue
            causal += 1
            kept += window is None \
                or (j + 1) * block_k - 1 >= i * block_q - (window - 1)
    return kept, causal


def layer_forward_flops(seq_len: int, embed: int, heads: int, kv_heads: int,
                        head_dim: int, window=None, gate: bool = True) -> int:
    """Forward operations of one attention mixer for ONE TOKEN: q (and the
    output gate's projection, as wide), k, v and o, and the score and value
    products over the keys a token sees on average under the band."""
    proj = 2 * embed * ((2 if gate else 1) * heads + 2 * kv_heads) * head_dim \
        + 2 * heads * head_dim * embed
    return proj + band_pairs(seq_len, window) * 4 * heads * head_dim \
        // seq_len


def window_moe_train_flops(seq_len: int, windows, dense_layers: int,
                           embed: int, heads: int, kv_heads: int,
                           head_dim: int, mlp: int, experts: dict,
                           vocab: int) -> int:
    """Forward and backward for one token of a decoder whose layer ``i``
    attends under ``windows[i]`` (None: full) and whose first
    ``dense_layers`` feed-forwards are SiLU-gated of width ``mlp``, the rest
    expert blocks: ``experts`` holds ``router`` (its width), ``width``,
    ``top_k``, ``held`` (a token's ``top_k`` experts are held here with
    probability ``held / router`` each under an even routing) and
    ``shared_width`` (the shared expert every token goes through). The
    embedding is a gather."""
    e = experts
    block = 2 * embed * e["router"] \
        + 3 * 2 * embed * e["width"] * e["top_k"] * e["held"] // e["router"] \
        + 3 * 2 * embed * e["shared_width"]
    fwd = sum(layer_forward_flops(seq_len, embed, heads, kv_heads, head_dim,
                                  window) for window in windows) \
        + dense_layers * 3 * 2 * embed * mlp \
        + (len(windows) - dense_layers) * block + 2 * embed * vocab
    return 3 * fwd
