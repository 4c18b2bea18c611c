"""Pairs, operations, bytes and tiles of attention under the block-diffusion
mask (training by diffusion over blocks: the noised and the clean copy of a
sequence of ``L`` data tokens, ``2 L`` rows, in blocks of ``B``), and a data
token's training cost in a grouped-query decoder of expert blocks trained
that way, beside ``flops.py`` and by its rules: two operations a
multiply-accumulate, matrix work only, recomputation not counted in a
token's training cost. **A sample is a data token**: what the ``2 L`` rows
cost is the method's, and is counted into the ``L`` tokens it trains on."""

from __future__ import annotations

from benchmarks import flops


def bd_pairs(length: int, block: int) -> int:
    """Query-key pairs the mask keeps over one sequence's ``2 L`` rows, with
    ``n = L / B`` blocks: clean-clean ``B^2 n (n + 1) / 2`` (block-causal),
    noised-clean ``B^2 n (n - 1) / 2`` (the clean blocks before a noised
    block's own), noised-noised ``n B^2`` (a block sees itself): ``L^2 + L
    B``."""
    n = length // block
    if n * block != length:
        raise ValueError(f"blocks of {block} do not divide {length} tokens")
    return block * block * (n * (n + 1) // 2 + n * (n - 1) // 2 + n)


def flash_forward_cost(batch: int, length: int, block: int, heads: int,
                       kv_heads: int, head_dim: int,
                       itemsize: int = 2) -> dict:
    """``flops.flash_forward_cost`` with the mask's pairs: two products a
    kept pair; q, k, v and o of all ``2 L`` rows read or written once."""
    cost = flops.flash_forward_cost(batch, 2 * length, heads, kv_heads,
                                    head_dim, itemsize)
    return {"ops": 4 * head_dim * batch * heads * bd_pairs(length, block),
            "bytes": cost["bytes"]}


def flash_backward_cost(batch: int, length: int, block: int, heads: int,
                        kv_heads: int, head_dim: int,
                        itemsize: int = 2) -> dict:
    """``flops.flash_backward_cost`` with the mask's pairs: five products a
    kept pair."""
    cost = flops.flash_backward_cost(batch, 2 * length, heads, kv_heads,
                                     head_dim, itemsize)
    return {"ops": 10 * head_dim * batch * heads * bd_pairs(length, block),
            "bytes": cost["bytes"]}


def bd_tiles(length: int, block: int, block_q: int, block_k: int) -> tuple:
    """``(tiles the mask's grid computes, tiles of the 2 L x 2 L
    rectangle)`` for ``2 L`` a multiple of both blocks: a tile is computed
    where it holds a kept pair. A row is its half and its block; a tile's
    rows are few distinct such, and the clauses are asked of each pair of
    them."""
    rows = 2 * length

    def kinds(first: int, count: int) -> set:
        return {(at >= length, at % length // block)
                for at in range(first, first + count)}

    def kept(q, k) -> bool:
        (q_clean, bq), (k_clean, bk) = q, k
        if not q_clean:
            return bk < bq if k_clean else bk == bq
        return k_clean and bk <= bq

    q_tiles = [kinds(i, block_q) for i in range(0, rows, block_q)]
    k_tiles = [kinds(j, block_k) for j in range(0, rows, block_k)]
    computed = sum(any(kept(q, k) for q in qs for k in ks)
                   for qs in q_tiles for ks in k_tiles)
    return computed, len(q_tiles) * len(k_tiles)


def bd_moe_train_flops(length: int, block: int, layers: int, embed: int,
                       heads: int, kv_heads: int, head_dim: int,
                       experts: dict, vocab: int) -> int:
    """Forward and backward for ONE DATA TOKEN of a sequence of ``length``
    trained by diffusion over blocks of ``block``: every layer's projections
    and expert block run over the token's two rows (the noised and the
    clean), attention over the mask's pairs, the head over the noised row
    alone. ``experts`` holds ``router`` (its width), ``width``, ``top_k``
    and ``held`` (a row's ``top_k`` experts are held here with probability
    ``held / router`` each under an even routing). The embedding is a
    gather."""
    e = experts
    proj = 2 * embed * (heads + 2 * kv_heads) * head_dim \
        + 2 * heads * head_dim * embed
    moe = 2 * embed * e["router"] \
        + 3 * 2 * embed * e["width"] * e["top_k"] * e["held"] // e["router"]
    attention = bd_pairs(length, block) * 4 * heads * head_dim // length
    fwd = layers * (2 * (proj + moe) + attention) + 2 * embed * vocab
    return 3 * fwd
