"""Operations and bytes of latent attention (MLA) as training runs it (keys
and values decompressed a head: a query/key head of ``key_dim`` = no-position
plus rotary dimensions beside a value head of ``value_dim``), and a token's
training cost in a decoder of MLA mixers over dense and expert
feed-forwards, beside ``flops.py`` and by its rules: two operations a
multiply-accumulate, matrix work only (the latent's norm, the rotary
embedding, the shared key's broadcast and every activation are elementwise
and not counted), recomputation not counted in a token's training cost. They
count the work, not the implementation: lanes a kernel pads a 192-wide head
to are its own and appear nowhere here."""

from __future__ import annotations

from benchmarks import flops


def flash_forward_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                       key_dim: int, value_dim: int,
                       itemsize: int = 2) -> dict:
    """``flops.flash_forward_cost`` at two widths: the score product over
    ``key_dim`` and the value product over ``value_dim`` for every kept
    pair; q and k read at ``key_dim``, v read and o written at
    ``value_dim``, once; one float32 log-sum-exp per query row."""
    pairs = batch * heads * flops.causal_pairs(seq_len)
    rows = batch * seq_len
    return {
        "ops": 2 * (key_dim + value_dim) * pairs,
        "bytes": rows * itemsize * (heads + kv_heads)
        * (key_dim + value_dim) + rows * heads * 4,
    }


def flash_backward_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                        key_dim: int, value_dim: int,
                        itemsize: int = 2) -> dict:
    """``flops.flash_backward_cost`` at two widths: of its five products a
    kept pair, the scores again, dK and dQ are over ``key_dim``, dP and dV
    over ``value_dim``. Reads q, k (``key_dim``), v, o, dO (``value_dim``)
    and the log-sum-exp, writes dQ, dK (``key_dim``) and dV
    (``value_dim``)."""
    pairs = batch * heads * flops.causal_pairs(seq_len)
    rows = batch * seq_len
    return {
        "ops": 2 * (3 * key_dim + 2 * value_dim) * pairs,
        "bytes": rows * itemsize * 2 * (heads + kv_heads)
        * (key_dim + value_dim) + rows * heads * 4,
    }


def mla_mixer_forward_flops(seq_len: int, embed: int, heads: int,
                            nope_dim: int, rope_dim: int, value_dim: int,
                            kv_rank: int) -> int:
    """Forward operations of one MLA mixer for ONE TOKEN of a causal
    sequence: the query projection (``heads`` heads of ``nope_dim +
    rope_dim``), the down-projection to the latent and the shared rotary
    key, the up-projection to every head's no-position key part and value,
    the output projection, and the score and value products over the
    ``(seq_len + 1) / 2`` keys a token sees on average."""
    key_dim = nope_dim + rope_dim
    proj = 2 * embed * heads * key_dim + 2 * embed * (kv_rank + rope_dim) \
        + 2 * kv_rank * heads * (nope_dim + value_dim) \
        + 2 * heads * value_dim * embed
    attn = flops.causal_pairs(seq_len) * 2 * (key_dim + value_dim) * heads \
        // seq_len
    return proj + attn


def mla_moe_train_flops(seq_len: int, layers: int, dense_layers: int,
                        embed: int, mla: dict, mlp: int, experts: dict,
                        vocab: int) -> int:
    """Forward and backward for one token of ``layers`` layers of an MLA
    mixer (``mla``: ``mla_mixer_forward_flops``'s keywords) whose first
    ``dense_layers`` feed-forwards are SiLU-gated of width ``mlp``, the rest
    expert blocks: ``experts`` holds ``router`` (its width), ``width``,
    ``top_k``, ``held`` (a token's ``top_k`` experts are held here with
    probability ``held / router`` each under an even routing, and only those
    are multiplied) and ``shared_width`` (the shared experts every token
    goes through). The head is one ``embed x vocab`` product; the embedding
    is a gather."""
    e = experts
    block = 2 * embed * e["router"] \
        + 3 * 2 * embed * e["width"] * e["top_k"] * e["held"] // e["router"] \
        + 3 * 2 * embed * e["shared_width"]
    fwd = layers * mla_mixer_forward_flops(seq_len, embed, **mla) \
        + dense_layers * 3 * 2 * embed * mlp \
        + (layers - dense_layers) * block + 2 * embed * vocab
    return 3 * fwd
