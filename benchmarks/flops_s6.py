"""Operations and bytes of a decoder-hybrid-decoder stack (Mamba-1 selective
scans, differential attention under a window, whole, and as cross-attention
to an earlier layer's keys and values, Gated Memory Units), from shapes,
beside ``flops.py`` and by its rules: two operations a multiply-accumulate,
matrix work only in a token's training cost, recomputation not counted.

The selective scan has no matrix product: its cost is what any
implementation of the recurrence moves and computes, so that the roofline
reads the same work whatever implements it."""

from __future__ import annotations

from benchmarks import flops_window

def mamba_mixer_forward_flops(embed: int, inner: int, state: int,
                              dt_rank: int) -> int:
    """Forward matrix operations of one Mamba-1 mixer for ONE TOKEN: the
    input projection to ``u`` and ``z``, ``[r | B | C]`` from the convolved
    channels, the step size from ``r``, the output projection. The
    convolution's taps, the scan and the gate are elementwise."""
    return 2 * embed * 2 * inner + 2 * inner * (dt_rank + 2 * state) \
        + 2 * dt_rank * inner + 2 * inner * embed


def gmu_forward_flops(embed: int, inner: int) -> int:
    """A Gated Memory Unit's two matrices for ONE TOKEN."""
    return 4 * embed * inner


def diff_attention_forward_flops(seq_len: int, embed: int, heads: int,
                                 kv_heads: int, head_dim: int, window=None,
                                 cross: bool = False) -> int:
    """Forward operations of one differential attention mixer for ONE
    TOKEN: the query and output projections, the key and value projections
    unless the layer reads another's (``cross``), and **two softmax maps a
    pair of heads**: ``heads / 2`` pairs, each two score products over
    ``head_dim`` and two value products over ``2 head_dim``, over the keys a
    token sees on average under the band."""
    proj = 2 * embed * heads * head_dim + 2 * heads * head_dim * embed \
        + (0 if cross else 2 * embed * 2 * kv_heads * head_dim)
    per_pair = 2 * (2 * head_dim + 2 * 2 * head_dim)
    return proj + flops_window.band_pairs(seq_len, window) \
        * (heads // 2) * per_pair // seq_len


def sambay_train_flops(seq_len: int, kinds, embed: int, heads: int,
                       kv_heads: int, head_dim: int, window: int, mlp: int,
                       vocab: int, inner: int, state: int,
                       dt_rank: int) -> int:
    """Forward and backward for one token of a stack whose layer ``i`` has
    the mixer ``kinds[i]`` (``mamba``, ``window``, ``full``, ``gmu`` or
    ``cross``) and a gated feed-forward of
    three ``embed x mlp`` matrices. The tied head is one ``embed x vocab``
    product; the embedding is a gather."""
    shape = dict(seq_len=seq_len, embed=embed, heads=heads,
                 kv_heads=kv_heads, head_dim=head_dim)
    mixers = {
        "mamba": mamba_mixer_forward_flops(embed, inner, state, dt_rank),
        "gmu": gmu_forward_flops(embed, inner),
        "window": diff_attention_forward_flops(window=window, **shape),
        "full": diff_attention_forward_flops(**shape),
        "cross": diff_attention_forward_flops(cross=True, **shape)}
    fwd = sum(mixers[kind] + 6 * embed * mlp for kind in kinds) \
        + 2 * embed * vocab
    return 3 * fwd


def diff_flash_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                    head_dim: int, window=None, itemsize: int = 2) -> dict:
    """What one differential attention layer asks of the flash kernels in a
    training step: two calls (one a softmax map), each a forward and a
    backward at ``heads / 2`` query and ``kv_heads / 2`` key heads of
    ``head_dim`` beside values of ``2 head_dim``, over the band's kept
    pairs. A call's products: score (2 D) and value (4 D) forward; scores
    again, dP (4 D), dV (4 D), dK and dQ (2 D each) backward. A call reads
    q, k, v, o (backward: and dO, writes dQ, dK, dV) once and a float32
    log-sum-exp a query row."""
    pairs = batch * (heads // 2) * flops_window.band_pairs(seq_len, window)
    rows, q_heads, k_heads = batch * seq_len, heads // 2, kv_heads // 2
    fwd_bytes = rows * itemsize * head_dim * (3 * q_heads + 3 * k_heads) \
        + rows * q_heads * 4
    bwd_bytes = rows * itemsize * head_dim * (
        (1 + 2 + 2 + 1) * q_heads + 2 * (1 + 2) * k_heads) \
        + rows * q_heads * 4
    return {"ops": 2 * pairs * head_dim * (6 + 14),
            "bytes": 2 * (fwd_bytes + bwd_bytes)}


def scan_pass_cost(tokens: int, inner: int, state: int,
                   itemsize: int = 2) -> dict:
    """What one pass of the selective scan over ``tokens`` tokens needs at
    least, whatever implements it: ``u`` read and ``y`` written in the
    compute type, ``dt`` read in float32, ``B`` and ``C`` read in the compute
    type; a token's channel and state: the decay's product and exponential,
    the state's multiply-add and the input's two products, the output's
    multiply-add (9 elementwise operations), and a skip's multiply-add a
    channel. The backward pass is two such passes (the states again, the
    adjoint recurrence) and reads ``dy``, writes ``du`` and ``d dt``."""
    return {"ops": tokens * inner * (9 * state + 2),
            "bytes": tokens * (inner * (2 * itemsize + 4)
                               + 2 * state * itemsize)}
