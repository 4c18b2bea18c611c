"""Operations and bytes from shapes: the benchmark's own yardstick.

Every count is of floating-point operations, two per multiply-accumulate.
Only matrix work is counted (convolutions, matmuls, attention products):
normalisation, activation, soft-max and optimizer arithmetic are not, as is
usual for model FLOP/s utilization. A training step needs the forward pass
once and, for the backward pass, one product for the gradient of each input
and one for the gradient of each weight, so three times the forward count;
recomputation (``remat``) is not needed by the mathematics and is not counted.
"""

from __future__ import annotations


def conv2d_flops(out_hw: int, kernel: int, cin: int, cout: int) -> int:
    """One image through one square convolution with a square output."""
    return 2 * out_hw * out_hw * kernel * kernel * cin * cout


def resnet_bottleneck_flops(in_hw: int, cin: int, filters: int, stride: int,
                            expansion: int = 4) -> int:
    """Forward operations of one v1.5 bottleneck block for one image:
    1x1 -> 3x3 (carries the stride) -> 1x1, plus the 1x1 projection on the
    shortcut where the shape changes."""
    out_hw = in_hw // stride
    cout = filters * expansion
    total = conv2d_flops(in_hw, 1, cin, filters)
    total += conv2d_flops(out_hw, 3, filters, filters)
    total += conv2d_flops(out_hw, 1, filters, cout)
    if stride != 1 or cin != cout:
        total += conv2d_flops(out_hw, 1, cin, cout)
    return total


def _resnet_stem_flops(image_size: int, num_filters: int) -> int:
    return conv2d_flops(image_size // 2, 7, 3, num_filters)


def resnet_forward_flops(image_size: int, stage_sizes, num_filters: int,
                         num_classes: int, expansion: int = 4) -> int:
    """Forward operations of a bottleneck ResNet for one image."""
    total = _resnet_stem_flops(image_size, num_filters)
    hw = image_size // 4            # 7x7 stride 2, then 3x3 max-pool stride 2
    cin = num_filters
    for i, blocks in enumerate(stage_sizes):
        filters = num_filters * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            total += resnet_bottleneck_flops(hw, cin, filters, stride,
                                             expansion)
            hw //= stride
            cin = filters * expansion
    return total + 2 * cin * num_classes


def resnet_train_flops(image_size: int, stage_sizes, num_filters: int,
                       num_classes: int, expansion: int = 4) -> int:
    """Forward and backward for one image. The first convolution's input is
    the image, whose gradient nobody needs."""
    fwd = resnet_forward_flops(image_size, stage_sizes, num_filters,
                               num_classes, expansion)
    return 3 * fwd - _resnet_stem_flops(image_size, num_filters)


def gpt_layer_forward_flops(seq_len: int, embed: int, heads: int,
                            kv_heads: int, head_dim: int, mlp: int) -> int:
    """Forward operations of one decoder layer for ONE TOKEN of a causal
    sequence of ``seq_len``: q/k/v/o projections, the two MLP matrices, and
    the score and value products over the (seq_len + 1) / 2 keys a token
    sees on average."""
    proj = 2 * embed * (heads + 2 * kv_heads) * head_dim \
        + 2 * heads * head_dim * embed
    attn = causal_pairs(seq_len) * 4 * heads * head_dim // seq_len
    return proj + 4 * embed * mlp + attn


def gpt_train_flops(seq_len: int, layers: int, embed: int, heads: int,
                    kv_heads: int, head_dim: int, mlp: int, vocab: int) -> int:
    """Forward and backward for one token. The embedding is a gather."""
    fwd = layers * gpt_layer_forward_flops(seq_len, embed, heads, kv_heads,
                                           head_dim, mlp) + 2 * embed * vocab
    return 3 * fwd


def causal_pairs(seq_len: int) -> int:
    """Query-key pairs a causal mask keeps in one sequence."""
    return seq_len * (seq_len + 1) // 2


def flash_forward_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                       head_dim: int, itemsize: int = 2) -> dict:
    """What causal attention needs for one forward call: the score and the
    value product for every kept pair; q and o at ``heads``, k and v at
    ``kv_heads`` (grouped-query) read or written once, and one float32
    log-sum-exp per query row."""
    pairs = batch * heads * causal_pairs(seq_len)
    rows = batch * seq_len
    return {
        "ops": 4 * head_dim * pairs,
        "bytes": rows * head_dim * itemsize * 2 * (heads + kv_heads)
        + rows * heads * 4,
    }


def flash_backward_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                        head_dim: int, itemsize: int = 2) -> dict:
    """What the backward of causal attention needs: five products per kept
    pair (scores again, dP, dV, dK, dQ). A kernel that recomputes scores and
    dP once for dK/dV and once more for dQ does seven; the two extra are the
    implementation's, not the algorithm's. Reads q, k, v, o, dO and the
    log-sum-exp, writes dQ, dK, dV."""
    pairs = batch * heads * causal_pairs(seq_len)
    rows = batch * seq_len
    return {
        "ops": 10 * head_dim * pairs,
        "bytes": rows * head_dim * itemsize * (4 * heads + 4 * kv_heads)
        + rows * heads * 4,
    }


def roofline_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for ``cost`` and which bound sets it."""
    compute = cost["ops"] / peak["bf16_flops_per_s"]
    memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
