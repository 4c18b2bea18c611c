"""The ``"attention"`` mixer: softmax attention, column-parallel heads over a
bound tp axis and a row-parallel output projection followed by one ``psum``
(Megatron's pattern through shard_map); sequence-parallel as
``parts._attention`` decides. Optionally an RMSNorm on the whole query and key
projections (``qk_norm``) or a head (``qk_head_norm``), the rotary embedding
left out (``LayerSpec.rope``), given another base or only a head's first
dimensions, the logits scaled by a constant, the output gated by a sigmoid of
a doubled query projection (``attention_gate``), a window
(``LayerSpec.window``), the block-diffusion mask over a noised and a clean
copy of the sequence (``GPTConfig.diffusion_block``). Its four matrices stay
at the layer's root."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from ....ops.attention import rope
from ....ops.pallas_util import LANES
from ..config import GPTConfig, LayerSpec
from ..parts import _attention, _norm, _projection_norm, _tp_psum, readings

KEY, NORM, SAVED_NAMES = None, "attn_norm", ()


def scope(spec: LayerSpec) -> str:
    """A window layer's mixer is under ``attn_window``, a full one's under
    ``attn`` (under the block-diffusion mask ``gpt._block`` says
    ``attn_bd``): a device trace tells their flash kernels apart by it."""
    return "attn" if spec.window is None else "attn_window"


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    H, Hkv, D, E = cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.embed_dim
    tp = cfg.tp_axis
    table = {
        "wq": (P(None, tp, None), lambda: dense(
            keys[0], (E, H, 2 * D if cfg.attention_gate else D), E)),
        "wk": (P(None, tp, None), lambda: dense(keys[1], (E, Hkv, D), E)),
        "wv": (P(None, tp, None), lambda: dense(keys[2], (E, Hkv, D), E)),
        "wo": (P(tp, None, None), lambda: dense(keys[3], (H, D, E), H * D)),
    }
    if cfg.qk_norm:
        table["q_norm"] = (P(tp, None), lambda: jnp.ones((H, D), jnp.float32))
        table["k_norm"] = (P(tp, None),
                           lambda: jnp.ones((Hkv, D), jnp.float32))
    elif cfg.qk_head_norm:
        table["q_norm"] = table["k_norm"] = (P(), lambda: norm((D,)))
    return table


init, specs = readings(_parameters)


@jax.custom_vjp
def _cotangent_sequence_minor(q):
    """``q``, ``[B, S, H, D]``, whose cotangent comes back with the sequence
    minor in memory (``[B, H, D, S]``): the layout in which XLA's product
    ``dq x wq -> dh``, a contraction over ``(h, d)``, reads it fastest, and
    the one it had while ``flash_attention`` copied every cotangent
    (``apply``)."""
    return q


_cotangent_sequence_minor.defvjp(
    lambda q: (q, None),
    lambda _, dq: (with_layout_constraint(
        dq, Layout(major_to_minor=(0, 2, 3, 1))),))


def apply(cfg: GPTConfig, spec: LayerSpec, lp, h, positions):
    """Softmax attention on normed activations ``h``: the projections, the
    norms of q and k, the rotary embedding where ``spec.rope`` says so, the
    attention ``_attention`` picks under ``spec.window``, the output gate
    and the output projection."""
    q = jnp.einsum("bse,ehd->bshd", h, lp["wq"].astype(cfg.dtype))
    k = jnp.einsum("bse,ehd->bshd", h, lp["wk"].astype(cfg.dtype))
    v = jnp.einsum("bse,ehd->bshd", h, lp["wv"].astype(cfg.dtype))
    if cfg.attention_gate:
        q, gate = jnp.split(q, 2, axis=-1)
    if cfg.qk_norm:
        q = _projection_norm(q, lp["q_norm"], cfg)
        k = _projection_norm(k, lp["k_norm"], cfg)
    elif cfg.qk_head_norm:
        q = _norm(cfg, q, lp["q_norm"])
        k = _norm(cfg, k, lp["k_norm"])
    if spec.rope:
        q = rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
        k = rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
    if cfg.attention_multiplier is not None:
        # Every attention here scales its logits by one over the
        # square root of head_dim: the rest goes onto q.
        q = q * (cfg.attention_multiplier
                 * float(np.sqrt(cfg.head_dim)))
    # The turn between these products' ``[B, S, H, D]`` and the flash
    # kernels' ``[B, H, S, D]`` is this mixer's to place. Merged to
    # ``[B*H, S, D]`` (``flash_attention``'s default) it is a copy of q, k, v,
    # the output and every cotangent at a batch of two or more, and nothing
    # at a batch of one, where the merge is a bitcast to XLA. Left at rank 4
    # (``heads_major``), XLA folds it into the neighbours, which here are
    # elementwise (the rotary embedding, the norms, a bias, the gate) but
    # for the products that contract over ``(h, d)`` and read ``[B, H, S, D]``
    # with the sequence between the two, at 2.5 times the compiler's estimate
    # for the sequence-minor read: the output projection, which reads the
    # kernels' output (cheaper than the copies all the same: Ouro), and q's
    # projection backward, ``dq x wq -> dh``, which reads dq through the
    # rotary embedding's transpose alone unless a head's norm, a reduction
    # over ``d`` in float32, stands between and turns it anyway. Without one
    # the cotangent is asked for sequence-minor, one copy kept of the eight:
    # 24:2 heads at 4096 rows lose 2.1% of their tokens a second without it
    # and gain 1.4% with it. Heads of one lane tile alone. Narrower ones are
    # unmeasured (the kernels take any width at rank 4; Granite's estimate
    # reads -0.2%). Of the other mixers the latent-attention one asks for
    # itself since PR 72 (``mixers/mla.py``: 192 beside 128, q's value kept
    # sequence-minor and no cotangent pinned); the CCA and differential
    # ones have not. Wider ones were measured and
    # lost: Qwen's 16:2 heads of 256, one layer in four, 1.03% of a step, and
    # not to a layout: XLA's memory-space assignment stopped prefetching
    # three other layers' output-projection weights in the re-scheduled
    # program (PERF.md, Findings, PR 70 and section 7).
    heads_major = q.shape[0] > 1 and cfg.head_dim == LANES
    if heads_major and not cfg.qk_head_norm:
        q = _cotangent_sequence_minor(q)
    attn = _attention(cfg, q, k, v, spec.window, heads_major=heads_major)
    if cfg.attention_gate:
        attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(cfg.dtype)
    o = jnp.einsum("bshd,hde->bse", attn, lp["wo"].astype(cfg.dtype))
    return _tp_psum(o, cfg)
