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
from jax.sharding import PartitionSpec as P

from ....ops.attention import rope
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
    attn = _attention(cfg, q, k, v, spec.window)
    if cfg.attention_gate:
        attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(cfg.dtype)
    o = jnp.einsum("bshd,hde->bse", attn, lp["wo"].astype(cfg.dtype))
    return _tp_psum(o, cfg)
