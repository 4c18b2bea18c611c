"""The ``"diff_attention"`` and ``"diff_cross"`` mixers: differential
attention (Ye et al., arXiv:2410.05258), whose map is the difference of two
softmax maps. The layer's query and key heads are taken in pairs (heads ``2j``
and ``2j + 1``; any fixed pairing is a permutation of the projections'
columns), a pair's two value heads side by side as one of twice the width::

    A_i = softmax(q_i k_i^T / sqrt(D))  under the causal mask or the window
    o   = A_1 v - lam A_2 v
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
    lam_init = 0.8 - 0.6 exp(-0.3 depth)
    out = (RMSNorm_2D(o) g (1 - lam_init)) W_o

``A_1 v`` and ``A_2 v`` are two calls of the attention ``parts._attention``
picks (the flash kernels at keys of ``D`` beside values of ``2 D``), under
the layer's scope; the combination and its norm are under ``diff`` inside
it. No position embedding: the layers between carry position. ``depth`` is
``LayerSpec.depth``, the layer's index in the published model.

``diff_attention`` projects its own keys and values and, where
``LayerSpec.publishes`` names ``PUBLISHES[0]``, hands ``(k_1, k_2, v)`` on;
``diff_cross`` has a query and an output projection only and reads them
(``READS``): cross-attention to an earlier layer's keys and values of the
same sequence, under the causal mask. The two are one module twice in
``MIXERS``: what differs is which projections the layer has, which the
table of parameters reads off the mixer's name."""

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .... import runtime
from ..config import GPTConfig, LayerSpec
from ..parts import _attention, _rmsnorm, _tp_psum, made, placed

KEY, NORM, SAVED_NAMES = None, "attn_norm", ()
PUBLISHES = ("diff_kv",)
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _parameters(cfg: GPTConfig, keys, dense, cross: bool) -> dict:
    H, Hkv, D, E = cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.embed_dim
    if H % 2 or Hkv % 2:
        raise ValueError(f"differential attention pairs its heads: {H} "
                         f"query and {Hkv} key/value heads")
    tp = cfg.tp_axis
    table = {"wq": (P(None, tp, None), lambda: dense(keys[0], (E, H, D), E)),
             "wo": (P(tp, None, None),
                    lambda: dense(keys[3], (H // 2, 2 * D, E), H * D))}
    if not cross:
        table["wk"] = (P(None, tp, None),
                       lambda: dense(keys[1], (E, Hkv, D), E))
        table["wv"] = (P(None, tp, None),
                       lambda: dense(keys[2], (E, Hkv, D), E))
    for i, name in enumerate(_LAMBDAS):
        table[name] = (P(), lambda i=i: 0.1 * jax.random.normal(
            jax.random.fold_in(keys[0], 1 + i), (D,), jnp.float32))
    table["subln"] = (P(), lambda: jnp.ones((2 * D,), jnp.float32))
    return table


def _pairs(t):
    """``[B, S, H, D]`` -> the first and the second of each pair of heads."""
    return t[:, :, 0::2], t[:, :, 1::2]


def _apply(cfg: GPTConfig, spec: LayerSpec, lp, h, kv=None):
    if spec.depth is None:
        raise ValueError(
            "a differential attention layer's lambda_init follows from its "
            f"index in the published model: give LayerSpec.depth, got {spec}")
    f32 = jnp.float32
    q1, q2 = _pairs(jnp.einsum("bse,ehd->bshd", h,
                               lp["wq"].astype(cfg.dtype)))
    if kv is None:
        k1, k2 = _pairs(jnp.einsum("bse,ehd->bshd", h,
                                   lp["wk"].astype(cfg.dtype)))
        v = jnp.einsum("bse,ehd->bshd", h, lp["wv"].astype(cfg.dtype))
        # A pair's two value heads side by side: heads 2j and 2j + 1 lie so.
        v = v.reshape(v.shape[:2] + (v.shape[2] // 2, 2 * v.shape[3]))
        kv = (k1, k2, v)
    k1, k2, v = kv
    runtime.note_traced(
        "hvdtpu_spmd_diff_attention_traces_total", pairs=q1.shape[2],
        kv_pairs=k1.shape[2], head_dim=cfg.head_dim,
        window=spec.window or 0, cross=str(bool(spec.reads)).lower())
    a1 = _attention(cfg, q1, k1, v, spec.window)
    a2 = _attention(cfg, q2, k2, v, spec.window)
    with jax.named_scope("diff"):
        init = lambda_init(spec.depth)
        lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
            - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init
        o = a1.astype(f32) - lam * a2.astype(f32)
        o = _rmsnorm(o, lp["subln"] * (1.0 - init), cfg.dtype, cfg.norm_eps)
    out = jnp.einsum("bshd,hde->bse", o, lp["wo"].astype(cfg.dtype))
    return _tp_psum(out, cfg), kv


class _Mixer:
    """One of the two mixers: the module's names, with the parameters and
    the values that cross layers its kind has."""
    KEY, NORM, SAVED_NAMES = KEY, NORM, SAVED_NAMES

    def __init__(self, cross: bool):
        self.cross = cross
        self.READS = PUBLISHES if cross else ()
        self.PUBLISHES = () if cross else PUBLISHES

    def scope(self, spec: LayerSpec) -> str:
        """``attn_cross``; else as the ``"attention"`` mixer's: a window
        layer's under ``attn_window``, a full one's under ``attn``."""
        return "attn_cross" if self.cross \
            else "attn" if spec.window is None else "attn_window"

    def init(self, keys, cfg, dense, norm):
        return made(_parameters(cfg, keys, dense, self.cross))

    def specs(self, cfg):
        return placed(_parameters(cfg, None, None, self.cross))

    def apply(self, cfg, spec, lp, h, positions, kv=None):
        out, kv = _apply(cfg, spec, lp, h, kv)
        return out if self.cross else (
            out, {name: kv for name in spec.publishes})


SELF, CROSS = _Mixer(False), _Mixer(True)
