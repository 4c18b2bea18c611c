"""The ``"kda"`` mixer: Kimi delta attention (one input projection to ``[q |
k | v]``, the causal depthwise convolution, a gate a key channel that is
bounded below, the writing strength a head, the chunked scan of
:mod:`horovod_tpu.ops.kda`, an RMSNorm a head under one sigmoid gate a head,
output projection). As many key heads as value heads, of ``kda_key_dim`` and
``kda_value_dim``. It runs on the sequence and the heads one rank holds:
under a bound tp or sp axis it raises, and its parameters are replicated."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ....ops.conv import causal_conv_silu
from ....ops.kda import kda_chunked
from ..config import GPTConfig, LayerSpec
from ..parts import _refuse_bound_axes, _rmsnorm, readings, subkeys

KEY, NORM = "kda", "kda_norm"
# The scan's output, 2 H V bytes a token a layer (67 MB in the Ling cell):
# the gated norm and ``W_o``'s gradient read it, and without it the
# recomputed copy runs both forward kernels for it. What the scan's backward
# kernels read beside their inputs is ``ops/kda.py``'s to name (PERF.md,
# Findings, PR 64).
SAVED_NAMES = ("kda_scan_out",)
_SUB_CHUNK = 16


def scope(spec: LayerSpec) -> str:
    return "kda"


def key_inner(cfg: GPTConfig) -> int:
    return cfg.kda_heads * cfg.kda_key_dim


def value_inner(cfg: GPTConfig) -> int:
    return cfg.kda_heads * cfg.kda_value_dim


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    """``A_log`` and ``dt_bias`` initialised as ``mixers/gdn.py``'s (``A``
    uniform in (0, 16], the bias at one, here one a key channel), the
    output norm's weight at one, the convolution as torch's ``Conv1d``
    without a bias, the gate's projection at full rank."""
    E, H = cfg.embed_dim, cfg.kda_heads
    keys_, values = key_inner(cfg), value_inner(cfg)
    conv = 2 * keys_ + values
    bound, k = 1.0 / float(np.sqrt(cfg.kda_conv)), subkeys(keys, 7)

    return {name: (P(), make) for name, make in {
        "w_qkv": lambda: dense(k(0), (E, conv), E),
        "conv_w": lambda: jax.random.uniform(
            k(1), (cfg.kda_conv, conv), jnp.float32, -bound, bound),
        "w_f": lambda: dense(k(2), (E, keys_), E),
        "dt_bias": lambda: jnp.ones((keys_,), jnp.float32),
        "A_log": lambda: jnp.log(jnp.maximum(jax.random.uniform(
            k(3), (H,), jnp.float32, 0.0, 16.0), 1e-4)),
        "w_beta": lambda: dense(k(4), (E, H), E),
        "w_gate": lambda: dense(k(5), (E, H), E),
        "norm": lambda: jnp.ones((cfg.kda_value_dim,), jnp.float32),
        "out_proj": lambda: dense(k(6), (values, E), values),
    }.items()}


init, specs = readings(_parameters)


def log_decay(cfg: GPTConfig, p, f):
    """The log decay a key channel, float32 ``[B, S, H K]``, of the gate's
    projection ``f = h W_f``: ``lower_bound * sigmoid(exp(A_log_h) (f +
    dt_bias))``, in ``(lower_bound, 0)``."""
    a = jnp.repeat(jnp.exp(p["A_log"]), cfg.kda_key_dim)
    return cfg.kda_lower_bound * jax.nn.sigmoid(
        a * (f.astype(jnp.float32) + p["dt_bias"]))


def head_gate(y, open_):
    """``y`` ``[B, S, H, V]`` under one gate a head, ``open_`` ``[B, S,
    H]``."""
    return y * open_[..., None]


def apply(cfg: GPTConfig, spec, p, h, positions):
    """A Kimi-delta-attention mixer on normed activations ``h`` ``[B, S,
    E]``: ``[q | k | v] = SiLU(conv(h W_qkv))`` (causal, depthwise, no
    bias); ``q`` and ``k`` L2-normalised a head, ``q`` over the root of its
    size besides (inside the scan's kernels); the log decay a key channel
    (:func:`log_decay`); ``beta = sigmoid(h W_beta)`` a head; the chunked
    scan (:func:`horovod_tpu.ops.kda.kda_chunked`); an RMSNorm a head (one
    weight of the head's size) times ``sigmoid(h W_g)``, one gate a head;
    ``W_o``. A bound sp or tp axis is refused by name."""
    _refuse_bound_axes(cfg, "Kimi-delta-attention")
    batch, seq = h.shape[:2]
    f32, heads = jnp.float32, cfg.kda_heads
    keys = key_inner(cfg)
    with jax.named_scope("kda_proj"):
        qkv = jnp.einsum("bse,ef->bsf", h, p["w_qkv"].astype(cfg.dtype))
        f = jnp.einsum("bse,ef->bsf", h, p["w_f"].astype(cfg.dtype))
        small = jnp.einsum(
            "bse,ef->bsf", h, jnp.concatenate(
                [p["w_beta"], p["w_gate"]], axis=1).astype(cfg.dtype))
    with jax.named_scope("kda_conv"):
        qkv = causal_conv_silu(qkv, p["conv_w"], None, minor="channels")
        q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)
    with jax.named_scope("kda_scan"):
        g = log_decay(cfg, p, f)
        beta, open_ = jnp.split(jax.nn.sigmoid(small.astype(f32)), 2,
                                axis=-1)
        o, _ = kda_chunked(
            q.reshape(batch, seq, heads, cfg.kda_key_dim),
            k.reshape(batch, seq, heads, cfg.kda_key_dim),
            v.reshape(batch, seq, heads, cfg.kda_value_dim),
            g.reshape(batch, seq, heads, cfg.kda_key_dim), beta,
            chunk=cfg.kda_chunk, sub_chunk=_SUB_CHUNK,
            lower_bound=cfg.kda_lower_bound,
            dtype=cfg.dtype, norm_qk=True)
        o = checkpoint_name(o, "kda_scan_out")
    with jax.named_scope("kda_gate"):
        y = head_gate(_rmsnorm(o, p["norm"], f32, cfg.norm_eps), open_)
        y = y.reshape(batch, seq, value_inner(cfg)).astype(cfg.dtype)
    with jax.named_scope("kda_proj"):
        return jnp.einsum("bsf,fe->bse", y, p["out_proj"].astype(cfg.dtype))
