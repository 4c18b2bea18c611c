"""The ``"gdn"`` mixer: gated-delta-rule linear attention (input
projections, the causal depthwise convolution, the chunked scan of
:mod:`horovod_tpu.ops.gated_delta` at key and value heads of any size, the
writing strength a sigmoid or twice one, an RMSNorm a head and then the
gate, output projection). It runs on the sequence and the heads one rank
holds: under a bound tp or sp axis it raises, and its parameters are
replicated."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ....ops.conv import causal_conv_silu
from ....ops.gated_delta import gated_delta_chunked
from ..config import GPTConfig, LayerSpec
from ..parts import _refuse_bound_axes, _rmsnorm, readings, subkeys

KEY, NORM = "gdn", "gdn_norm"
# The scan's output, 2 Hv V bytes a token a layer: +5.6% on the chip
# (PERF.md, Findings, PR 31). What the scan's backward kernels read beside
# their inputs is ``ops/gated_delta.py``'s to name.
SAVED_NAMES = ("gdn_scan_out",)


def scope(spec: LayerSpec) -> str:
    return "gdn"


def key_inner(cfg: GPTConfig) -> int:
    return cfg.gdn_key_heads * cfg.gdn_key_dim


def value_inner(cfg: GPTConfig) -> int:
    return cfg.gdn_value_heads * cfg.gdn_value_dim


def conv_dim(cfg: GPTConfig) -> int:
    """The convolved channels: q, k and v side by side."""
    return 2 * key_inner(cfg) + value_inner(cfg)


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    """Initialised as the published Qwen3-Next code does (as remembered):
    ``A`` uniform in (0, 16], ``dt_bias`` at one, the gated norm's weight at
    one, the convolution as torch's ``Conv1d`` without a bias."""
    E, Hv = cfg.embed_dim, cfg.gdn_value_heads
    conv, values = conv_dim(cfg), value_inner(cfg)
    bound, k = 1.0 / float(np.sqrt(cfg.gdn_conv)), subkeys(keys, 5)

    return {name: (P(), make) for name, make in {
        # [q | k | v | z] and [b | a]
        "in_proj": lambda: dense(k(0), (E, conv + values), E),
        "in_proj_ba": lambda: dense(k(1), (E, 2 * Hv), E),
        "conv_w": lambda: jax.random.uniform(
            k(2), (cfg.gdn_conv, conv), jnp.float32, -bound, bound),
        "dt_bias": lambda: jnp.ones((Hv,), jnp.float32),
        "A_log": lambda: jnp.log(jnp.maximum(jax.random.uniform(
            k(3), (Hv,), jnp.float32, 0.0, 16.0), 1e-4)),
        "norm": lambda: jnp.ones((cfg.gdn_value_dim,), jnp.float32),
        "out_proj": lambda: dense(k(4), (values, E), values),
    }.items()}


init, specs = readings(_parameters)


def apply(cfg: GPTConfig, spec, p, h, positions):
    """A gated-delta-rule mixer on normed activations ``h`` ``[B, S, E]``:
    ``[q | k | v | z] = h W_qkvz``, ``[b | a] = h W_ba``; ``[q | k | v]``
    through the causal depthwise convolution (no bias) and SiLU; ``q`` and
    ``k`` L2-normalised a head, ``q`` over the root of its size besides
    (inside the scan's chunk-local kernels, ``norm_qk``: the mixer hands
    both over as the convolution wrote them and holds no float32 copy);
    ``beta = sigmoid(b)``, or ``2 sigmoid(b)`` under
    ``cfg.gdn_allow_neg_eigval``, ``g = -exp(A_log) softplus(a + dt_bias)``,
    both float32, one a value head; the chunked scan
    (:func:`horovod_tpu.ops.gated_delta.gated_delta_chunked`, which takes
    key and value heads of any size); an RMSNorm a
    value head (one plain weight of the head's size) and **then** the gate
    ``silu(z)``, where Mamba-2 gates first; ``W_out``. A bound sp or tp axis
    is refused by name, as for a state-space layer."""
    _refuse_bound_axes(cfg, "gated-delta-rule")
    batch, seq = h.shape[:2]
    f32 = jnp.float32
    key_heads, heads = cfg.gdn_key_heads, cfg.gdn_value_heads
    keys = key_inner(cfg)
    with jax.named_scope("in_proj"):
        qkvz = jnp.einsum("bse,ef->bsf", h, p["in_proj"].astype(cfg.dtype))
        z = qkvz[..., conv_dim(cfg):]
        ba = jnp.einsum("bse,ef->bsf", h, p["in_proj_ba"].astype(cfg.dtype))
    with jax.named_scope("conv"):
        # q, k and v read in place, out of the projection's output. Channels
        # on the lanes where the scan's kernels read q, k and v as they
        # leave here; where a head is carried to whole lane tiles first,
        # that copy turns the tensor round and XLA holds it tokens-minor up
        # to there (PERF.md, Findings, PR 38).
        whole = cfg.gdn_key_dim % 128 == 0 and cfg.gdn_value_dim % 128 == 0
        qkv = causal_conv_silu(qkvz, p["conv_w"], None,
                               minor="channels" if whole else "tokens")
        q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)
    with jax.named_scope("scan"):
        b, a = jnp.split(ba.astype(f32), 2, axis=-1)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
        beta_max = 2 if cfg.gdn_allow_neg_eigval else 1
        # q and k as the convolution left them: the scan's kernels norm a
        # head's rows in VMEM and scale q (norm_qk).
        o, _ = gated_delta_chunked(
            q.reshape(batch, seq, key_heads, cfg.gdn_key_dim),
            k.reshape(batch, seq, key_heads, cfg.gdn_key_dim),
            v.reshape(batch, seq, heads, cfg.gdn_value_dim), g,
            jax.nn.sigmoid(b) if beta_max == 1
            else float(beta_max) * jax.nn.sigmoid(b),
            chunk=cfg.gdn_chunk, dtype=cfg.dtype, beta_max=beta_max,
            norm_qk=True)
        o = checkpoint_name(o, "gdn_scan_out")
    with jax.named_scope("gate_norm"):
        y = _rmsnorm(o, p["norm"], f32, cfg.norm_eps) * jax.nn.silu(
            z.reshape(o.shape).astype(f32))
        y = y.reshape(batch, seq, value_inner(cfg)).astype(cfg.dtype)
    with jax.named_scope("out_proj"):
        return jnp.einsum("bsf,fe->bse", y, p["out_proj"].astype(cfg.dtype))
