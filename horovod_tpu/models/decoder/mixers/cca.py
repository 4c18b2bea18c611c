"""The ``"cca"`` mixer: softmax attention whose q and k are made in a
compressed latent and mixed over the sequence before the heads attend (two
stacked causal convolutions, a q/k mean, an L2 norm a head under a learned
key temperature, half of the value from the token before). It runs under the
scope ``attn`` and through ``parts._attention`` as an ``"attention"`` mixer
does, on one rank's whole sequence and all its heads: a bound sp or tp axis
is refused, and its parameters are replicated."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .... import runtime
from ....ops.cca import cca_mix
from ....parallel.axes import axis_bound as _axis_bound
from ..config import GPTConfig, LayerSpec
from ..parts import _attention, readings, subkeys

KEY, NORM, SAVED_NAMES = "cca", "cca_norm", ()


def scope(spec: LayerSpec) -> str:
    """``attn``, its own parts ``cca_proj`` and ``cca_mix`` inside."""
    return "attn"


def latent(cfg: GPTConfig) -> int:
    """The convolved channels: q and k side by side."""
    return (cfg.num_heads + cfg.kv_heads) * cfg.head_dim


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    """The latent projections ``[q | k]`` and ``[v of the token | v of the
    token before]``, the two convolutions as torch's ``Conv1d`` (uniform
    within one over the square root of the inputs a tap sums times the
    taps), the key heads' temperatures at zero, the output projection."""
    E, D, kv = cfg.embed_dim, cfg.head_dim, cfg.kv_heads * cfg.head_dim
    groups, width = cfg.num_heads + cfg.kv_heads, latent(cfg)
    taps0, taps1, k = *cfg.cca_taps, subkeys(keys, 7)
    if cfg.kv_heads % 2 or cfg.num_heads % cfg.kv_heads:
        raise ValueError(
            "a CCA mixer gives half of its key/value heads the token's "
            "value and half the value of the token before, and a key/value "
            f"head a whole group of query heads: {cfg.num_heads} query and "
            f"{cfg.kv_heads} key/value heads")

    def uniform(key, shape, fan_in):
        bound = 1.0 / float(np.sqrt(fan_in))
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    return {name: (P(), make) for name, make in {
        "wqk": lambda: dense(k(0), (E, width), E),
        "wv": lambda: dense(k(1), (E, kv), E),
        "conv0_w": lambda: uniform(k(2), (taps0, width), taps0),
        "conv0_b": lambda: uniform(k(3), (width,), taps0),
        # [tap, group, channel in, channel out]
        "conv1_w": lambda: uniform(k(4), (taps1, groups, D, D), taps1 * D),
        "conv1_b": lambda: uniform(k(5), (width,), taps1 * D),
        "temp": lambda: jnp.zeros((cfg.kv_heads,), jnp.float32),
        "wo": lambda: dense(k(6), (cfg.num_heads * D, E), cfg.num_heads * D),
    }.items()}


init, specs = readings(_parameters)


def _before(t, tokens: int = 1):
    """``t`` ``[B, S, ...]`` moved ``tokens`` later along the sequence, zeros
    in front: position ``i`` holds what ``i - tokens`` held."""
    if not tokens:
        return t
    pad = ((0, 0), (tokens, 0)) + ((0, 0),) * (t.ndim - 2)
    return jnp.pad(t, pad)[:, :t.shape[1]]


def apply(cfg: GPTConfig, spec: LayerSpec, p, h, positions):
    """A CCA mixer on normed activations ``h`` ``[B, S, E]`` (``Hq`` query
    and ``Hk`` key/value heads of ``D``, ``G = Hq / Hk``): ``u = [q0 | k0] =
    h W_qk``; ``u`` through a causal depthwise convolution and then a causal
    convolution grouped by head (``Hq + Hk`` groups of ``D -> D`` channels),
    each with a bias and neither with an activation; ``q = conv[:Hq D] + qm``
    with ``qm_h = (q0_h + k0_{h // G}) / 2`` and ``k = conv[Hq D:] + km``
    with ``km_g`` the mean of ``qm`` over the group's query heads; ``q`` and
    ``k`` L2-normalised a head to length ``sqrt(D)`` (eps 1e-6 under the
    root), ``k`` times ``exp(temp_g)``, all float32; the rotary embedding
    where ``spec.rope`` says so (all of that, from ``u`` to ``q`` and ``k``,
    one pass of ``ops/cca.py::cca_mix``'s kernels a direction); the first
    half of the value heads ``h W_v`` of the token, the second half that of
    the token before it; the
    attention ``_attention`` picks; ``W_o``. The convolutions and the value
    read the token before on this rank, and the means and the grouped
    stage a key/value head's whole group: a bound sp or tp axis is refused
    by name."""
    for axis, why in ((cfg.sp_axis, "the convolutions and the value of the "
                       "token before would start each sequence shard from "
                       "zeros"),
                      (cfg.tp_axis, "the q/k means and the value's two "
                       "halves cross the heads a rank would hold")):
        if _axis_bound(axis):
            raise ValueError(
                f"a CCA layer runs on one rank's whole sequence and all its "
                f"heads: the {axis!r} axis is bound ({why}); bind neither")
    batch, seq = h.shape[:2]
    heads, kv_heads, dim = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    taps0, taps1 = cfg.cca_taps
    rotary = (cfg.rotary_dim or dim) if spec.rope else 0
    runtime.note_traced(
        "hvdtpu_spmd_cca_traces_total", heads=heads, kv_heads=kv_heads,
        head_dim=dim, taps0=taps0, taps1=taps1, rotary_dim=rotary)
    with jax.named_scope("cca_proj"):
        u = jnp.einsum("bse,ef->bsf", h, p["wqk"].astype(cfg.dtype))
        hv = jnp.einsum("bse,ef->bsf", h, p["wv"].astype(cfg.dtype))
    with jax.named_scope("cca_mix"):
        q, k = cca_mix(u, p["conv0_w"], p["conv0_b"], p["conv1_w"],
                       p["conv1_b"], p["temp"],
                       positions if spec.rope else None, heads=heads,
                       kv_heads=kv_heads, rope_theta=cfg.rope_theta,
                       rotary_dim=cfg.rotary_dim)
        half = hv.shape[-1] // 2
        v = jnp.concatenate([hv[..., :half], _before(hv[..., half:])],
                            axis=-1).reshape(batch, seq, kv_heads, dim)
    attn = _attention(cfg, q, k, v)
    with jax.named_scope("cca_proj"):
        return jnp.einsum("bsf,fe->bse",
                          attn.reshape(batch, seq, heads * dim),
                          p["wo"].astype(cfg.dtype))
