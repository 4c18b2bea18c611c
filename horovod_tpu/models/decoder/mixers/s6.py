"""The ``"s6"`` mixer: Mamba-1 (Gu and Dao, arXiv:2312.00752): input
projection, causal depthwise convolution, a step size, ``B`` and ``C``
projected **from the convolved channels**, the selective scan of
:mod:`horovod_tpu.ops.s6` (one decay a channel and state), the gate, the
output projection. A layer whose ``LayerSpec.publishes`` names
``PUBLISHES[0]`` also hands its scan's output on, before the gate, to the
Gated Memory Units behind it (``mixers/gmu.py``). It runs on the sequence
and the channels one rank holds: under a bound tp or sp axis it raises, and
its parameters are replicated."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .... import runtime
from ....ops import s6 as scan
from ....ops.conv import causal_conv_silu
from ..config import GPTConfig, LayerSpec
from ..parts import _refuse_bound_axes, readings, subkeys

KEY, NORM = "s6", "s6_norm"
# What a layer may hand on to later layers: its scan's output ``[B, S,
# s6_inner]`` in the compute dtype (``gmu.READS``).
PUBLISHES = ("s6_scan",)
# The scan's output, 2 s6_inner bytes a token a layer (the published value is
# the same array): with it and the states entering the scan's blocks of
# tokens, which ``ops/s6.py`` names, the recomputed copy of a block runs no
# scan kernel. The projections, the convolution and the step size are made
# again.
SAVED_NAMES = ("s6_scan_out",)


def scope(spec: LayerSpec) -> str:
    return "s6"


def dt_rank(cfg: GPTConfig) -> int:
    return cfg.s6_dt_rank or -(-cfg.embed_dim // 16)


def _published(y, gated):
    """What a publishing layer hands on: the scan's output with the skip,
    **before** the gate (a reading of the published code that
    ``benchmarks/configs/phi-4-mini-flash-reasoning.json`` lists under
    ``assumed``; ``scripts/check_sweep.py --variant memory_after_gate`` is
    the other one, which the cell's check tells apart)."""
    return y


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    """Initialised as the published Mamba code does: ``A[c, n] = -(n + 1)``,
    the step size log-uniform in [1e-3, 1e-1] (``dt_bias`` its inverse
    soft-plus) under a ``dt_proj`` uniform within ``dt_rank ** -0.5``, the
    skip at one, the convolution as torch's ``Conv1d``."""
    E, C, N, R = cfg.embed_dim, cfg.s6_channels, cfg.ssm_state, dt_rank(cfg)
    bound, k = 1.0 / float(np.sqrt(cfg.ssm_conv)), subkeys(keys, 7)

    def uniform(key, shape, low, high):
        return jax.random.uniform(key, shape, jnp.float32, low, high)

    def dt_bias():
        dt = jnp.exp(uniform(k(4), (C,), 0.0, 1.0)
                     * float(np.log(1e-1) - np.log(1e-3))
                     + float(np.log(1e-3)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))

    return {name: (P(), make) for name, make in {
        "in_proj": lambda: dense(k(0), (E, 2 * C), E),
        "conv_w": lambda: uniform(k(1), (cfg.ssm_conv, C), -bound, bound),
        "conv_b": lambda: uniform(k(2), (C,), -bound, bound),
        "x_proj": lambda: dense(k(3), (C, R + 2 * N), C),
        "dt_proj": lambda: uniform(k(5), (R, C), -R ** -0.5, R ** -0.5),
        "dt_bias": dt_bias,
        "A_log": lambda: jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (C, N))),
        "D": lambda: jnp.ones((C,), jnp.float32),
        "out_proj": lambda: dense(k(6), (C, E), C),
    }.items()}


init, specs = readings(_parameters)


def apply(cfg: GPTConfig, spec: LayerSpec, p, h, positions):
    """A Mamba-1 mixer on normed activations ``h`` ``[B, S, E]``: ``[u | z]
    = h W_in``; ``u <- silu(conv(u) + b)``; ``[r | B | C] = u W_x``; ``dt =
    softplus(r W_dt + b_dt)``, ``A = -exp(A_log)``, both float32; the
    selective scan ``y``; ``(y * silu(z)) W_out``. Returns ``(the branch,
    {name: y} for the names the layer publishes)``. The scan starts every
    sequence a rank holds from a zero state, so a bound sp or tp axis is
    refused by name."""
    _refuse_bound_axes(cfg, "selective-scan")
    f32 = jnp.float32
    width, state, rank = cfg.s6_channels, cfg.ssm_state, dt_rank(cfg)
    runtime.note_traced(
        "hvdtpu_spmd_s6_traces_total", channels=width, state=state,
        dt_rank=rank, conv=cfg.ssm_conv,
        publishes=str(bool(spec.publishes)).lower())
    with jax.named_scope("in_proj"):
        uz = jnp.einsum("bse,ef->bsf", h, p["in_proj"].astype(cfg.dtype))
        z = uz[..., width:]
    with jax.named_scope("conv"):
        # u read in place, out of the projection's output; channels on the
        # lanes, as the scan's kernels read it.
        u = causal_conv_silu(uz, p["conv_w"], p["conv_b"], minor="channels")
    with jax.named_scope("x_proj"):
        r, b_in, c_in = jnp.split(
            jnp.einsum("bsc,cf->bsf", u, p["x_proj"].astype(cfg.dtype)),
            [rank, rank + state], axis=-1)
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(jnp.einsum(
            "bsr,rc->bsc", r, p["dt_proj"].astype(cfg.dtype),
            preferred_element_type=f32) + p["dt_bias"])
        y = scan.selective_scan(u, dt, -jnp.exp(p["A_log"]), b_in, c_in,
                                p["D"])
        y = checkpoint_name(y, "s6_scan_out")
    with jax.named_scope("gate"):
        gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
    with jax.named_scope("out_proj"):
        out = jnp.einsum("bsc,ce->bse", gated, p["out_proj"].astype(cfg.dtype))
    return out, {name: _published(y, gated) for name in spec.publishes}
