"""The ``"ssm"`` mixer: Mamba-2 (input projection, causal depthwise
convolution, the chunked scan of :mod:`horovod_tpu.ops.ssd`, gated RMSNorm,
output projection). The gated norm runs over each of ``ssm_groups`` groups'
channels, so a group of its heads is a smaller mixer whose parameters are
slices of the whole's. It runs on the sequence and the heads one rank holds:
under a bound tp or sp axis it raises, and its parameters are replicated."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ....ops.conv import causal_conv_silu
from ....ops.ssd import ssd_chunked
from ..config import GPTConfig, LayerSpec
from ..parts import _refuse_bound_axes, _rmsnorm, readings, subkeys

KEY, NORM = "ssm", "ssm_norm"
# The scan's output, 2 H P bytes a token a layer: with it the gated norm, the
# output projection and the rest of the block are made again without the
# scan's output product, and on the chip the step's peak memory is lower than
# without it (PERF.md, Findings, PR 29). The input projection, the
# convolution, the decays and the chunk states stay recomputed.
SAVED_NAMES = ("ssm_scan_out",)


def scope(spec: LayerSpec) -> str:
    return "ssm"


def inner(cfg: GPTConfig) -> int:
    return cfg.ssm_heads * cfg.ssm_head_dim


def conv_dim(cfg: GPTConfig) -> int:
    """The convolved channels: x, B and C side by side."""
    return inner(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    """Initialised as the published Mamba-2 code does: ``A`` uniform in [1,
    16], the step size log-uniform in [1e-3, 1e-1] (``dt_bias`` its inverse
    soft-plus), the skip at one, the convolution as torch's ``Conv1d``
    (uniform within one over the square root of its taps)."""
    E, H, width, conv = cfg.embed_dim, cfg.ssm_heads, inner(cfg), conv_dim(cfg)
    bound, k = 1.0 / float(np.sqrt(cfg.ssm_conv)), subkeys(keys, 6)

    def uniform(key, shape, low=-bound, high=bound):
        return jax.random.uniform(key, shape, jnp.float32, low, high)

    def dt_bias():
        dt = jnp.exp(uniform(k(2), (H,), 0.0, 1.0)
                     * float(np.log(1e-1) - np.log(1e-3))
                     + float(np.log(1e-3)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))

    return {name: (P(), make) for name, make in {
        "in_proj": lambda: dense(k(0), (E, width + conv + H), E),
        "conv_w": lambda: uniform(k(1), (cfg.ssm_conv, conv)),
        "conv_b": lambda: uniform(k(5), (conv,)),
        "dt_bias": dt_bias,
        "A_log": lambda: jnp.log(uniform(k(3), (H,), 1.0, 16.0)),
        "D": lambda: jnp.ones((H,), jnp.float32),
        "norm": lambda: jnp.ones((width,), jnp.float32),
        "out_proj": lambda: dense(k(4), (width, E), width),
    }.items()}


init, specs = readings(_parameters)


def apply(cfg: GPTConfig, spec, p, h, positions):
    """A Mamba-2 mixer on normed activations ``h`` ``[B, S, E]``: ``[z | xBC
    | dt] = h W_in``; ``xBC`` through the causal depthwise convolution and
    SiLU, split into ``x``, ``B``, ``C``; ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``, both float32; the chunked scan; ``RMSNorm(y *
    silu(z))`` with the mean square taken over each of ``ssm_groups``
    groups' channels (one group: the whole inner width), so that a group of
    heads is a mixer of its own up to ``W_out``'s sum; ``W_out``. The scan
    starts every sequence a rank holds from a zero state and the norm runs
    over the heads it holds, so a bound sp or tp axis is refused by name."""
    _refuse_bound_axes(cfg, "state-space")
    batch, seq = h.shape[:2]
    heads, width = cfg.ssm_heads, inner(cfg)
    groups, state = cfg.ssm_groups, cfg.ssm_state
    with jax.named_scope("in_proj"):
        zxbcdt = jnp.einsum("bse,ef->bsf", h, p["in_proj"].astype(cfg.dtype))
        z, _, dt = jnp.split(
            zxbcdt, [width, width + conv_dim(cfg)], axis=-1)
    with jax.named_scope("conv"):
        # xBC read in place, out of the projection's output; tokens on the
        # lanes, as the scan's kernels read x, B and C.
        xbc = causal_conv_silu(zxbcdt, p["conv_w"], p["conv_b"], first=width,
                               minor="tokens")
        x, b_in, c_in = jnp.split(
            xbc, [width, width + groups * state], axis=-1)
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        y, _ = ssd_chunked(
            x.reshape(batch, seq, heads, cfg.ssm_head_dim), dt,
            -jnp.exp(p["A_log"]), b_in.reshape(batch, seq, groups, state),
            c_in.reshape(batch, seq, groups, state), p["D"],
            chunk=cfg.ssm_chunk, dtype=cfg.dtype)
        y = checkpoint_name(y, "ssm_scan_out")
    with jax.named_scope("gate_norm"):
        gated = y.reshape(batch, seq, width).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        # One group is the whole inner width as it lies: these reshapes are
        # then no operation (granite's program stays as it was).
        by_group = (groups, width // groups) if groups > 1 else (width,)
        y = _rmsnorm(gated.reshape(batch, seq, *by_group),
                     p["norm"].reshape(by_group), cfg.dtype,
                     cfg.norm_eps).reshape(batch, seq, width)
    with jax.named_scope("out_proj"):
        return jnp.einsum("bsf,fe->bse", y, p["out_proj"].astype(cfg.dtype))
