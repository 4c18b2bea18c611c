"""What every sublayer of the decoder shares: the RMSNorms, the sum over a
bound tp axis, how a branch joins the residual stream, the refusal of bound
axes, :func:`_attention` (**the one place that chooses the attention
kernel**), and the two readings of a parameter table (:func:`made`,
:func:`placed`). Imports ``config`` and nothing else of ``models/decoder``."""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.attention import default_attention, repeat_kv_heads
from ...ops.flash_attention import flash_attention
from ...parallel.axes import axis_size as _axis_size, axis_bound as _axis_bound
from ...parallel.ring_attention import ring_attention_p
from ...parallel.ulysses import ulysses_attention_p
from .config import GPTConfig


def made(table: dict) -> dict:
    """The initial values of a parameter table: ``name -> (PartitionSpec, a
    function of nothing that makes the value)``, or a table again (a
    sub-dict). A sublayer writes its parameters once, as such a table."""
    return {name: made(entry) if isinstance(entry, dict) else entry[1]()
            for name, entry in table.items()}


def placed(table: dict) -> dict:
    """The PartitionSpecs of a parameter table (no value is made)."""
    return {name: placed(entry) if isinstance(entry, dict) else entry[0]
            for name, entry in table.items()}


def readings(parameters) -> tuple:
    """A sublayer's ``(init, specs)`` off its table ``parameters(cfg, keys,
    ...)``, which cannot disagree on a key: ``init(keys, cfg, ...)`` makes it
    with the layer's keys, ``specs(cfg, ...)`` reads it with none."""
    return (lambda keys, cfg, *rest: made(parameters(cfg, keys, *rest)),
            lambda cfg, *rest: placed(parameters(cfg, None, *rest)))


def subkeys(keys, count: int, fold=None):
    """``i ->`` the ``i``-th of ``count`` keys split from a sublayer's first
    (folded with ``fold``), split when a value is made: ``specs`` has none."""
    split = functools.cache(lambda: jax.random.split(
        keys[0] if fold is None else jax.random.fold_in(keys[0], fold), count))
    return lambda i: split()[i]


def _rmsnorm(x, w, dtype, eps, zero_centered: bool = False):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    if zero_centered:
        w = 1.0 + w
    return (x32 * lax.rsqrt(var + eps) * w).astype(dtype)


def _layernorm(x, w, b, dtype, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * w + b).astype(dtype)


def _norm(cfg: GPTConfig, x, w):
    """The model's norm over the last axis: an RMSNorm, ``1 + w`` if the
    configuration centres its weights at zero; where ``w`` is a stream
    norm's ``{"weight", "bias"}`` (``GPTConfig.norm_kind = "layer"``), a
    LayerNorm."""
    if isinstance(w, dict):
        return _layernorm(x, w["weight"], w["bias"], cfg.dtype, cfg.norm_eps)
    return _rmsnorm(x, w, cfg.dtype, cfg.norm_eps, cfg.norm_zero_centered)


def _projection_norm(x, w, cfg: GPTConfig):
    """RMSNorm of ``[B, S, heads, D]`` over all heads together, the heads
    possibly sharded over tp."""
    x32 = x.astype(jnp.float32)
    total = _tp_psum(jnp.sum(x32 * x32, axis=(-2, -1), keepdims=True), cfg)
    width = x.shape[-2] * x.shape[-1] * _axis_size(cfg.tp_axis)
    return (x32 * lax.rsqrt(total / width + cfg.norm_eps) * w).astype(
        cfg.dtype)


def _tp_psum(x, cfg: GPTConfig):
    if _axis_bound(cfg.tp_axis):
        return lax.psum(x, cfg.tp_axis)
    return x


_ATTENTION_KINDS = ("flash", "dense", "ring", "ulysses")


def _attention(cfg: GPTConfig, q, k, v, window: Optional[int] = None,
               heads_major: bool = False):
    """Which attention runs: the one place that decides, and this table is
    the whole rule. No row falls back to another. ``heads_major`` is a
    mixer's word on the layouts round the flash kernels where they run on
    the sequence a rank holds (``flash_attention``); no other row reads it.
    ``window`` (a layer's,
    ``LayerSpec.window``) goes to whichever runs: the flash kernels and the
    dense reference take it, Ulysses hands it to the kernel it calls, and
    ring attention under a bound sp axis refuses a window layer by name
    (its hops wholly outside the band are not skipped yet). The
    block-diffusion mask (``cfg.diffusion_block``: the sequence a rank
    holds is the noised and the clean copy of its positions) goes to the
    flash kernels and the dense reference alike; ring attention and Ulysses
    under a bound sp axis refuse it by name (a shard of the rows is neither
    half).

    ============  ==================  ===============================
    attention     sp axis not bound   sp axis bound
    ============  ==================  ===============================
    ``flash``     flash kernel        ValueError
    ``dense``     dense reference     ValueError
    ``ring``      flash kernel        ``ring_attention_p``
    ``ulysses``   flash kernel        ``ulysses_attention_p`` (flash
                                      kernel on each device)
    ============  ==================  ===============================

    ``flash`` and ``dense`` attend the sequence a rank holds, so under a
    bound sp axis they would attend a shard to itself. ``dense`` is
    :func:`horovod_tpu.ops.attention.default_attention`, S x S logits and
    all: the reference the tests compare against."""
    kind, sp = cfg.attention, cfg.sp_axis
    if kind not in _ATTENTION_KINDS:
        raise ValueError(f"unknown attention {kind!r} "
                         f"(expected one of {_ATTENTION_KINDS})")
    block = cfg.diffusion_block
    if not _axis_bound(sp):
        if kind == "dense":
            # The reference takes equal head counts (ring and Ulysses tile
            # K/V up themselves; the flash kernels read them as they are).
            return default_attention(q, repeat_kv_heads(k, q.shape[2]),
                                     repeat_kv_heads(v, q.shape[2]),
                                     causal=True, window=window,
                                     block_diffusion=block)
        return flash_attention(q, k, v, causal=True, window=window,
                               block_diffusion=block,
                               heads_major=heads_major)
    if block is not None and kind in ("ring", "ulysses"):
        raise ValueError(
            f"attention={kind!r} under the bound {sp!r} axis has no "
            f"block_diffusion mask (diffusion_block={block}): a rank's "
            "shard of the rows is neither the noised nor the clean half; "
            "bind no sp axis")
    if kind == "ring":
        if window is not None:
            raise ValueError(
                f"attention='ring' under the bound {sp!r} axis has no "
                f"window: a layer with window={window} would pass every "
                "hop, those wholly outside its band too; use 'ulysses'")
        return ring_attention_p(q, k, v, causal=True, axis=sp)
    if kind == "ulysses":
        return ulysses_attention_p(
            q, k, v, causal=True, axis=sp,
            attn_fn=functools.partial(flash_attention, window=window))
    raise ValueError(
        f"attention={kind!r} is local attention: under the bound "
        f"{sp!r} axis each rank would attend its own sequence shard only; "
        "use 'ring' or 'ulysses'")


def _refuse_bound_axes(cfg: GPTConfig, what: str) -> None:
    for axis in (cfg.sp_axis, cfg.tp_axis):
        if _axis_bound(axis):
            raise ValueError(
                f"a {what} layer runs on one rank's whole sequence and "
                f"all its heads: the {axis!r} axis is bound (sp would scan "
                "each sequence shard from a zero state, tp would hold a "
                "shard of the heads); bind neither")


def _residual(cfg: GPTConfig, x, branch, scaling=None):
    """The stream after a sublayer: ``x + branch`` (the branch times
    ``residual_multiplier``), or under ``residual_scaling``, with the
    sublayer's four vectors ``scaling``, ``a_r (x + b_r) + a_h (branch +
    b_h)`` in float32, rounded once."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    if scaling is None:
        return x + branch
    f32 = jnp.float32
    with jax.named_scope("res_scale"):
        return (scaling["stream_scale"]
                * (x.astype(f32) + scaling["stream_bias"])
                + scaling["branch_scale"]
                * (branch.astype(f32) + scaling["branch_bias"])
                ).astype(cfg.dtype)

