"""GPT: the flagship explicitly-parallel decoder-only LM (pure JAX, shard_map).

No reference analog (Horovod is model-agnostic, data-parallel only — SURVEY.md
§2.7); this model exists so the framework's tensor / sequence / data-parallel
mechanisms compose in one first-class consumer, and as the long-context
benchmark family. Parallelism is *explicit* shard_map-style (the TPU-idiomatic
regime): parameters are plain nested dicts with global shapes plus a matching
``PartitionSpec`` pytree (:func:`param_specs`); inside ``run_step`` every rank
computes on its local shard and the model inserts exactly the collectives the
math needs: **tp** (heads and hidden widths column-parallel, output
projections row-parallel and one ``psum`` each), **sp** (activations
sequence-sharded; ring or Ulysses attention, ``decoder/parts.py::_attention``
holds the whole rule), **ep** (an expert block's experts over the axis,
:mod:`horovod_tpu.parallel.moe`) and **dp** (gradient averaging from autodiff
under shard_map(check_vma); ``DistributedOptimizer`` then only normalizes).
bfloat16 activations / fp32 params+accumulators; every norm's weight may be
centred at zero, the head the embedding's transpose, and the embedding, each
residual branch and the logits scaled by a constant. With expert blocks the
loss carries the router's two auxiliary terms (:func:`loss_and_aux`).

This file is the stack: the parameters, a block, what a checkpointed block
keeps, the head and the loss. Its parts live in ``models/decoder``, arrows one
way: ``config`` (:class:`GPTConfig`, :class:`LayerSpec`) <- ``parts`` (norms,
the residual, ``_attention``) <- one module a mixer (``mixers/``: ``MIXERS``)
and a feed-forward (``feed_forward``, ``experts``: ``FEED_FORWARDS``) <- here.
A new mixer is a module and an entry of its table: ``init_params``,
``param_specs`` and ``_block`` look a layer up (:func:`_sublayers`) and name
none. Each module's docstring says what it computes.

**What each layer is, is said once**: ``GPTConfig.plan``, one
:class:`LayerSpec` a layer (the mixer, an attention layer's window and
whether the rotary embedding applies to it, the feed-forward's kind; **either
sublayer may be absent**: the block is then the other one alone with its one
norm), given outright (``GPTConfig.layers``) or resolved from ``layer_kinds``,
``moe_every`` and ``gated_mlp`` in :func:`layer_plan` and nowhere else.
**Where a block's norms sit is said once too**: :func:`norm_placement`
resolves ``GPTConfig.norms`` (``"pre"``: ``x + f(N(x))``, the default;
``"pre_post"``: ``x + N2(f(N1(x)))``; ``"post"``: ``x + N(f(x))``) and the
older ``post_norm`` into ``(a norm before each branch, a norm after it)``.
Under ``residual_scaling`` a sublayer joins the stream as ``a_r (x + b_r) +
a_h (f(N(x)) + b_h)``, four learned vectors a sublayer (``parts._residual``).
**What crosses layers beside the stream is one carry** (:func:`_hidden`): a
mixer may hand a value on to later layers (``LayerSpec.publishes``; a
Mamba-1 layer's scan output, a differential attention layer's keys and
values) and a later one read it (``LayerSpec.reads``; a Gated Memory Unit,
a cross-attention layer), and an MLP router's state from expert block to
expert block rides the same carry; ``layer_plan`` refuses a reader with no
producer before it. The stream's norms are RMSNorms or, under
``norm_kind="layer"``, LayerNorms.
**A looped stack** (``GPTConfig.loop_passes`` > 1, :func:`_passes`) runs the
same layers that many times a step, the norm before the head at the end of
every pass and carried on; a head and a learned exit gate read every pass's
state, and the loss is the exit distribution's expected cross-entropy less
``exit_entropy_coef`` times its entropy (:func:`_exit_distribution`).
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .. import runtime
from ..ops import flash_attention, gated_delta, kda, s6
from ..ops.pallas_util import varying_like
from ..parallel import moe
from ..parallel.axes import axis_bound as _axis_bound
from .decoder import experts, feed_forward
from .decoder.config import (NORM_KINDS, NORMS, GPTConfig,  # noqa: F401
                             LayerSpec, layer_plan, norm_placement)
from .decoder.experts import (ROUTER_READS, ROUTERS,  # noqa: F401
                              trainable, update_router_bias)
from .decoder.mixers import MIXERS
from .decoder.parts import _attention, _norm, _residual  # noqa: F401

FEED_FORWARDS = {"dense": feed_forward, "gated": feed_forward,
                 "experts": experts}


def _sublayers(spec: LayerSpec) -> tuple:
    """A layer's ``(mixer, feed-forward)`` out of the two tables, None for
    the one it has not: the one place a plan's names meet them."""
    if spec.mixer not in (*MIXERS, None) \
            or spec.ff not in (*FEED_FORWARDS, None):
        raise ValueError(
            f"layers / layer_kinds must name a layer's mixer one of "
            f"{tuple(MIXERS)}, its feed-forward one of "
            f"{tuple(FEED_FORWARDS)}, got {spec!r}")
    mixer = MIXERS.get(spec.mixer)
    reads = getattr(mixer, "READS", ())
    publishes = getattr(mixer, "PUBLISHES", ())
    if spec.reads != reads or set(spec.publishes) - set(publishes):
        raise ValueError(f"a {spec.mixer!r} mixer reads {reads} and may "
                         f"publish {publishes}, got {spec!r}")
    return mixer, FEED_FORWARDS.get(spec.ff)


# A sublayer's residual scaling (``GPTConfig.residual_scaling``): on the
# stream and on the branch, a scale at one and a bias at zero each; one key a
# sublayer the layer has.
_RESIDUAL_NAMES = ("stream_scale", "stream_bias", "branch_scale",
                   "branch_bias")
_RESIDUAL_KEYS = ("mixer_res", "mlp_res")


def _norm_names(spec: LayerSpec, before: bool, after: bool) -> list:
    """The keys of a layer's norms over the residual stream: before the
    mixer (the mixer's own ``NORM``: ``attn_norm``, ``ssm_norm``, ...) and
    the feed-forward, after each; of a sublayer the layer has not, none."""
    names = ([getattr(_sublayers(spec)[0], "NORM", None), "mlp_norm"]
             if before else []) \
        + (["mixer_post_norm", "mlp_post_norm"] if after else [])
    return [name for name, has in zip(names, spec.sublayers * 2) if has]


def _tree(cfg: GPTConfig, rng=None) -> dict:
    """The parameter tree in one walk, so that every name is written once:
    the initial values under ``rng``, the PartitionSpecs without one (tp
    shards heads and hidden widths, ep the experts: a sublayer's own say)."""
    make, E = rng is not None, cfg.embed_dim

    def dense(key, shape, fan_in):
        # float() keeps the scale weakly-typed so params stay fp32 under x64.
        return (jax.random.normal(key, shape, jnp.float32) /
                float(np.sqrt(fan_in)))

    def norm(shape):
        return jnp.zeros(shape, jnp.float32) if cfg.norm_zero_centered \
            else jnp.ones(shape, jnp.float32)

    def vector(init=norm):
        return init((E,)) if make else P()

    if cfg.norm_kind not in NORM_KINDS:
        raise ValueError(f"norm_kind must be one of {NORM_KINDS}, got "
                         f"{cfg.norm_kind!r}")

    def stream_norm():
        """A norm over the residual stream: an RMSNorm's weight, or a
        LayerNorm's weight and bias (``parts._norm`` tells them apart)."""
        if cfg.norm_kind == "rms":
            return vector()
        return {"weight": vector(), "bias": vector(functools.partial(
            jnp.zeros, dtype=jnp.float32))}

    before, after = norm_placement(cfg)
    keys = jax.random.split(rng, 2 + cfg.num_layers) if make else None
    tree: dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, E),
                                   jnp.float32) * 0.02 if make else P(),
        "out_norm": stream_norm(), "layers": []}
    if not cfg.tie_embeddings:
        tree["lm_head"] = dense(keys[1], (E, cfg.vocab_size), E) if make \
            else P()
    if cfg.loop_passes > 1:
        # The exit gate of a looped stack: lambda = sigmoid(w . h + b) on a
        # pass's normed state, float32.
        tree["exit_gate"] = {
            "w": jax.random.normal(jax.random.fold_in(keys[1], 1), (E,),
                                   jnp.float32) * 0.02 if make else P(),
            "b": jnp.zeros((), jnp.float32) if make else P()}
    for i, (spec, carry) in enumerate(zip(
            cfg.plan, experts.routers_with_carry(cfg))):
        # A mixer gets the layer's first four keys, a feed-forward the rest.
        ks = jax.random.split(keys[2 + i], 8) if make else None
        mixer, ff = _sublayers(spec)
        layer: dict = {}
        if mixer is not None:
            own = mixer.init(ks[:4], cfg, dense, norm) if make \
                else mixer.specs(cfg)
            layer.update(own if mixer.KEY is None else {mixer.KEY: own})
        for name in _norm_names(spec, before, after):
            layer[name] = stream_norm()
        for name, has in zip(_RESIDUAL_KEYS, spec.sublayers):
            if has and cfg.residual_scaling:
                layer[name] = {
                    part: vector(functools.partial(
                        jnp.ones if part.endswith("scale") else jnp.zeros,
                        dtype=jnp.float32)) for part in _RESIDUAL_NAMES}
        if ff is not None:
            own = ff.init(ks[4:], cfg, spec, carry, dense) if make \
                else ff.specs(cfg, spec, carry)
            layer.update(own if ff.KEY is None else {ff.KEY: own})
        tree["layers"].append(layer)
    return tree


def init_params(rng, cfg: GPTConfig) -> dict:
    """Global-shape parameter pytree (plain dicts; fp32). Shard with
    :func:`param_specs` + ``jax.device_put`` (or pass the specs as
    ``run_step`` in_specs) before feeding a shard_mapped step."""
    return _tree(cfg, rng)


def param_specs(cfg: GPTConfig) -> dict:
    """PartitionSpec pytree matching :func:`init_params`."""
    return _tree(cfg)


# The router state's name in the carry, beside the mixers' published names.
_ROUTER_STATE = "router_state"


def _block(cfg: GPTConfig, spec: LayerSpec, layer_params, x, positions,
           carry=None):
    """One decoder block, as ``spec`` says it is: ``(x, aux, handed on)``,
    ``aux`` the expert layer's auxiliary terms (``parallel/moe.py``) or None
    for a block without one. A block is its mixer's sublayer and then its
    feed-forward's, or the one of the two it has (``spec.sublayers``).

    ``carry`` is what the block is handed beside the stream, by name
    (``_hidden`` holds the one carry and hands a block what it reads): the
    values its mixer reads (``spec.reads``, published by a layer before it)
    and, for an expert block, the router state an MLP router hands from one
    expert block to the next. ``handed on`` is what the block adds to the
    carry: what its mixer publishes (``spec.publishes``) and the new router
    state. Under ``jax.checkpoint`` a read value is an input of the block
    and a published one an output: nothing of the producer is made again
    for a reader, and the producer's backward pass receives the sum of its
    readers' cotangents."""
    carry, handed_on = carry or {}, {}
    # The scopes sit inside the function ``jax.checkpoint`` wraps, so the
    # recomputed copy of a block carries them too (``forward`` has the rest).
    lp = layer_params
    norm_before, norm_after = norm_placement(cfg)
    mixer, ff = _sublayers(spec)
    mixer_res, mlp_res = (lp.get(key) for key in _RESIDUAL_KEYS) \
        if cfg.residual_scaling else (None, None)

    def before(key):
        return _norm(cfg, x, lp[key]) if norm_before else x

    def after(branch, key):
        # The backward pass of a norm after the branch reads the branch's
        # value, and so does a learned scale's gradient in ``_residual``:
        # under either the value is named (``BLOCK_SAVED_NAMES``). A block
        # that adds the branch and nothing else names nothing.
        if norm_after or cfg.residual_scaling:
            branch = checkpoint_name(branch, "branch_out")
        if not norm_after:
            return branch
        with jax.named_scope("post_norm"):
            return _norm(cfg, branch, lp[key])

    early = None
    if ff is experts and cfg.router_reads != "ff_input":
        # A router that reads the block's input gives its outputs here,
        # before the mixer; they cross it to the expert sublayer below. The
        # barrier makes the block's input one value: without it XLA gave the
        # forward router another copy of the stream (the block before's last
        # sum, fused in and rounded otherwise) than the one the checkpoint
        # keeps and the recomputed router reads, and on the chip 106 and 164
        # of 8192 tokens chose other experts in the two passes of two layers
        # (PERF.md, Findings, PR 53); behind it both passes' outputs agree to
        # float32 rounding and no choice differs.
        x = lax.optimization_barrier(x)
        early = experts.early_router(cfg, lp[ff.KEY], x)

    if mixer is not None:
        # Under the block-diffusion mask every mixer is plain attention
        # (``layer_plan``), and its kernels are told apart as ``attn_bd``.
        with jax.named_scope("attn_bd" if cfg.diffusion_block is not None
                             else mixer.scope(spec)):
            branch = mixer.apply(
                cfg, spec, lp if mixer.KEY is None else lp[mixer.KEY],
                before(mixer.NORM), positions,
                *(carry[name] for name in spec.reads))
            if getattr(mixer, "PUBLISHES", ()):
                branch, handed_on = branch
            x = _residual(cfg, x, after(branch, "mixer_post_norm"),
                          mixer_res)

    if ff is None:
        return x, None, handed_on
    with jax.named_scope(ff.SCOPE):
        out, aux, router_state = ff.apply(
            cfg, spec, lp if ff.KEY is None else lp[ff.KEY],
            before("mlp_norm"), carry.get(_ROUTER_STATE), early)
        if router_state is not None:
            handed_on = {**handed_on, _ROUTER_STATE: router_state}
        return _residual(cfg, x, after(out, "mlp_post_norm"),
                         mlp_res), aux, handed_on


# What ``remat="full"`` keeps from a block's forward pass beside its input:
# the values whose recomputation is a kernel, the block's widest matmul or a
# pass over memory that buys nothing. **Each name is declared where it is
# born**, in its module's ``SAVED_NAMES`` beside the ``checkpoint_name``, with
# what it costs and what measured it; here they are gathered. A block that
# produces none of a name keeps nothing under it; norms, rotary and
# projections stay recomputed.
#
# This module's own: a branch's output where the block's own backward pass
# reads it (``_block``'s ``after``: under a norm after the branch or a learned
# residual scale, whose gradient is ``<g, branch + bias>``; 2 E bytes a token
# a sublayer, in token order; without it the recomputed copy runs each branch
# to its last product again, an expert sublayer whole. On the chip (PERF.md,
# Findings, PR 48): ``trinity-mini_s8192`` +8.5% for 10 tensors of 64 MiB,
# ``olmo-hybrid-7b_s8192`` +3.9% for 8 of 60, ``zaya1-8b_s4096`` +4.6%, 12 of 64
BLOCK_SAVED_NAMES = ("branch_out",)
SAVED_NAMES = (flash_attention.SAVED_NAMES + gated_delta.SAVED_NAMES
               + kda.SAVED_NAMES + s6.SAVED_NAMES + moe.SAVED_NAMES
               + BLOCK_SAVED_NAMES + sum(
                   (part.SAVED_NAMES for part in dict.fromkeys(
                       (*MIXERS.values(), *FEED_FORWARDS.values()))), ()))
_save_names = jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)


def _full_policy(prim, *avals, **params):
    """``save_only_these_names(*SAVED_NAMES)``, telling ``hvd.metrics()``
    the bytes of each value it keeps (while JAX splits a block into its
    forward and backward parts: trace time, nothing per step)."""
    saved = _save_names(prim, *avals, **params)
    if saved:
        runtime.note_traced(
            "hvdtpu_spmd_remat_saved_bytes_total",
            avals[0].size * avals[0].dtype.itemsize,
            mode="full", name=params["name"])
    return saved


def _block_fn(cfg: GPTConfig):
    """The per-layer apply ``(cfg, spec, layer_params, x, positions,
    carry)``, optionally wrapped in ``jax.checkpoint`` (cfg and a layer's spec
    are frozen dataclasses, so they ride static_argnums)."""
    if cfg.remat == "none":
        return _block
    if cfg.remat == "full":
        return jax.checkpoint(_block, static_argnums=(0, 1),
                              policy=_full_policy)
    raise ValueError(f"unknown remat mode {cfg.remat!r} "
                     "(expected 'none' or 'full')")


def _passes(params, tokens, positions, cfg: GPTConfig):
    """``([the normed hidden rows [B, S_local, E] at the end of each pass],
    [aux of each expert block, every pass's])``: everything before the
    head's matrix. One pass, one state, unless the stack is looped
    (``cfg.loop_passes``): then the same ``params["layers"]`` run that many
    times, the norm before the head at the end of **every** pass, its output
    both what the head and the exit gate read at that pass and what the next
    pass starts from."""
    # Scopes name the program's parts in every instruction's ``op_name``:
    # ``embed``, ``layer<i>`` (inside, from ``_block``: the mixer's
    # ``scope(spec)`` and the feed-forward's ``SCOPE``, ``mlp`` or ``moe``,
    # each module's own parts within; ``post_norm`` where the configuration
    # norms a branch after it, ``res_scale`` where the residual is scaled;
    # the product of a router that reads the block's input under
    # ``moe/router_early``, before the mixer's scope), ``head``;
    # ``loss_and_aux`` adds ``loss``. A looped stack puts ``pass<t>`` around
    # a pass's ``layer<i>`` and ``head``. A device trace is read by them
    # (PERF.md section 3).
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    block = _block_fn(cfg)
    plan, passes = cfg.plan, cfg.loop_passes
    layers, out_norm = params["layers"], params["out_norm"]
    if passes > 1:
        runtime.note_traced("hvdtpu_spmd_loop_passes_total",
                            passes=passes, layers=len(plan))
        # A replicated parameter read in every pass is marked varying as the
        # rows are once, here: the mark's transpose is the all-reduce of its
        # gradient, and a mark a use would make one a pass.
        layers, out_norm = jax.tree.map(
            lambda p: varying_like(p, x), (layers, out_norm))
    states, auxes = [], []
    for t in range(passes):
        with jax.named_scope(f"pass{t}") if passes > 1 \
                else contextlib.nullcontext():
            # The one carry: what crosses blocks beside the stream, by name.
            # A block is handed what it reads of it and no more (an expert
            # block the router state, a mixer its ``spec.reads``). Nothing
            # of it crosses passes (``layer_plan`` refuses what would).
            carry = {}
            for i, (spec, lp) in enumerate(zip(plan, layers, strict=True)):
                wanted = spec.reads + (
                    (_ROUTER_STATE,) if FEED_FORWARDS.get(spec.ff) is experts
                    else ())
                with jax.named_scope(f"layer{i}"):
                    x, aux, handed_on = block(
                        cfg, spec, lp, x, positions,
                        {name: carry[name] for name in wanted
                         if name in carry})
                carry.update(handed_on)
                for name in spec.publishes:
                    runtime.note_traced(
                        "hvdtpu_spmd_shared_values_total", value=name,
                        producer=i, readers=sum(
                            name in later.reads for later in plan[i + 1:]))
                if aux is not None:
                    auxes.append(aux)
            with jax.named_scope("head"):
                states.append(_norm(cfg, x, out_norm))
            # The next pass starts from the normed state the head and the
            # gate read, not from the stream the norm read.
            x = states[-1]
    return states, auxes


def _hidden(params, tokens, positions, cfg: GPTConfig):
    """``(the last pass's normed hidden rows, the auxes)`` of
    :func:`_passes`: what a caller that wants logits reads."""
    states, auxes = _passes(params, tokens, positions, cfg)
    return states[-1], auxes


def _head_matrix(params, cfg: GPTConfig):
    """The head's matrix in the compute dtype: the embedding ``[V, E]`` where
    the two are tied, else ``lm_head`` ``[E, V]``."""
    name = "embed" if cfg.tie_embeddings else "lm_head"
    return params[name].astype(cfg.dtype)


def _logits(x, w, tied: bool, scaling: float):
    """Float32 logits of the rows ``x`` ``[..., E]``: the product in the
    operands' dtype, read in float32, over ``scaling``."""
    logits = jnp.einsum("...e,ve->...v" if tied else "...e,ev->...v", x, w)
    logits = logits.astype(jnp.float32)
    return logits / scaling if scaling != 1.0 else logits


# The rows of one block of ``_head_loss`` follow from the token count and the
# vocabulary alone (``head_loss_rows``): at most ``_HEAD_LOSS_MOST_ROWS``,
# and fewer where that many rows' logits, read as float32, would pass
# ``_HEAD_LOSS_BLOCK_BYTES`` (a vocabulary over 131,072). Why 2048: the
# head's weight gradient is summed over the blocks in float32, 8 V E bytes
# read and written a block for 2 R V E operations, so a block under a
# thousand rows waits for memory on a v5e (+20 ms a step at V = 100,352),
# and at 4096 the dense cells' steps ran 1.5% slower than at 2048 while a
# block held twice the memory (PERF.md, Findings, PR 41).
_HEAD_LOSS_MOST_ROWS = 2048
_HEAD_LOSS_BLOCK_BYTES = 1 << 30


def head_loss_rows(tokens: int, vocab: int) -> int:
    """Rows of one block of the head-and-loss rule for ``tokens`` rows over
    a vocabulary of ``vocab``: all of them where they fit a block (the two
    constants above); else the largest multiple of 8 that divides
    ``tokens``, fits and is more than half of what fits; else an even split
    into the fewest blocks that fit (the last one is what is left)."""
    most = max(8, min(_HEAD_LOSS_MOST_ROWS,
                      _HEAD_LOSS_BLOCK_BYTES // (4 * vocab)))
    if tokens <= most:
        return tokens
    for rows in range(most - most % 8, most // 2, -8):
        if tokens % rows == 0:
            return rows
    blocks = -(-tokens // most)
    return -(-tokens // (8 * blocks)) * 8


def _note_head_loss(cfg: GPTConfig, tokens: int, rows: int) -> None:
    """Trace time only: tell ``hvd.metrics()`` which blocks the rule got."""
    runtime.note_traced(
        "hvdtpu_spmd_head_loss_traces_total", rows_per_block=rows,
        blocks=-(-tokens // rows), vocab=cfg.vocab_size,
        tied=str(cfg.tie_embeddings).lower())


def _head_loss_block(x, targets, weights, w, tied: bool, scaling: float):
    """One block of rows: ``(summed loss, d loss / d x [R, E], d loss / d w,
    each row's un-weighted cross-entropy [R], zero on a masked row)`` at a
    cotangent of 1, both gradients float32. The block's logits are made
    once and die here. ``weights`` (float32 ``[R]`` or None: ones) multiply
    each row's loss."""
    with jax.named_scope("head"):
        logits = _logits(x, w, tied, scaling)
    with jax.named_scope("loss"):
        valid = targets >= 0
        hit = (lax.broadcasted_iota(jnp.int32, logits.shape, 1)
               == targets[:, None])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        each = lse - picked
        num = jnp.sum(jnp.where(
            valid, each if weights is None else weights * each, 0.0))
        each = jnp.where(valid, each, 0.0)
        # softmax - onehot, zero on a masked row; rounded to the compute
        # dtype once, where autodiff rounds the float32 logits' cotangent.
        rows = valid[:, None]
        d = jnp.exp(logits - lse[:, None]) - hit
        if weights is not None:
            d = weights[:, None] * d
        d = jnp.where(rows, d, 0.0)
        d = (d / scaling if scaling != 1.0 else d).astype(x.dtype)
    with jax.named_scope("head"):
        dx = jnp.einsum("rv,ve->re" if tied else "rv,ev->re", d, w,
                        preferred_element_type=jnp.float32)
        dw = jnp.einsum("rv,re->ve" if tied else "rv,re->ev", d, x,
                        preferred_element_type=jnp.float32)
    return num, dx, dw, each


def _head_loss(x, w, targets, weights, tied: bool, scaling: float, rows: int):
    """The sum of :func:`_head_loss_rows` alone."""
    return _head_loss_rows(x, w, targets, weights, tied, scaling, rows)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head_loss_rows(x, w, targets, weights, tied: bool, scaling: float,
                    rows: int):
    """``(sum, each)``: the summed cross-entropy of the rows ``x`` ``[T, E]``
    under the head ``w`` against ``targets`` ``[T]`` (negative: a masked
    row), each row's times its float32 weight (``weights`` ``[T]``; None:
    ones, and the program is the one without them), as one rule over blocks
    of ``rows`` rows: a block's logits, in float32 their
    log-sum-exp, the block's loss and ``softmax - onehot``, and from that at
    once the block's part of ``d w`` (summed in float32) and its rows of
    ``d x``. No ``[T, V]`` array is made or kept; the backward rule scales
    the two gradients by the sum's cotangent, and **``weights`` that are no
    constants** (a looped stack's exit probabilities) receive that cotangent
    times each row's cross-entropy. ``each`` is those cross-entropies,
    float32 ``[T]``, un-weighted, zero on a masked row: **a reading, through
    which no gradient goes** (the rule drops its cotangent; a caller that
    differentiates stops it)."""
    return _head_loss_fwd(x, w, targets, weights, tied, scaling, rows)[0]


def _head_loss_fwd(x, w, targets, weights, tied, scaling, rows):
    # A Python loop and no ``lax.scan``: the blocks are few, and as a
    # ``while`` they ran 7 to 10 ms behind this on the chip (PERF.md,
    # Findings, PR 41). The last block is the rows that are left.
    num, dx, each, dw = [], [], [], None
    for i in range(0, x.shape[0], rows):
        xb = x[i:i + rows]
        if dw is not None:
            # A block's rows wait for the sum of the weight gradient over the
            # blocks before it, or the scheduler makes every block's logits
            # first and holds them all (16 x 192 MB at 32,768 rows of 49,152:
            # PR 69). At 4 and 8 blocks the wait costs 0.1 to 0.4 ms a step
            # or wins 2.4 (free | chained, PERF.md, Findings, PR 71).
            xb, dw = lax.optimization_barrier((xb, dw))
        num_b, dx_b, dw_b, each_b = _head_loss_block(
            xb, targets[i:i + rows],
            None if weights is None else weights[i:i + rows],
            w, tied, scaling)
        num.append(num_b)
        dx.append(dx_b)
        each.append(each_b)
        dw = dw_b if dw is None else dw + dw_b
    each = jnp.concatenate(each)
    # The empty slice hands the backward rule the compute dtype; the rows'
    # cross-entropies are kept for the weights' cotangent, where there are
    # weights (a caller's constants take none, and the compiler drops it).
    return (sum(num), each), (jnp.concatenate(dx), dw, x[:0],
                              None if weights is None else each)


def _head_loss_bwd(tied, scaling, rows, residuals, cotangents):
    dx, dw, like, each = residuals
    g, _ = cotangents
    with jax.named_scope("head"):
        # Scaled in float32 and rounded to the compute dtype once, as the
        # products autodiff makes in that dtype are.
        return ((g * dx).astype(like.dtype), (g * dw).astype(like.dtype),
                None, None if each is None else g * each)


_head_loss_rows.defvjp(_head_loss_fwd, _head_loss_bwd)


def _exit_scores(gate, x, passes: int):
    """The exit gate's scores ``w . h^t + b``, float32 ``[passes, rows]``,
    from the passes' normed rows ``x`` ``[passes * rows, E]``, pass after
    pass. A float32 sum over the lanes, no matrix product: one column would
    idle the MXU, and its default precision is one bfloat16 pass."""
    return (jnp.sum(x.astype(jnp.float32) * gate["w"], axis=-1)
            + gate["b"]).reshape(passes, -1)


def _exit_distribution(score):
    """A looped stack's exit distribution over its passes, a token, float32:
    ``(p [passes, rows], its entropy [rows])`` from the gate's scores. The
    gate is ``lambda^t = sigmoid(score^t)``; ``p^1 = lambda^1``, ``p^t =
    lambda^t prod_{j<t} (1 - lambda^j)`` and the last pass takes the mass
    that is left, ``prod_{j<T} (1 - lambda^j)`` (``lambda^T`` is not read),
    so a token's ``p`` add up to 1. In logarithms throughout: a gate that
    has closed gives ``p log p`` its limit and no ``0 * inf``."""
    log_stay = jax.nn.log_sigmoid(-score)            # log(1 - lambda)
    stayed = jnp.cumsum(log_stay, axis=0) - log_stay      # over j < t
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(score[:-1]) + stayed[:-1], stayed[-1:]])
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, axis=0)


def forward(params, tokens, positions, cfg: GPTConfig):
    """Logits ``[B, S_local, vocab]`` (fp32), for a caller that wants logits
    (generation, evaluation, the tests): the one function here that makes
    them whole. ``tokens``/``positions`` are this rank's sequence shard
    (global positions) when sp is active."""
    x, _ = _hidden(params, tokens, positions, cfg)
    with jax.named_scope("head"):
        return _logits(x, _head_matrix(params, cfg), cfg.tie_embeddings,
                       cfg.logits_scaling)


def loss_fn(params, tokens, targets, positions, cfg: GPTConfig,
            ignore_index: int = -1, weights=None, divisor=None):
    """The training loss: :func:`loss_and_aux` without its parts (and, like
    it, without ever holding the logits :func:`forward` returns)."""
    return loss_and_aux(params, tokens, targets, positions, cfg,
                        ignore_index, weights, divisor)[0]


def loss_and_aux(params, tokens, targets, positions, cfg: GPTConfig,
                 ignore_index: int = -1, weights=None, divisor=None):
    """``(loss, aux)``: mean next-token cross-entropy over all *global*
    target tokens, plus, with expert blocks, ``load_balance_coef`` times
    their summed load-balance terms and ``router_z_coef`` times their summed
    router z terms (each a local-batch estimate, averaged over dp as the loss
    is; ``jax.value_and_grad(..., has_aux=True)`` takes the pair).

    ``targets`` is sequence-sharded like ``tokens`` (shift done globally by the
    caller, so shard boundaries need no neighbor exchange); positions with
    ``ignore_index`` are masked out. Averages over sp so every rank returns the
    identical global-mean loss. **What a caller whose loss is not that mean
    says, it says here** (training by diffusion over blocks): **under
    ``cfg.diffusion_block``, and only there**, ``targets`` may be ``[B,
    S_local / 2]``, the targets of each sequence's noised half, its first
    rows, **whatever those rows are to predict** (a caller shifts or does
    not), and the head multiplies those rows alone: the clean half is in
    the stack as keys and values and never meets the head's matrix (any
    other length raises by name);
    ``weights`` (float32, as ``targets``) multiply each target's
    cross-entropy (``1 / t`` of a noised token's block); ``divisor`` (a
    number: what the sum, taken over sp and ep, is divided by, a rank's
    data tokens; dp averaging is the caller's as ever) stands in for the
    count of targets kept. Without the three, the program is the one it
    was. ``aux`` holds ``cross_entropy`` and, with
    expert blocks, ``load_balance``, ``router_z`` (the sums over blocks) and
    ``counts`` ``[blocks, experts]``, tokens per expert; under
    ``cfg.router_probe`` also ``router_inputs`` ``[blocks, T, d]`` and
    ``router_logits`` ``[blocks, T, experts]`` (float32; the inputs of a
    router that reads the block's input in the stream's type), this rank's
    (an ep group's) tokens as each block's router read them and what it
    gave.

    **A looped stack** (``cfg.loop_passes`` = T > 1; it takes neither
    ``weights`` nor ``divisor``): every pass's rows go through the head in
    one call of the rule, T x the rows, each row's cross-entropy ``l^t_i``
    weighted by that token's exit probability at that pass
    (:func:`_exit_distribution`; the weights are functions of the gate's
    parameters and of the hidden states, and the rule hands them their
    cotangent), and the loss is ``1/N sum_i [sum_t p^t_i l^t_i -
    exit_entropy_coef H(p_i)]`` over the N targets kept, no stop-gradient
    anywhere. ``aux`` then holds ``cross_entropy`` (the expected one, the
    first term), ``pass_losses`` ``[T]`` (each pass's mean ``l^t``),
    ``exit_probs`` ``[T]`` (the mean ``p^t``) and ``exit_entropy`` (the mean
    ``H``). The gate, the distribution, the entropy and their backward pass
    lie under the scope ``exit``.

    It makes no logits ``[B, S_local, vocab]``: head and loss are one rule
    over blocks of token rows (:func:`_head_loss`, ``head_loss_rows`` rows a
    block), equal to :func:`forward` and a float32 cross-entropy and their
    gradients.
    """
    states, auxes = _passes(params, tokens, positions, cfg)
    x, passes = states[-1], cfg.loop_passes
    mask = (targets != ignore_index)
    if passes > 1 and (weights is not None or divisor is not None):
        raise ValueError(
            f"loop_passes={passes}: the rows' weights are the exit "
            "distribution's and the divisor the targets kept; a caller "
            "gives neither")
    with jax.named_scope("head"):
        if targets.shape[1] != x.shape[1]:
            if cfg.diffusion_block is None \
                    or 2 * targets.shape[1] != x.shape[1]:
                raise ValueError(
                    f"targets of {targets.shape[1]} rows a sequence beside "
                    f"{x.shape[1]} rows of tokens: only under "
                    "cfg.diffusion_block may targets be fewer, and then "
                    "the noised half's, half the rows "
                    f"(diffusion_block={cfg.diffusion_block})")
            x = x[:, :targets.shape[1]]
        x = x.reshape(-1, x.shape[-1])
        if passes > 1:
            # Every pass's rows through the head's one rule, pass after pass.
            x = jnp.concatenate(
                [state.reshape(x.shape) for state in states])
        rows = head_loss_rows(x.shape[0], cfg.vocab_size)
        _note_head_loss(cfg, x.shape[0], rows)
        # A replicated matrix enters the rule as varying as the rows are: a
        # rule's cotangent has its input's type, and the mark's transpose
        # sums the matrix's over the ranks.
        w = varying_like(_head_matrix(params, cfg), x)
    flat_targets = jnp.where(mask, targets, -1).reshape(-1)
    if passes == 1:
        num = _head_loss(x, w, flat_targets,
                         None if weights is None
                         else weights.astype(jnp.float32).reshape(-1),
                         cfg.tie_embeddings, cfg.logits_scaling, rows)
        sums = {}
    else:
        kept = mask.reshape(-1).astype(jnp.float32)
        with jax.named_scope("exit"):
            probs, entropy = _exit_distribution(
                _exit_scores(params["exit_gate"], x, passes))
        num, each = _head_loss_rows(
            x, w, jnp.tile(flat_targets, passes), probs.reshape(-1),
            cfg.tie_embeddings, cfg.logits_scaling, rows)
        with jax.named_scope("exit"):
            # Sums over the targets kept; a masked row's ``each`` is zero.
            sums = {"exit_entropy": jnp.sum(kept * entropy),
                    "exit_probs": jnp.sum(kept * probs, axis=1),
                    "pass_losses": jnp.sum(
                        lax.stop_gradient(each).reshape(passes, -1), axis=1)}
    with jax.named_scope("loss"):
        den = jnp.sum(mask.astype(jnp.float32))
        # The token population is sharded over sp (sequence) and, when experts
        # are parallel, over ep (batch rides (dp, ep)); reduce over both so
        # every rank returns the same global-mean — dp averaging is the
        # caller's (optimizer's).
        for ax in (cfg.sp_axis, cfg.ep_axis):
            if _axis_bound(ax):
                num = lax.psum(num, ax)
                den = lax.psum(den, ax)
                if sums:
                    sums = lax.psum(sums, ax)
        loss = num / (jnp.maximum(den, 1.0) if divisor is None else divisor)
    parts = {"cross_entropy": loss}
    if sums:
        with jax.named_scope("exit"):
            # The expected cross-entropy, each pass's own mean, the mean exit
            # distribution and its mean entropy; the loss less the entropy's
            # share.
            parts.update({name: total / jnp.maximum(den, 1.0)
                          for name, total in sums.items()})
            loss = loss - cfg.exit_entropy_coef * parts["exit_entropy"]
    if not auxes:
        return loss, parts
    with jax.named_scope("aux_loss"):
        aux = {**parts,
               "load_balance": sum(a["load_balance"] for a in auxes),
               "router_z": sum(a["router_z"] for a in auxes),
               "counts": jnp.stack([a["counts"] for a in auxes])}
        if cfg.router_probe:
            aux["router_inputs"] = jnp.stack(
                [a["router_input"] for a in auxes])
            aux["router_logits"] = jnp.stack(
                [a["router_logits"] for a in auxes])
        # An ep group routes its tokens together, so its ranks hold the same
        # terms; sp ranks hold their own sequence shard's.
        for ax, all_counts in ((cfg.sp_axis, lax.psum),
                               (cfg.ep_axis, lax.pmax)):
            if _axis_bound(ax):
                aux["load_balance"] = lax.pmean(aux["load_balance"], ax)
                aux["router_z"] = lax.pmean(aux["router_z"], ax)
                aux["counts"] = all_counts(aux["counts"], ax)
        return (loss + cfg.load_balance_coef * aux["load_balance"]
                + cfg.router_z_coef * aux["router_z"]), aux


def data_specs(cfg: GPTConfig) -> tuple:
    """(tokens/targets spec, positions spec): batch over dp — and over ep when
    expert parallelism is on (the MoE batch rides (dp, ep), see moe.py) —
    sequence over sp."""
    dp = runtime.dp_axis()
    batch_axes = (dp, cfg.ep_axis) if cfg.ep_axis else dp
    return P(batch_axes, cfg.sp_axis), P(batch_axes, cfg.sp_axis)
