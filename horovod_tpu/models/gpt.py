"""GPT: the flagship explicitly-parallel decoder-only LM (pure JAX, shard_map).

No reference analog (Horovod is model-agnostic, data-parallel only — SURVEY.md
§2.7); this model exists so the framework's tensor / sequence / data-parallel
mechanisms compose in one first-class consumer, and as the long-context
benchmark family. Parallelism is *explicit* shard_map-style (the TPU-idiomatic
regime): parameters are plain nested dicts with global shapes plus a matching
``PartitionSpec`` pytree (:func:`param_specs`); inside ``run_step`` every rank
computes on its local shard and the model inserts exactly the collectives the
math needs:

* **tp** — attention heads and MLP hidden are column-parallel; o-proj / down-proj
  are row-parallel followed by one ``psum`` each (Megatron pattern, but via
  shard_map + XLA collectives over ICI, not hand-written NCCL).
* **sp** — activations are sequence-sharded; attention is ring attention
  (``ppermute`` ring) or Ulysses (all-to-all, the flash kernel on each
  device), per config. Without a bound sp axis attention is the flash kernel
  (:mod:`horovod_tpu.ops.flash_attention`) unless the config asks for the
  dense reference by name; ``_attention`` holds the whole rule.
* **ep** — optional expert blocks (dropless top-k, gated experts) hold
  their experts over the ep axis (:mod:`horovod_tpu.parallel.moe`).
* **dp** — gradient averaging comes from autodiff under shard_map(check_vma):
  dp-invariant params get their grad psum inserted automatically;
  ``DistributedOptimizer`` then only normalizes.

bfloat16 activations / fp32 params+accumulators, RoPE, pre-norm RMSNorm;
optionally an RMSNorm on the whole query and key projections (``qk_norm``).
With expert blocks the loss carries the router's two auxiliary terms
(:func:`loss_and_aux`).

A layer's mixer follows its kind (``GPTConfig.layer_kinds``): ``"attention"``
as above, ``"ssm"``, a Mamba-2 state-space mixer (input projection,
causal depthwise convolution, the chunked scan of
:mod:`horovod_tpu.ops.ssd`, gated RMSNorm, output projection), or ``"gdn"``,
a gated-delta-rule linear-attention mixer (input projections, the same
convolution, the chunked scan of :mod:`horovod_tpu.ops.gated_delta` at key
and value heads of any size, the writing strength a sigmoid or twice one, an
RMSNorm a head and then the gate, output projection). A recurrent layer runs
on the sequence and the heads one rank holds: under a bound tp or sp axis it
raises (``_ssm_mixer``, ``_gdn_mixer``). The dense feed-forward may be
SiLU-gated, the rotary embedding left out, given another base or only the
first dimensions of a head, q and k normed a head, attention's output gated
by a sigmoid of a doubled query projection, every norm's weight centred at
zero (``1 + w``), the head the embedding's transpose, and the embedding, the
attention logits, each residual branch and the logits scaled by a constant.
A ``"cca"`` mixer is softmax attention whose q and k are made in a
compressed latent and mixed over the sequence before the heads attend
(:func:`_cca_mixer`: two stacked causal convolutions, a q/k mean, an L2 norm
a head under a learned key temperature, half of the value from the token
before); it runs under the scope ``attn`` and through ``_attention`` as an
``"attention"`` mixer does, on one rank's whole sequence and all its heads.
An ``"mla"`` mixer is latent attention (:func:`_mla_mixer`): a query head
is a no-position part of ``head_dim`` beside a rotary part of
``mla_rope_dim``, keys and values come from an RMS-normed latent of
``mla_kv_rank`` (a key head's no-position part and a value head of
``mla_value_dim`` each), and one rotary key a token is shared by all heads,
so the scores are over ``head_dim + mla_rope_dim`` dimensions and the values
of another width; it runs under ``attn`` and through ``_attention`` too, and
a bound tp axis holds a shard of its heads.
An expert block may hold a share of its router's experts
(``experts_held``, ``first_expert``: ``parallel/moe.py``), renormalise a
token's weights, add a shared expert every token goes through (under a
sigmoid gate of its own or as it is), be as wide as ``expert_dim`` where the
dense feed-forward is ``mlp_dim``, and score with a sigmoid under a
selection bias that is state, not a parameter (``router_bias``,
:func:`update_router_bias`, :func:`trainable`). Its router is one matrix
or, under ``router_kind="mlp"``, an MLP on a down-projection that adds the
down-projection of the expert block before it (:func:`_mlp_router`): that
state leaves a block beside ``x`` and enters the next. A linear router
reads what the experts read or, under ``router_reads="block_input"``, the
stream as it enters the block, un-normed, before the mixer
(:func:`_early_router`); the experts' gate is SiLU or, under
``expert_activation="relu"``, ReLU, or under ``"relu2"`` the experts (a
shared one too) are un-gated, two matrices and a squared ReLU. With
``moe_latent_dim`` the routed experts run in a latent narrower than the
stream (:func:`_expert_ff`: one down-projection of the normed stream
before the dispatch, under the scope ``moe/latent_down``, one up-projection
of the weighted sum after the combine, ``moe/latent_up``; the router and a
shared expert read the stream). A state-space mixer's gated norm runs over
each of ``ssm_groups`` groups' channels, so a group of its heads is a
smaller mixer whose parameters are slices of the whole's. Under
``residual_scaling`` a sublayer joins the stream as ``a_r (x + b_r) + a_h
(f(N(x)) + b_h)``, four learned vectors a sublayer (:func:`_residual`).

**What each layer is, is said once**: ``GPTConfig.plan``, one
:class:`LayerSpec` a layer (the mixer, an attention layer's window and
whether the rotary embedding applies to it, the feed-forward's kind; **either
sublayer may be absent**, ``mixer=None`` or ``ff=None``: the block is then
the other one alone with its one norm, as in a stack that alternates
mixers and feed-forwards block by block), either
given outright (``GPTConfig.layers``: dense layers before expert layers,
window attention beside full) or resolved from ``layer_kinds``, ``moe_every``
and ``gated_mlp`` in :func:`layer_plan` and nowhere else. ``init_params``,
``param_specs`` and ``_block`` read the plan, never which keys a layer's
parameters hold. **Where a block's norms sit is said once too**:
:func:`norm_placement` resolves ``GPTConfig.norms`` (``"pre"``: ``x +
f(N(x))``, the default; ``"pre_post"``: ``x + N2(f(N1(x)))``; ``"post"``: ``x
+ N(f(x))``, no norm before a branch) and the older ``post_norm`` into ``(a
norm before each branch, a norm after it)``, which those three read.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .. import runtime
from ..ops.attention import default_attention, repeat_kv_heads, rope
from ..ops.cca import cca_mix
from ..ops.conv import causal_conv_silu
from ..ops.flash_attention import flash_attention
from ..ops.gated_delta import gated_delta_chunked
from ..ops.pallas_util import varying_like
from ..ops.ssd import ssd_chunked
from ..parallel.ring_attention import ring_attention_p
from ..parallel.ulysses import ulysses_attention_p


MIXERS = ("attention", "cca", "mla", "ssm", "gdn")
ROUTERS = ("linear", "mlp")
ROUTER_READS = ("ff_input", "block_input")
FEED_FORWARDS = ("dense", "gated", "experts")


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of the stack: its mixer (one of ``MIXERS``), for an
    attention mixer the ``window`` (a query sees itself and the ``window -
    1`` keys before it; None: every key before it), for an attention, a
    CCA or an MLA mixer whether the rotary
    embedding applies (``GPTConfig.rope_theta``, ``rotary_dim``), and its
    feed-forward (one of ``FEED_FORWARDS``: two matrices and a GELU, three
    and a SiLU gate, or the expert block with what ``GPTConfig`` says of
    experts). **Either sublayer may be None**: the block is then the other
    one alone, ``x + f(N(x))`` with one norm (a stack whose blocks are a
    mixer or a feed-forward each); a layer with neither is refused
    (:func:`layer_plan`). :attr:`sublayers` says which a block has, and
    the parameters, the specs and ``_block`` read it there."""
    mixer: Optional[str] = "attention"
    window: Optional[int] = None
    rope: bool = True
    ff: Optional[str] = "dense"

    @property
    def sublayers(self) -> Tuple[bool, bool]:
        """``(a mixer, a feed-forward)``: which sublayers the block has."""
        return self.mixer is not None, self.ff is not None


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None      # GQA; default == num_heads
    head_dim: int = 64
    embed_dim: int = 512
    mlp_dim: int = 2048
    dtype: Any = jnp.bfloat16
    # Mesh axis names; None disables that parallelism dimension.
    tp_axis: Optional[str] = "tp"
    sp_axis: Optional[str] = "sp"
    ep_axis: Optional[str] = None
    # "flash" | "dense" | "ring" | "ulysses": the table in ``_attention``
    # (the flash kernel on each device, except under "dense", the reference;
    # "ring" and "ulysses" cross a bound sp axis, the other two refuse one).
    attention: str = "ring"
    # Experts (active when moe_every > 0): every moe_every-th block's
    # feed-forward is the dropless expert layer of ``parallel/moe.py``:
    # num_experts gated experts (``expert_activation``) of width mlp_dim,
    # experts_per_token of them a token. The loss adds the layers' summed
    # load-balance and router z terms under these coefficients.
    moe_every: int = 0
    num_experts: int = 8
    experts_per_token: int = 1
    load_balance_coef: float = 0.0
    router_z_coef: float = 0.0
    # A rank's share of an expert-parallel deployment, run alone: the block
    # holds experts first_expert to first_expert + experts_held of the
    # router's num_experts (None: all of them) and returns their part of the
    # sum. A token's experts_per_token weights divided by their sum. A
    # gated expert of width shared_expert_dim (0: none) that every token
    # goes through, under a sigmoid gate of its own.
    experts_held: Optional[int] = None
    first_expert: int = 0
    renormalize_experts: bool = False
    shared_expert_dim: int = 0
    # RMSNorm over the whole query and the whole key projection (all heads
    # together), before the rotary embedding; qk_head_norm: over each head
    # instead, one weight of head_dim for all heads.
    qk_norm: bool = False
    qk_head_norm: bool = False
    norm_eps: float = 1e-6
    # Norm weights enter as 1 + w and start at zero (every norm but the
    # recurrent mixers' gated ones and the whole-projection qk_norm).
    norm_zero_centered: bool = False
    # Per-block rematerialization (jax.checkpoint) — the TPU lever trading
    # FLOPs for HBM so long sequences fit: "none" stores every block
    # activation; "full" stores a block's input and what is dear to make
    # again (``SAVED_NAMES``: the flash kernel's output and log-sum-exp, the
    # dense feed-forward's pre-activation, the expert layer's matrices in
    # the compute dtype and what fixes its routing, a state-space scan's
    # output, each branch's output under a norm after the branch or a
    # learned residual scale) and
    # recomputes the rest in backward: in bfloat16
    # 2E + 2HD + 4H + 2M bytes a token a layer where the input alone is 2E
    # (an expert block: no 2M, 6 bytes an expert parameter a layer and
    # 4 experts + 16 experts_per_token bytes a token; 4E more where the
    # branches' outputs are kept);
    # "dots" instead saves every matmul output (recompute only the cheap
    # elementwise work).
    remat: str = "none"                      # "none" | "full" | "dots"
    # Each layer's mixer, ``"attention"``, ``"ssm"`` or ``"gdn"``, one entry
    # a layer; None is attention throughout. A state-space mixer has
    # ssm_heads heads of ssm_head_dim, a state of ssm_state a head,
    # ssm_groups groups of heads that share B and C, a convolution of
    # ssm_conv taps and a scan in chunks of ssm_chunk tokens.
    layer_kinds: Optional[Tuple[str, ...]] = None
    ssm_heads: int = 8
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # A gated-delta-rule mixer has gdn_key_heads query/key heads of
    # gdn_key_dim and gdn_value_heads value heads of gdn_value_dim (value
    # head h reads key head h // (value heads / key heads)), a convolution
    # of gdn_conv taps and a scan in chunks of gdn_chunk tokens. The writing
    # strength beta is sigmoid(b), or with gdn_allow_neg_eigval twice that:
    # a token's transition I - beta k k^T then has its eigenvalue along the
    # key in (-1, 1) and not (0, 1).
    gdn_key_heads: int = 4
    gdn_value_heads: int = 8
    gdn_key_dim: int = 64
    gdn_value_dim: int = 64
    gdn_conv: int = 4
    gdn_chunk: int = 64
    gdn_allow_neg_eigval: bool = False
    # The dense feed-forward as silu(gate) * up (three matrices) instead of
    # gelu(up) (two).
    gated_mlp: bool = False
    # False: no position embedding on q and k. Else at base rope_theta on
    # the first rotary_dim dimensions of a head (None: all).
    rope: bool = True
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None
    # wq is twice as wide a head, [q | gate], and attention's output is
    # multiplied by sigmoid(gate) before the output projection.
    attention_gate: bool = False
    # The head is the embedding's transpose: one parameter receives the
    # gather's and the head's gradient.
    tie_embeddings: bool = False
    # Constants on the embedding, on the attention logits (None: one over
    # the square root of head_dim), on each residual branch, and dividing
    # the logits.
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # The layers said outright, one LayerSpec each; None: resolved from
    # layer_kinds, moe_every, gated_mlp and rope (``layer_plan``). What only
    # a per-layer description can say (a window on some attention layers,
    # the rotary embedding on some, dense layers before expert layers) is
    # said here and has no field of its own.
    layers: Optional[Tuple[LayerSpec, ...]] = None
    # An expert's width (None: mlp_dim, which stays the dense layers').
    expert_dim: Optional[int] = None
    # A norm after each branch as well as before: x + N2(f(N1(x))). The
    # older way to say norms="pre_post" (``norm_placement``).
    post_norm: bool = False
    # Where a block's RMSNorms sit, one of ``NORMS``: "pre" (before each
    # branch; what None means without post_norm), "pre_post" (before and
    # after) or "post" (after alone: x + N(f(x))). The norm before the head
    # is there in all three.
    norms: Optional[str] = None
    # False: the shared expert is added as it is, with no gate of its own.
    shared_expert_gate: bool = True
    # The router's scores, "softmax" or "sigmoid"; router_bias: a bias
    # [num_experts] added to the scores for the choice of experts alone,
    # kept beside the router's matrix but no parameter (no gradient reaches
    # it; ``trainable`` keeps the optimizer off it, ``update_router_bias``
    # moves it from the step's token counts); route_scale multiplies a
    # token's weights.
    router_score: str = "softmax"
    router_bias: bool = False
    route_scale: float = 1.0
    # ``loss_and_aux``'s parts also hold what each expert block's router
    # read and gave (``router_inputs``, ``router_logits``): a check holds
    # the float32 product to a reference fed the same activations, which
    # no norm or count of the step can (a step that drops them costs
    # nothing: the compiler removes what nobody reads).
    router_probe: bool = False
    # A "cca" mixer's convolutions over its latent [q | k] of num_heads +
    # kv_heads heads of head_dim: the taps of the depthwise stage and of the
    # stage grouped by head.
    cca_taps: Tuple[int, int] = (2, 2)
    # An "mla" mixer (latent attention): a query and key head is head_dim
    # dimensions without position beside mla_rope_dim rotary ones, the
    # rotary key one a token for all heads; keys' no-position parts and the
    # value heads of mla_value_dim come from an RMS-normed latent of
    # mla_kv_rank. The query is projected straight from the stream.
    mla_kv_rank: int = 512
    mla_rope_dim: int = 64
    mla_value_dim: int = 128
    # The router of an expert block, one of ``ROUTERS``: "linear", one
    # matrix [embed, experts]; "mlp": a down-projection to router_dim plus
    # a learned vector times the down-projection of the expert block
    # before it (none for the first), an RMSNorm, two GELU layers of
    # router_dim and a matrix [router_dim, experts] (``_mlp_router``).
    router_kind: str = "linear"
    router_dim: int = 256
    # What an expert block's router reads, one of ``ROUTER_READS``:
    # "ff_input", what the experts read (the normed stream after the mixer);
    # "block_input": the stream as it enters the block, un-normed, before
    # the mixer runs (``_block``: the product under the scope
    # ``moe/router_early``; a linear router alone).
    router_reads: str = "ff_input"
    # The experts' form, one of ``parallel/moe.py::ACTIVATIONS``: gated by
    # "silu" or "relu" (three matrices an expert) or the un-gated squared
    # ReLU "relu2" (two, no ``w_gate``). A shared expert takes the same.
    expert_activation: str = "silu"
    # The routed experts run in a latent of this width (0: at the stream's):
    # the router reads the normed stream, ``u = h W_down_latent`` is what the
    # experts read, and their weighted sum goes through ``W_up_latent`` back
    # to the stream (``_expert_ff``); a shared expert stays on the stream.
    moe_latent_dim: int = 0
    # A sublayer joins the residual stream as a_r (x + b_r) + a_h (f + b_h):
    # four learned vectors of embed_dim a sublayer, ones and zeros at
    # initialisation (``_residual``).
    residual_scaling: bool = False

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def plan(self) -> Tuple[LayerSpec, ...]:
        """What each layer is (:func:`layer_plan`)."""
        return layer_plan(self)

    def kind(self, layer: int) -> str:
        return self.plan[layer].mixer

    @property
    def expert_width(self) -> int:
        return self.expert_dim or self.mlp_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """The convolved channels: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def gdn_key_inner(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def gdn_value_inner(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def gdn_conv_dim(self) -> int:
        """The convolved channels: q, k and v side by side."""
        return 2 * self.gdn_key_inner + self.gdn_value_inner

    @property
    def cca_latent(self) -> int:
        """A CCA mixer's convolved channels: q and k side by side."""
        return (self.num_heads + self.kv_heads) * self.head_dim


from ..parallel.axes import axis_size as _axis_size, axis_bound as _axis_bound


@functools.lru_cache(maxsize=None)
def layer_plan(cfg: GPTConfig) -> Tuple[LayerSpec, ...]:
    """One :class:`LayerSpec` a layer: ``cfg.layers`` where it is given,
    else what ``layer_kinds`` (the mixers; None: attention throughout),
    ``moe_every`` (every ``moe_every``-th block's feed-forward is the expert
    block), ``gated_mlp`` (the other blocks') and ``rope`` say. The one
    place those inputs are read."""
    kinds = cfg.layer_kinds
    if kinds is not None and (len(kinds) != cfg.num_layers
                              or set(kinds) - set(MIXERS)):
        raise ValueError(
            f"layer_kinds must name one of {MIXERS} for each of the "
            f"{cfg.num_layers} layers, got {kinds!r}")
    if cfg.layers is None:
        return tuple(LayerSpec(
            mixer="attention" if kinds is None else kinds[i], rope=cfg.rope,
            ff="experts" if cfg.moe_every > 0
            and (i + 1) % cfg.moe_every == 0
            else "gated" if cfg.gated_mlp else "dense")
            for i in range(cfg.num_layers))
    if kinds is not None or cfg.moe_every:
        raise ValueError("layers says each layer outright: leave "
                         "layer_kinds and moe_every unset beside it")
    plan = tuple(cfg.layers)
    if len(plan) != cfg.num_layers or any(
            not isinstance(spec, LayerSpec)
            or spec.mixer not in MIXERS + (None,)
            or spec.ff not in FEED_FORWARDS + (None,)
            or not any(spec.sublayers)
            or (spec.window is not None
                and (spec.mixer != "attention" or spec.window < 1))
            for spec in plan):
        raise ValueError(
            f"layers must hold a LayerSpec (mixer one of {MIXERS}, "
            f"feed-forward one of {FEED_FORWARDS}, either of them None but "
            f"not both, a window of at least one "
            f"key on attention alone: a CCA layer has none yet, nor an MLA "
            f"layer) for each of the {cfg.num_layers} layers, got {plan!r}")
    return plan


# placement -> (a norm before each branch, a norm after it)
NORMS = {"pre": (True, False), "pre_post": (True, True),
         "post": (False, True)}


def norm_placement(cfg: GPTConfig) -> Tuple[bool, bool]:
    """``(before, after)``: whether a block norms each branch's input
    (parameters ``attn_norm`` / ``ssm_norm`` / ``gdn_norm`` and
    ``mlp_norm``) and its output (``mixer_post_norm``, ``mlp_post_norm``).
    ``cfg.norms`` says it; the older ``post_norm`` resolves here, beside
    the plan, and nowhere else (both given: ``ValueError``)."""
    if cfg.norms is None:
        return NORMS["pre_post" if cfg.post_norm else "pre"]
    if cfg.post_norm or cfg.norms not in NORMS:
        raise ValueError(
            f"norms must be one of {tuple(NORMS)} with post_norm left "
            f"unset beside it, got norms={cfg.norms!r}, "
            f"post_norm={cfg.post_norm}")
    return NORMS[cfg.norms]


def _init_ssm(key, cfg: GPTConfig, dense) -> dict:
    """A state-space mixer's parameters, initialised as the published
    Mamba-2 code does: ``A`` uniform in [1, 16], the step size log-uniform
    in [1e-3, 1e-1] (``dt_bias`` its inverse soft-plus), the skip at one,
    the convolution as torch's ``Conv1d`` (uniform within one over the
    square root of its taps)."""
    E, H, inner = cfg.embed_dim, cfg.ssm_heads, cfg.ssm_inner
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32)
                 * float(np.log(1e-1) - np.log(1e-3)) + float(np.log(1e-3)))
    dt = jnp.maximum(dt, 1e-4)
    bound = 1.0 / float(np.sqrt(cfg.ssm_conv))
    return {
        "in_proj": dense(ks[0], (E, inner + cfg.ssm_conv_dim + H), E),
        "conv_w": jax.random.uniform(
            ks[1], (cfg.ssm_conv, cfg.ssm_conv_dim), jnp.float32,
            -bound, bound),
        "conv_b": jax.random.uniform(ks[5], (cfg.ssm_conv_dim,), jnp.float32,
                                     -bound, bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((inner,), jnp.float32),
        "out_proj": dense(ks[4], (inner, E), inner),
    }


def _init_gdn(key, cfg: GPTConfig, dense) -> dict:
    """A gated-delta-rule mixer's parameters, initialised as the published
    Qwen3-Next code does (as remembered): ``A`` uniform in (0, 16],
    ``dt_bias`` at one, the gated norm's weight at one, the convolution as
    torch's ``Conv1d`` without a bias."""
    E, Hv = cfg.embed_dim, cfg.gdn_value_heads
    ks = jax.random.split(key, 5)
    bound = 1.0 / float(np.sqrt(cfg.gdn_conv))
    return {
        # [q | k | v | z] and [b | a]
        "in_proj": dense(ks[0], (E, cfg.gdn_conv_dim + cfg.gdn_value_inner),
                         E),
        "in_proj_ba": dense(ks[1], (E, 2 * Hv), E),
        "conv_w": jax.random.uniform(
            ks[2], (cfg.gdn_conv, cfg.gdn_conv_dim), jnp.float32,
            -bound, bound),
        "dt_bias": jnp.ones((Hv,), jnp.float32),
        "A_log": jnp.log(jnp.maximum(jax.random.uniform(
            ks[3], (Hv,), jnp.float32, 0.0, 16.0), 1e-4)),
        "norm": jnp.ones((cfg.gdn_value_dim,), jnp.float32),
        "out_proj": dense(ks[4], (cfg.gdn_value_inner, E),
                          cfg.gdn_value_inner),
    }


def _init_cca(key, cfg: GPTConfig, dense) -> dict:
    """A CCA mixer's parameters: the latent projections ``[q | k]`` and ``[v
    of the token | v of the token before]``, the two convolutions as
    torch's ``Conv1d`` (uniform within one over the square root of the
    inputs a tap sums times the taps), the key heads' temperatures at zero,
    the output projection."""
    E, D, kv = cfg.embed_dim, cfg.head_dim, cfg.kv_heads * cfg.head_dim
    groups, latent = cfg.num_heads + cfg.kv_heads, cfg.cca_latent
    taps0, taps1 = cfg.cca_taps
    if cfg.kv_heads % 2 or cfg.num_heads % cfg.kv_heads:
        raise ValueError(
            "a CCA mixer gives half of its key/value heads the token's "
            "value and half the value of the token before, and a key/value "
            f"head a whole group of query heads: {cfg.num_heads} query and "
            f"{cfg.kv_heads} key/value heads")
    ks = jax.random.split(key, 7)

    def uniform(key, shape, fan_in):
        bound = 1.0 / float(np.sqrt(fan_in))
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    return {
        "wqk": dense(ks[0], (E, latent), E),
        "wv": dense(ks[1], (E, kv), E),
        "conv0_w": uniform(ks[2], (taps0, latent), taps0),
        "conv0_b": uniform(ks[3], (latent,), taps0),
        # [tap, group, channel in, channel out]
        "conv1_w": uniform(ks[4], (taps1, groups, D, D), taps1 * D),
        "conv1_b": uniform(ks[5], (latent,), taps1 * D),
        "temp": jnp.zeros((cfg.kv_heads,), jnp.float32),
        "wo": dense(ks[6], (cfg.num_heads * D, E), cfg.num_heads * D),
    }


_CCA_NAMES = ("wqk", "wv", "conv0_w", "conv0_b", "conv1_w", "conv1_b",
              "temp", "wo")


def _init_mla(key, cfg: GPTConfig, dense, norm) -> dict:
    """A latent-attention mixer's parameters: the query projection ``[E, H,
    no-position | rotary]``, the down-projection to ``[latent | the shared
    rotary key]``, the latent's norm, the up-projection ``[rank, H, key's
    no-position part | value]`` and the output projection."""
    E, H, rank = cfg.embed_dim, cfg.num_heads, cfg.mla_kv_rank
    nope, rot, value = cfg.head_dim, cfg.mla_rope_dim, cfg.mla_value_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense(ks[0], (E, H, nope + rot), E),
        "wkv_a": dense(ks[1], (E, rank + rot), E),
        "kv_norm": norm((rank,)),
        "wkv_b": dense(ks[2], (rank, H, nope + value), rank),
        "wo": dense(ks[3], (H, value, E), H * value),
    }


def _init_mlp_router(key, cfg: GPTConfig, dense, carry: bool) -> dict:
    """An MLP router's parameters; ``carry`` (every expert block but the
    first) the vector on the state from the block before, at one."""
    E, R = cfg.embed_dim, cfg.router_dim
    ks = jax.random.split(key, 4)

    def zeros():
        return jnp.zeros((R,), jnp.float32)

    router = {
        "down": dense(ks[0], (E, R), E), "down_b": zeros(),
        "norm": jnp.ones((R,), jnp.float32),
        "w1": dense(ks[1], (R, R), R), "b1": zeros(),
        "w2": dense(ks[2], (R, R), R), "b2": zeros(),
        "w3": dense(ks[3], (R, cfg.num_experts), R),
    }
    if carry:
        router["carry"] = jnp.ones((R,), jnp.float32)
    return router


def _mlp_router_names(carry: bool) -> tuple:
    return ("down", "down_b", "norm", "w1", "b1", "w2", "b2", "w3") \
        + (("carry",) if carry else ())


# A sublayer's residual scaling (``GPTConfig.residual_scaling``): on the
# stream and on the branch, a scale at one and a bias at zero each.
_RESIDUAL_NAMES = ("stream_scale", "stream_bias", "branch_scale",
                   "branch_bias")
_RESIDUAL_KEYS = ("mixer_res", "mlp_res")


def _residual_keys(spec: "LayerSpec") -> list:
    """The keys of a layer's residual scalings: one a sublayer it has."""
    return [key for key, has in zip(_RESIDUAL_KEYS, spec.sublayers) if has]


def _routers_with_carry(cfg: GPTConfig) -> list:
    """For each layer, whether its expert block's router takes a state: an
    MLP router's does from the expert block before it, so every one but
    the plan's first."""
    seen, out = False, []
    for spec in cfg.plan:
        experts = spec.ff == "experts" and cfg.router_kind == "mlp"
        out.append(experts and seen)
        seen = seen or experts
    return out


def _norm_names(spec: LayerSpec, before: bool, after: bool) -> list:
    """The keys of a layer's norms over the residual stream: before the
    mixer (the key carries the mixer's name: ``attn_norm``, ``ssm_norm``,
    ``gdn_norm``) and the feed-forward, after each; of a sublayer the layer
    has not, none."""
    mixer = "attn" if spec.mixer == "attention" else spec.mixer
    names = ([f"{mixer}_norm", "mlp_norm"] if before else []) \
        + (["mixer_post_norm", "mlp_post_norm"] if after else [])
    return [name for name, has in zip(names, spec.sublayers * 2) if has]


def _experts_gated(cfg: GPTConfig) -> bool:
    """Whether an expert (a shared one too) has a gate matrix: every form
    of ``parallel/moe.py::ACTIVATIONS`` but the un-gated ones."""
    from ..parallel.moe import ACTIVATIONS, UNGATED
    if cfg.expert_activation not in ACTIVATIONS:
        raise ValueError(f"expert_activation must be one of "
                         f"{tuple(ACTIVATIONS)}, got "
                         f"{cfg.expert_activation!r}")
    return cfg.expert_activation not in UNGATED


def _held(cfg: GPTConfig) -> int:
    """Experts an expert block's matrices hold."""
    held = cfg.num_experts if cfg.experts_held is None else cfg.experts_held
    if cfg.ep_axis is not None and held != cfg.num_experts:
        raise ValueError(
            "experts_held is one rank's share run without the mesh; with "
            f"ep_axis={cfg.ep_axis!r} the axis divides the experts itself")
    return held


def init_params(rng, cfg: GPTConfig) -> dict:
    """Global-shape parameter pytree (plain dicts; fp32).

    Shard with :func:`param_specs` + ``jax.device_put`` (or pass the specs as
    ``run_step`` in_specs) before feeding a shard_mapped step.
    """
    H, Hkv, D, E, M = (cfg.num_heads, cfg.kv_heads, cfg.head_dim,
                       cfg.embed_dim, cfg.mlp_dim)

    def dense(key, shape, fan_in):
        # float() keeps the scale weakly-typed so params stay fp32 under x64.
        return (jax.random.normal(key, shape, jnp.float32) /
                float(np.sqrt(fan_in)))

    def norm(shape):
        return jnp.zeros(shape, jnp.float32) if cfg.norm_zero_centered \
            else jnp.ones(shape, jnp.float32)

    if cfg.router_kind not in ROUTERS:
        raise ValueError(f"router_kind must be one of {ROUTERS}, got "
                         f"{cfg.router_kind!r}")
    plan = cfg.plan
    before, after = norm_placement(cfg)
    carries = _routers_with_carry(cfg)
    keys = jax.random.split(rng, 2 + cfg.num_layers)
    params: dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, E),
                                   jnp.float32) * 0.02,
        "out_norm": norm((E,)),
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (E, cfg.vocab_size), E)
    for i, spec in enumerate(plan):
        ks = jax.random.split(keys[2 + i], 8)
        if spec.mixer is None:
            layer = {}
        elif spec.mixer == "ssm":
            layer = {"ssm": _init_ssm(ks[0], cfg, dense)}
        elif spec.mixer == "gdn":
            layer = {"gdn": _init_gdn(ks[0], cfg, dense)}
        elif spec.mixer == "cca":
            layer = {"cca": _init_cca(ks[0], cfg, dense)}
        elif spec.mixer == "mla":
            layer = {"mla": _init_mla(ks[0], cfg, dense, norm)}
        else:
            layer = {
                "wq": dense(ks[0], (E, H, 2 * D if cfg.attention_gate else D),
                            E),
                "wk": dense(ks[1], (E, Hkv, D), E),
                "wv": dense(ks[2], (E, Hkv, D), E),
                "wo": dense(ks[3], (H, D, E), H * D),
            }
            if cfg.qk_norm:
                layer["q_norm"] = jnp.ones((H, D), jnp.float32)
                layer["k_norm"] = jnp.ones((Hkv, D), jnp.float32)
            elif cfg.qk_head_norm:
                layer["q_norm"] = norm((D,))
                layer["k_norm"] = norm((D,))
        for name in _norm_names(spec, before, after):
            layer[name] = norm((E,))
        if cfg.residual_scaling:
            for name in _residual_keys(spec):
                layer[name] = {
                    part: (jnp.ones if part.endswith("scale")
                           else jnp.zeros)((E,), jnp.float32)
                    for part in _RESIDUAL_NAMES}
        if spec.ff == "experts":
            n_exp, held, Mx = cfg.num_experts, _held(cfg), cfg.expert_width
            # The experts' width in and out: the latent's, else the stream's.
            L = cfg.moe_latent_dim or E
            layer["moe"] = {
                "router": dense(ks[4], (E, n_exp), E)
                if cfg.router_kind == "linear"
                else _init_mlp_router(ks[4], cfg, dense, carries[i]),
                "w_up": dense(ks[5], (held, L, Mx), L),
                "w_down": dense(ks[6], (held, Mx, L), Mx),
            }
            # Two matrices an expert in an un-gated form, the shared one too.
            gated = _experts_gated(cfg)
            if gated:
                layer["moe"]["w_gate"] = dense(ks[7], (held, L, Mx), L)
            if cfg.moe_latent_dim:
                lk = jax.random.split(jax.random.fold_in(ks[4], 2), 2)
                layer["moe"]["latent_down"] = dense(lk[0], (E, L), E)
                layer["moe"]["latent_up"] = dense(lk[1], (L, E), L)
            if cfg.router_bias:
                layer["moe"]["router_bias"] = jnp.zeros((n_exp,),
                                                        jnp.float32)
            if cfg.shared_expert_dim:
                sk = jax.random.split(jax.random.fold_in(ks[4], 1), 4)
                Ms = cfg.shared_expert_dim
                layer["moe"]["shared"] = {
                    "w_up": dense(sk[1], (E, Ms), E),
                    "w_down": dense(sk[2], (Ms, E), Ms),
                }
                if gated:
                    layer["moe"]["shared"]["w_gate"] = dense(sk[0], (E, Ms),
                                                             E)
                if cfg.shared_expert_gate:
                    layer["moe"]["shared"]["gate"] = dense(sk[3], (E,), E)
        elif spec.ff is not None:
            if spec.ff == "gated":
                layer["w_gate"] = dense(ks[7], (E, M), E)
            layer["w_up"] = dense(ks[5], (E, M), E)
            layer["w_down"] = dense(ks[6], (M, E), M)
        params["layers"].append(layer)
    return params


def param_specs(cfg: GPTConfig) -> dict:
    """PartitionSpec pytree matching :func:`init_params` — tp shards heads and
    MLP hidden (a latent-attention mixer's ``wq``, ``wkv_b`` and ``wo`` by
    head, its down-projection and the latent's norm whole on every rank);
    ep shards experts; everything else replicated, a state-space,
    gated-delta-rule or CCA mixer included (each refuses a bound tp axis),
    an MLP router, the residual scaling's vectors and the router's selection
    bias (state every rank holds whole)."""
    tp, ep = cfg.tp_axis, cfg.ep_axis
    specs: dict = {
        "embed": P(),
        "out_norm": P(),
        "layers": [],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P()
    before, after = norm_placement(cfg)
    for spec, carry in zip(cfg.plan, _routers_with_carry(cfg)):
        if spec.mixer is None:
            layer = {}
        elif spec.mixer == "ssm":
            layer = {"ssm": {
                name: P() for name in (
                    "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                    "norm", "out_proj")}}
        elif spec.mixer == "gdn":
            layer = {"gdn": {
                name: P() for name in (
                    "in_proj", "in_proj_ba", "conv_w", "dt_bias", "A_log",
                    "norm", "out_proj")}}
        elif spec.mixer == "cca":
            layer = {"cca": {name: P() for name in _CCA_NAMES}}
        elif spec.mixer == "mla":
            layer = {"mla": {
                "wq": P(None, tp, None), "wkv_a": P(), "kv_norm": P(),
                "wkv_b": P(None, tp, None), "wo": P(tp, None, None)}}
        else:
            layer = {
                "wq": P(None, tp, None),
                "wk": P(None, tp, None),
                "wv": P(None, tp, None),
                "wo": P(tp, None, None),
            }
            if cfg.qk_norm:
                layer["q_norm"] = P(tp, None)
                layer["k_norm"] = P(tp, None)
            elif cfg.qk_head_norm:
                layer["q_norm"] = P()
                layer["k_norm"] = P()
        for name in _norm_names(spec, before, after):
            layer[name] = P()
        if cfg.residual_scaling:
            for name in _residual_keys(spec):
                layer[name] = {part: P() for part in _RESIDUAL_NAMES}
        if spec.ff == "experts":
            _held(cfg)
            layer["moe"] = {
                "router": P() if cfg.router_kind == "linear"
                else {name: P() for name in _mlp_router_names(carry)},
                "w_up": P(ep, None, tp),
                "w_down": P(ep, tp, None),
            }
            gated = _experts_gated(cfg)
            if gated:
                layer["moe"]["w_gate"] = P(ep, None, tp)
            if cfg.moe_latent_dim:
                layer["moe"]["latent_down"] = P()
                layer["moe"]["latent_up"] = P()
            if cfg.router_bias:
                layer["moe"]["router_bias"] = P()
            if cfg.shared_expert_dim:
                layer["moe"]["shared"] = {"w_up": P(None, tp),
                                          "w_down": P(tp, None)}
                if gated:
                    layer["moe"]["shared"]["w_gate"] = P(None, tp)
                if cfg.shared_expert_gate:
                    layer["moe"]["shared"]["gate"] = P()
        elif spec.ff is not None:
            if spec.ff == "gated":
                layer["w_gate"] = P(None, tp)
            layer["w_up"] = P(None, tp)
            layer["w_down"] = P(tp, None)
        specs["layers"].append(layer)
    return specs


def _rmsnorm(x, w, dtype, eps, zero_centered: bool = False):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    if zero_centered:
        w = 1.0 + w
    return (x32 * lax.rsqrt(var + eps) * w).astype(dtype)


def _norm(cfg: GPTConfig, x, w):
    """The model's RMSNorm: over the last axis, ``1 + w`` if the
    configuration centres its weights at zero."""
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


def _attention(cfg: GPTConfig, q, k, v, window: Optional[int] = None):
    """Which attention runs: the one place that decides, and this table is
    the whole rule. No row falls back to another. ``window`` (a layer's,
    ``LayerSpec.window``) goes to whichever runs: the flash kernels and the
    dense reference take it, Ulysses hands it to the kernel it calls, and
    ring attention under a bound sp axis refuses a window layer by name
    (its hops wholly outside the band are not skipped yet).

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
    if not _axis_bound(sp):
        if kind == "dense":
            # The reference takes equal head counts (ring and Ulysses tile
            # K/V up themselves; the flash kernels read them as they are).
            return default_attention(q, repeat_kv_heads(k, q.shape[2]),
                                     repeat_kv_heads(v, q.shape[2]),
                                     causal=True, window=window)
        return flash_attention(q, k, v, causal=True, window=window)
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


def _ssm_mixer(cfg: GPTConfig, p, h):
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
    heads, inner = cfg.ssm_heads, cfg.ssm_inner
    groups, state = cfg.ssm_groups, cfg.ssm_state
    with jax.named_scope("in_proj"):
        zxbcdt = jnp.einsum("bse,ef->bsf", h, p["in_proj"].astype(cfg.dtype))
        z, _, dt = jnp.split(
            zxbcdt, [inner, inner + cfg.ssm_conv_dim], axis=-1)
    with jax.named_scope("conv"):
        # xBC read in place, out of the projection's output; tokens on the
        # lanes, as the scan's kernels read x, B and C.
        xbc = causal_conv_silu(zxbcdt, p["conv_w"], p["conv_b"], first=inner,
                               minor="tokens")
        x, b_in, c_in = jnp.split(
            xbc, [inner, inner + groups * state], axis=-1)
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        y, _ = ssd_chunked(
            x.reshape(batch, seq, heads, cfg.ssm_head_dim), dt,
            -jnp.exp(p["A_log"]), b_in.reshape(batch, seq, groups, state),
            c_in.reshape(batch, seq, groups, state), p["D"],
            chunk=cfg.ssm_chunk, dtype=cfg.dtype)
        y = checkpoint_name(y, "ssm_scan_out")
    with jax.named_scope("gate_norm"):
        gated = y.reshape(batch, seq, inner).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        # One group is the whole inner width as it lies: these reshapes are
        # then no operation (granite's program stays as it was).
        by_group = (groups, inner // groups) if groups > 1 else (inner,)
        y = _rmsnorm(gated.reshape(batch, seq, *by_group),
                     p["norm"].reshape(by_group), cfg.dtype,
                     cfg.norm_eps).reshape(batch, seq, inner)
    with jax.named_scope("out_proj"):
        return jnp.einsum("bsf,fe->bse", y, p["out_proj"].astype(cfg.dtype))


def _gdn_mixer(cfg: GPTConfig, p, h):
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
    key_inner, conv_dim = cfg.gdn_key_inner, cfg.gdn_conv_dim
    with jax.named_scope("in_proj"):
        qkvz = jnp.einsum("bse,ef->bsf", h, p["in_proj"].astype(cfg.dtype))
        z = qkvz[..., conv_dim:]
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
        q, k, v = jnp.split(qkv, [key_inner, 2 * key_inner], axis=-1)
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
        y = y.reshape(batch, seq, cfg.gdn_value_inner).astype(cfg.dtype)
    with jax.named_scope("out_proj"):
        return jnp.einsum("bsf,fe->bse", y, p["out_proj"].astype(cfg.dtype))


def _shared_expert(cfg: GPTConfig, p, h):
    """The expert every token goes through, in the routed experts' form
    (``cfg.expert_activation``, ``parallel/moe.py::expert_hidden``):
    ``W_down (act(W_gate h) * W_up h)`` or, un-gated, ``W_down act(W_up
    h)``; under ``sigmoid(<h, w_g>)`` where the configuration gates it."""
    from ..parallel.moe import expert_hidden
    hidden = expert_hidden(cfg.expert_activation, lambda name: jnp.einsum(
        "bse,em->bsm", h, p[name].astype(cfg.dtype)))
    down = _tp_psum(jnp.einsum("bsm,me->bse", hidden,
                               p["w_down"].astype(cfg.dtype)), cfg)
    if not cfg.shared_expert_gate:
        return down
    open_ = jax.nn.sigmoid(jnp.einsum(
        "bse,e->bs", h, p["gate"].astype(cfg.dtype),
        preferred_element_type=jnp.float32))
    return (down.astype(jnp.float32) * open_[..., None]).astype(cfg.dtype)


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


def _attention_mixer(cfg: GPTConfig, spec: LayerSpec, lp, h, positions):
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


def _before(t, tokens: int = 1):
    """``t`` ``[B, S, ...]`` moved ``tokens`` later along the sequence, zeros
    in front: position ``i`` holds what ``i - tokens`` held."""
    if not tokens:
        return t
    pad = ((0, 0), (tokens, 0)) + ((0, 0),) * (t.ndim - 2)
    return jnp.pad(t, pad)[:, :t.shape[1]]


def _cca_mixer(cfg: GPTConfig, spec: LayerSpec, p, h, positions):
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


def _mla_mixer(cfg: GPTConfig, spec: LayerSpec, p, h, positions):
    """Latent attention (MLA, as training runs it: keys and values
    decompressed a head) on normed activations ``h`` ``[B, S, E]``, ``H``
    heads, ``dn = head_dim``, ``dr = mla_rope_dim``, ``dv = mla_value_dim``,
    ``r = mla_kv_rank``: ``q_h = [qn_h (dn) | qr_h (dr)] = h W_q``; ``[c0 (r)
    | kr0 (dr)] = h W_kv_a``; ``c = RMSNorm(c0)``; ``[kn_h (dn) | v_h (dv)] =
    c W_kv_b``; where ``spec.rope`` says so the rotary embedding on all
    ``dr`` dimensions of ``qr_h`` and of ``kr0``, **one** rotary key a token
    that every head shares; ``k_h = [kn_h | kr]``; the attention
    ``_attention`` picks, scores over ``dn + dr`` dimensions scaled by one
    over its root, values ``dv`` wide; ``W_o``. No bias. Under a bound tp
    axis a rank holds a shard of the heads (``W_q``, ``W_kv_b``, ``W_o``)
    and makes the latent and the shared key whole."""
    nope, rot, rank = cfg.head_dim, cfg.mla_rope_dim, cfg.mla_kv_rank
    runtime.note_traced(
        "hvdtpu_spmd_mla_traces_total", heads=cfg.num_heads, nope_dim=nope,
        rope_dim=rot, value_dim=cfg.mla_value_dim, kv_rank=rank,
        q_rank="none")
    with jax.named_scope("mla_proj"):
        q = jnp.einsum("bse,ehd->bshd", h, p["wq"].astype(cfg.dtype))
        a = jnp.einsum("bse,ef->bsf", h, p["wkv_a"].astype(cfg.dtype))
        c = _norm(cfg, a[..., :rank], p["kv_norm"])
        kv = jnp.einsum("bsr,rhd->bshd", c, p["wkv_b"].astype(cfg.dtype))
    with jax.named_scope("mla_rope"):
        q_rot, k_rot = q[..., nope:], a[:, :, None, rank:]
        if spec.rope:
            q_rot = rope(q_rot, positions, cfg.rope_theta)
            k_rot = rope(k_rot, positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rot, (*kv.shape[:3], rot))], axis=-1)
        v = kv[..., nope:]
        if cfg.attention_multiplier is not None:
            # As in ``_attention_mixer``: every attention scales by one
            # over the root of the query's width, the rest goes onto q.
            q = q * (cfg.attention_multiplier * float(np.sqrt(nope + rot)))
    attn = _attention(cfg, q, k, v)
    with jax.named_scope("mla_proj"):
        o = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(cfg.dtype))
    return _tp_psum(o, cfg)


def _mlp_router(cfg: GPTConfig, r, h, state):
    """``(the router's outputs [B, S, experts], the state [B, S, R])`` of an
    MLP router ``r`` on normed activations ``h``, all float32 at the highest
    precision: ``z = h W_d + b_d``, plus ``carry * state`` where the expert
    block before handed one on (``state`` its ``z``; None for the first);
    ``s = RMSNorm(z)``; ``W_3 gelu(W_2 gelu(W_1 s + b_1) + b_2)``, the GELU
    exact. ``z``, before the norm, is the state for the next expert block."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST

    def layer(t, w, b):
        return jax.nn.gelu(jnp.dot(t, r[w], precision=hi) + r[b],
                           approximate=False)

    z = jnp.dot(h.astype(f32), r["down"], precision=hi) + r["down_b"]
    if state is not None:
        z = z + r["carry"] * state
    s = _rmsnorm(z, r["norm"], f32, cfg.norm_eps)
    return jnp.dot(layer(layer(s, "w1", "b1"), "w2", "b2"), r["w3"],
                   precision=hi), z


def _dense_ff(cfg: GPTConfig, spec: LayerSpec, lp, h):
    up = jnp.einsum("bse,em->bsm", h, lp["w_up"].astype(cfg.dtype))
    up = checkpoint_name(up, "ffn_pre_activation")
    if spec.ff == "gated":
        gate = jnp.einsum("bse,em->bsm", h, lp["w_gate"].astype(cfg.dtype))
        up = jax.nn.silu(gate) * up
    else:
        up = jax.nn.gelu(up)
    down = jnp.einsum("bsm,me->bse", up, lp["w_down"].astype(cfg.dtype))
    return _tp_psum(down, cfg)


def _early_router(cfg: GPTConfig, m, x):
    """``(operand, logits)`` of an expert block's linear router on the
    stream ``x`` as it enters the block (``router_reads="block_input"``):
    ``float32(x) W_r`` at the highest precision, before the mixer runs; the
    operand is ``x`` as it came."""
    if cfg.router_reads not in ROUTER_READS:
        raise ValueError(f"router_reads must be one of {ROUTER_READS}, got "
                         f"{cfg.router_reads!r}")
    if cfg.router_kind != "linear":
        raise ValueError(
            "router_reads='block_input' is a linear router's: an "
            f"{cfg.router_kind!r} router that reads the block's input is "
            "not implemented")
    # The probe's operand is ``x`` in the stream's own type, the values the
    # checkpoint keeps (``_block`` puts a barrier on it).
    with jax.named_scope("moe"), jax.named_scope("router_early"):
        return x, jnp.dot(x.astype(jnp.float32),
                          m["router"].astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)


def _expert_ff(cfg: GPTConfig, m, h, router_state=None, early=None):
    """``(y, aux, router state)`` of the expert block ``m`` on normed
    activations: the state an MLP router hands to the next expert block
    (``router_state``: what the one before handed to this), None under a
    linear router. ``early``: :func:`_early_router`'s pair, where the
    router read the block's input and not ``h``."""
    from ..parallel.moe import moe_layer
    router = dict(router_w=m["router"])
    if cfg.moe_latent_dim:
        # The experts' operand, apart from the router's: made again in the
        # backward pass, one [T, E] x [E, L] product (nothing names it).
        with jax.named_scope("latent_down"):
            router["expert_in"] = jnp.einsum(
                "bse,el->bsl", h, m["latent_down"].astype(cfg.dtype))
    if early is not None:
        router.update(router_w=None, logits=early[1],
                      router_kind="linear_early")
    elif cfg.router_kind == "mlp":
        with jax.named_scope("router"):
            logits, state = _mlp_router(cfg, m["router"], h, router_state)
        router.update(router_w=None, logits=logits, router_kind="mlp",
                      router_state=router_state is not None)
        router_state = state
    out, aux = moe_layer(
        h, w_gate=m.get("w_gate"), w_up=m["w_up"], w_down=m["w_down"],
        top_k=cfg.experts_per_token, axis=cfg.ep_axis,
        tp_axis=cfg.tp_axis, dtype=cfg.dtype,
        first_expert=cfg.first_expert,
        renormalize=cfg.renormalize_experts, score=cfg.router_score,
        bias=m["router_bias"] if cfg.router_bias else None,
        scale=cfg.route_scale, probe=cfg.router_probe,
        activation=cfg.expert_activation, **router)
    if early is not None and cfg.router_probe:
        # The probe's operand is what the early product read, not ``h``.
        read = early[0].reshape(-1, early[0].shape[-1])
        if _axis_bound(cfg.ep_axis):
            read = lax.all_gather(read, cfg.ep_axis, axis=0, tiled=True)
        aux = {**aux, "router_input": read}
    if cfg.moe_latent_dim:
        # The up-projection's weight gradient reads the experts' sum: kept
        # by name (``SAVED_NAMES``), else the recomputed copy runs the layer
        # to its end for it, every window of a share too.
        out = checkpoint_name(out, "moe_latent_out")
        with jax.named_scope("latent_up"):
            out = jnp.einsum("bsl,le->bse", out,
                             m["latent_up"].astype(cfg.dtype))
    if cfg.shared_expert_dim:
        with jax.named_scope("shared"):
            out = out + _shared_expert(cfg, m["shared"], h)
    return out, aux, router_state


_RECURRENT_MIXERS = {"ssm": _ssm_mixer, "gdn": _gdn_mixer}


def _block(cfg: GPTConfig, spec: LayerSpec, layer_params, x, positions,
           router_state=None):
    """One decoder block, as ``spec`` says it is: ``(x, aux, router
    state)``, ``aux`` the expert layer's auxiliary terms
    (``parallel/moe.py``) or None for a block without one, the router state
    what an MLP router hands from one expert block to the next (a block
    without one hands on what it was given; None under linear routers). A
    block is its mixer's sublayer and then its feed-forward's, or the one
    of the two it has (``spec.sublayers``)."""
    # The scopes sit inside the function ``jax.checkpoint`` wraps, so the
    # recomputed copy of a block carries them too (``forward`` has the rest).
    # A window layer's mixer is under ``attn_window``, a full one's under
    # ``attn``: a device trace tells their flash kernels apart by it. A CCA
    # layer's is under ``attn`` too, its own parts ``cca_proj`` and
    # ``cca_mix`` inside; so is a latent-attention layer's, with
    # ``mla_proj`` and ``mla_rope``.
    lp = layer_params
    norm_before, norm_after = norm_placement(cfg)
    has_mixer, has_ff = spec.sublayers
    mixer_res, mlp_res = (lp.get(key) for key in _RESIDUAL_KEYS) \
        if cfg.residual_scaling else (None, None)

    def before(key):
        return _norm(cfg, x, lp[key]) if norm_before else x

    def after(branch, key):
        # The backward pass of a norm after the branch reads the branch's
        # value, and so does a learned scale's gradient in ``_residual``:
        # under either the value is named (``SAVED_NAMES``), else the
        # recomputed copy runs every branch to its last product again. A
        # block that adds the branch and nothing else names nothing.
        if norm_after or cfg.residual_scaling:
            branch = checkpoint_name(branch, "branch_out")
        if not norm_after:
            return branch
        with jax.named_scope("post_norm"):
            return _norm(cfg, branch, lp[key])

    early = None
    if spec.ff == "experts" and cfg.router_reads != "ff_input":
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
        early = _early_router(cfg, lp["moe"], x)

    # A recurrent mixer's scope, its parameters' key and its norm's
    # (``<mixer>_norm``) carry its name; so do a CCA or MLA mixer's key and
    # norm.
    if has_mixer:
        scope = spec.mixer
        if spec.mixer in ("attention", "cca", "mla"):
            scope = "attn" if spec.window is None else "attn_window"
        with jax.named_scope(scope):
            h = before(_norm_names(spec, True, False)[0])
            if spec.mixer == "attention":
                branch = _attention_mixer(cfg, spec, lp, h, positions)
            elif spec.mixer == "cca":
                branch = _cca_mixer(cfg, spec, lp["cca"], h, positions)
            elif spec.mixer == "mla":
                branch = _mla_mixer(cfg, spec, lp["mla"], h, positions)
            else:
                branch = _RECURRENT_MIXERS[spec.mixer](cfg, lp[spec.mixer],
                                                       h)
            x = _residual(cfg, x, after(branch, "mixer_post_norm"),
                          mixer_res)

    if not has_ff:
        return x, None, router_state
    if spec.ff == "experts":
        with jax.named_scope("moe"):
            h = before("mlp_norm")
            out, aux, router_state = _expert_ff(cfg, lp["moe"], h,
                                                router_state, early)
            return _residual(cfg, x, after(out, "mlp_post_norm"),
                             mlp_res), aux, router_state
    with jax.named_scope("mlp"):
        h = before("mlp_norm")
        return _residual(cfg, x, after(_dense_ff(cfg, spec, lp, h),
                                       "mlp_post_norm"),
                         mlp_res), None, router_state


# What ``remat="full"`` keeps from a block's forward pass beside its input:
# the values whose recomputation is a kernel, the block's widest matmul or a
# pass over memory that buys nothing, named where they are born. The flash
# kernel's output and log-sum-exp (``ops/flash_attention.py``; with either
# missing the kernel runs again), the dense feed-forward's pre-activation
# (``_block``; of a gated one the up product, the gate is made again), the
# expert layer's three matrices in the compute dtype (``parallel/moe.py``: a
# cast's output), and the state-space scan's output (``_ssm_mixer``: 2 H P
# bytes a token a layer; with it the gated norm, the output projection and
# the rest of the block are made again without the scan's output product,
# and on the chip the step needs less memory at its peak than without it:
# PERF.md, Findings, PR 29), and of the gated-delta-rule scan
# (``_gdn_mixer``, ``ops/gated_delta.py``) its output (2 Hv V bytes a token
# a layer: +5.6% on the chip, PERF.md, Findings, PR 31) and what its
# backward kernels read beside their inputs: the chunk-local kernel's five
# outputs (``gdn_scan_operands``: a value head a token the lanes' V in
# float32 and 3 K + Q in the compute dtype, 738 MB a layer in the Qwen
# cell, 472 in the Olmo cell; they cross HBM to the recurrence's kernels in
# the forward pass already) and each chunk's entering state
# (``gdn_scan_entering``, 268 MB a layer in the Qwen cell), named only
# where no lane of a state is padding (key and value head both whole lane
# tiles; the scan computes it from its shapes, no field here). With both
# the recomputed copy runs neither ``hvd_gdn_fwd`` nor ``hvd_gdn_rec_fwd``;
# with the five alone, the Olmo cell's 96 x 192 heads, it runs the second
# (PERF.md, Findings, PR 56), and a branch's output where the
# block's own backward pass reads it (``_block``'s ``after``: under a norm
# after the branch, whose backward pass reads what it normed, or a learned
# residual scale, whose gradient is ``<g, branch + bias>``; 2 E bytes a token
# a sublayer, 4 E a layer, in token order; without it the recomputed copy
# runs each branch to its last product again, the feed-forward's down
# product, a mixer's output projection and an expert sublayer whole, windows
# and shared expert too; a block that only adds its branches names nothing
# and keeps nothing. On the chip, PERF.md, Findings, PR 48:
# ``trinity-mini_s8192`` +8.5% for 10 tensors of 64 MiB of which the step's
# peak shows 0.10 GiB, ``olmo-hybrid-7b_s8192`` +3.9% for 8 of 60 MiB and
# 0.23 GiB, ``zaya1-8b_s4096`` +4.6% for 12 of 64 MiB and 0.80 GiB). A block
# that produces none of a name keeps nothing under it. And what fixes an
# expert layer's routing (``parallel/moe.py``, PR 54): the router's outputs
# ``[T, E]`` float32 (the layer's own product, an MLP router's or an early
# router's alike), a token's chosen experts and their scores ``[T, k]``, the
# sort's order and, un-windowed, its inverse ``[T k]``: 4 E + 12 k to 16 k
# bytes a token a layer (33.5 + 2.0 MB a layer in the Qwen cell, the
# dearest; 0.5 MB in ZAYA1's), so the backward pass makes no router's
# product, no full-row sort and no argsort again and differentiates the
# routing the forward pass used, whatever a router made again would have
# chosen (on the chip not always the same: PERF.md, Findings, PR 53 and
# PR 54). And the routed experts' weighted sum in the latent where they run
# in one (``_expert_ff``, ``moe_latent_dim``: 2 L bytes a token a block, in
# token order): the up-projection's weight gradient reads it, and without
# it the recomputed copy ran every window of a share again (30 grouped
# matmuls a step in the compiled Nemotron step for 20 with it: PERF.md,
# Findings, PR 55). Norms, rotary,
# projections (a recurrent mixer's input projection too), the convolution,
# the scans' decays, the state-space scan's chunk states and the
# gated-delta-rule scan's where a head is carried padded, an MLP router's
# hidden rows, the experts' sorted rows, gate and up products and activation
# stay recomputed.
# Nothing that lies in the sort's order is named yet; such rows may be from
# now on only because the order is kept with them (``parallel/moe.py``'s
# docstring; PERF.md, Findings, PR 28).
SAVED_NAMES = ("flash_out", "flash_lse", "ffn_pre_activation",
               "moe_expert_matrices", "ssm_scan_out", "gdn_scan_out",
               "branch_out", "moe_router_logits", "moe_top_experts",
               "moe_top_weights", "moe_order", "moe_order_inverse",
               "moe_latent_out", "gdn_scan_operands", "gdn_scan_entering")
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
    """The per-layer apply ``(cfg, spec, layer_params, x, positions, router
    state)``, optionally wrapped in ``jax.checkpoint`` (cfg and a layer's spec are
    frozen dataclasses, so they ride static_argnums)."""
    if cfg.remat == "none":
        return _block
    if cfg.remat == "full":
        return jax.checkpoint(_block, static_argnums=(0, 1),
                              policy=_full_policy)
    if cfg.remat == "dots":
        return jax.checkpoint(
            _block, static_argnums=(0, 1),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(f"unknown remat mode {cfg.remat!r} "
                     "(expected 'none', 'full' or 'dots')")


def _hidden(params, tokens, positions, cfg: GPTConfig):
    """``(the normed hidden rows [B, S_local, E], [aux of each expert
    block])``: everything before the head's matrix, which ``forward`` and
    ``loss_and_aux`` share."""
    # Scopes name the program's parts in every instruction's ``op_name``:
    # ``embed``, ``layer<i>`` (with ``attn``, ``attn_window`` (an attention
    # layer with a window), ``ssm`` or ``gdn`` and ``mlp`` or ``moe``
    # inside, from ``_block``, each with ``post_norm`` where the
    # configuration norms a branch after it (``norm_placement``); ``ssm``
    # and ``gdn`` hold
    # ``in_proj``, ``conv``, ``scan``, ``gate_norm``, ``out_proj``; a CCA
    # layer's ``attn`` holds ``cca_proj`` and ``cca_mix``, a
    # latent-attention layer's ``mla_proj`` and ``mla_rope``; ``moe``
    # holds ``router`` (an MLP router whole, its state included; the
    # product of a router that reads the block's input is under
    # ``router_early``, before the mixer's scope),
    # ``dispatch``, ``experts``, ``combine`` and
    # ``shared``; ``res_scale`` where the residual is scaled), ``head``;
    # ``loss_and_aux``
    # adds ``loss``. A device trace is read by them (PERF.md section 3).
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    block = _block_fn(cfg)
    auxes, router_state = [], None
    for i, (spec, lp) in enumerate(zip(cfg.plan, params["layers"],
                                       strict=True)):
        with jax.named_scope(f"layer{i}"):
            x, aux, router_state = block(cfg, spec, lp, x, positions,
                                         router_state)
        if aux is not None:
            auxes.append(aux)
    with jax.named_scope("head"):
        return _norm(cfg, x, params["out_norm"]), auxes


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


def _head_loss_block(x, targets, w, tied: bool, scaling: float):
    """One block of rows: ``(summed loss, d loss / d x [R, E], d loss / d w)``
    at a cotangent of 1, both gradients float32. The block's logits are made
    once and die here."""
    with jax.named_scope("head"):
        logits = _logits(x, w, tied, scaling)
    with jax.named_scope("loss"):
        valid = targets >= 0
        hit = (lax.broadcasted_iota(jnp.int32, logits.shape, 1)
               == targets[:, None])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        num = jnp.sum(jnp.where(valid, lse - picked, 0.0))
        # softmax - onehot, zero on a masked row; rounded to the compute
        # dtype once, where autodiff rounds the float32 logits' cotangent.
        d = jnp.where(valid[:, None],
                      jnp.exp(logits - lse[:, None]) - hit, 0.0)
        d = (d / scaling if scaling != 1.0 else d).astype(x.dtype)
    with jax.named_scope("head"):
        dx = jnp.einsum("rv,ve->re" if tied else "rv,ev->re", d, w,
                        preferred_element_type=jnp.float32)
        dw = jnp.einsum("rv,re->ve" if tied else "rv,re->ev", d, x,
                        preferred_element_type=jnp.float32)
    return num, dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _head_loss(x, w, targets, tied: bool, scaling: float, rows: int):
    """The summed cross-entropy of the rows ``x`` ``[T, E]`` under the head
    ``w`` against ``targets`` ``[T]`` (negative: a masked row), as one rule
    over blocks of ``rows`` rows: a block's logits, in float32 their
    log-sum-exp, the block's loss and ``softmax - onehot``, and from that at
    once the block's part of ``d w`` (summed in float32) and its rows of
    ``d x``. No ``[T, V]`` array is made or kept; the backward rule scales
    the two gradients by the sum's cotangent."""
    return _head_loss_fwd(x, w, targets, tied, scaling, rows)[0]


def _head_loss_fwd(x, w, targets, tied, scaling, rows):
    # A Python loop and no ``lax.scan``: the blocks are few, and as a
    # ``while`` they ran 7 to 10 ms behind this on the chip (PERF.md,
    # Findings, PR 41). The last block is the rows that are left.
    num, dx, dw = zip(*(
        _head_loss_block(x[i:i + rows], targets[i:i + rows], w, tied, scaling)
        for i in range(0, x.shape[0], rows)))
    # The empty slice hands the backward rule the compute dtype.
    return sum(num), (jnp.concatenate(dx), sum(dw), x[:0])


def _head_loss_bwd(tied, scaling, rows, residuals, g):
    dx, dw, like = residuals
    with jax.named_scope("head"):
        # Scaled in float32 and rounded to the compute dtype once, as the
        # products autodiff makes in that dtype are.
        return ((g * dx).astype(like.dtype), (g * dw).astype(like.dtype),
                None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


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
            ignore_index: int = -1):
    """The training loss: :func:`loss_and_aux` without its parts (and, like
    it, without ever holding the logits :func:`forward` returns)."""
    return loss_and_aux(params, tokens, targets, positions, cfg,
                        ignore_index)[0]


def loss_and_aux(params, tokens, targets, positions, cfg: GPTConfig,
                 ignore_index: int = -1):
    """``(loss, aux)``: mean next-token cross-entropy over all *global*
    target tokens, plus, with expert blocks, ``load_balance_coef`` times
    their summed load-balance terms and ``router_z_coef`` times their summed
    router z terms (each a local-batch estimate, averaged over dp as the loss
    is; ``jax.value_and_grad(..., has_aux=True)`` takes the pair).

    ``targets`` is sequence-sharded like ``tokens`` (shift done globally by the
    caller, so shard boundaries need no neighbor exchange); positions with
    ``ignore_index`` are masked out. Averages over sp so every rank returns the
    identical global-mean loss. ``aux`` holds ``cross_entropy`` and, with
    expert blocks, ``load_balance``, ``router_z`` (the sums over blocks) and
    ``counts`` ``[blocks, experts]``, tokens per expert; under
    ``cfg.router_probe`` also ``router_inputs`` ``[blocks, T, d]`` and
    ``router_logits`` ``[blocks, T, experts]`` (float32; the inputs of a
    router that reads the block's input in the stream's type), this rank's
    (an ep group's) tokens as each block's router read them and what it
    gave.

    It makes no logits ``[B, S_local, vocab]``: head and loss are one rule
    over blocks of token rows (:func:`_head_loss`, ``head_loss_rows`` rows a
    block), equal to :func:`forward` and a float32 cross-entropy and their
    gradients.
    """
    x, auxes = _hidden(params, tokens, positions, cfg)
    mask = (targets != ignore_index)
    with jax.named_scope("head"):
        x = x.reshape(-1, x.shape[-1])
        rows = head_loss_rows(x.shape[0], cfg.vocab_size)
        _note_head_loss(cfg, x.shape[0], rows)
        # A replicated matrix enters the rule as varying as the rows are: a
        # rule's cotangent has its input's type, and the mark's transpose
        # sums the matrix's over the ranks.
        w = varying_like(_head_matrix(params, cfg), x)
    num = _head_loss(x, w, jnp.where(mask, targets, -1).reshape(-1),
                     cfg.tie_embeddings, cfg.logits_scaling, rows)
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
        loss = num / jnp.maximum(den, 1.0)
    if not auxes:
        return loss, {"cross_entropy": loss}
    with jax.named_scope("aux_loss"):
        aux = {"cross_entropy": loss,
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


def data_specs(cfg: GPTConfig) -> Tuple[P, P]:
    """(tokens/targets spec, positions spec): batch over dp — and over ep when
    expert parallelism is on (the MoE batch rides (dp, ep), see moe.py) —
    sequence over sp."""
    dp = runtime.dp_axis()
    batch_axes = (dp, cfg.ep_axis) if cfg.ep_axis else dp
    return P(batch_axes, cfg.sp_axis), P(batch_axes, cfg.sp_axis)


def trainable(params) -> dict:
    """A tree of booleans like ``params``: False on the leaves that are
    state and no parameter, the routers' selection biases. For
    ``optax.masked(optimizer, gpt.trainable)``: the optimizer then neither
    moves nor decays them (AdamW's decay would, at a zero gradient)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) != "router_bias",
        params)


def update_router_bias(params, counts, rate: float) -> dict:
    """``params`` with every expert block's selection bias moved towards an
    even load, after an optimizer step: with ``c_e`` the tokens expert ``e``
    of the block got in that step over all data-parallel ranks (``counts``
    ``[expert blocks, experts]``, ``aux["counts"]`` summed over ranks), ``d_e
    = rate * sign(mean(c) - c_e)`` and ``b <- b + d - mean(d)``. Outside the
    loss: no gradient is involved."""
    layers, block = [], 0
    for lp in params["layers"]:
        if "moe" in lp and "router_bias" in lp["moe"]:
            c = counts[block].astype(jnp.float32)
            d = rate * jnp.sign(jnp.mean(c) - c)
            lp = {**lp, "moe": {**lp["moe"], "router_bias":
                                lp["moe"]["router_bias"] + d - jnp.mean(d)}}
        block += "moe" in lp
        layers.append(lp)
    return {**params, "layers": layers}
