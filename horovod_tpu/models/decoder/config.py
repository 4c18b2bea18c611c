"""The bottom of ``models/decoder``, which imports nothing of the package:
:class:`GPTConfig`, one :class:`LayerSpec` a layer (:func:`layer_plan`) and
where a block's norms sit (:func:`norm_placement`). A mixer's derived shapes
are functions of ``cfg`` in its own file (``mixers/``)."""

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of the stack: its mixer (a key of ``mixers.MIXERS``), for an
    attention mixer the ``window`` (a query sees itself and the ``window -
    1`` keys before it; None: every key before it), for an attention, a
    CCA or an MLA mixer whether the rotary embedding applies
    (``GPTConfig.rope_theta``, ``rotary_dim``), and its feed-forward (a key
    of ``gpt.FEED_FORWARDS``: two matrices and a GELU, three and a SiLU
    gate, or the expert block with what ``GPTConfig`` says of experts).
    **Either sublayer may be None**: the block is then the other one alone,
    ``x + f(N(x))`` with one norm (a stack whose blocks are a mixer or a
    feed-forward each); a layer with neither is refused
    (:func:`layer_plan`). :attr:`sublayers` says which a block has, and
    the parameters, the specs and ``gpt._block`` read it there.

    **What crosses layers beside the stream is said here too**: the names
    of the values the layer's mixer hands on (``publishes``: a mixer
    module's ``PUBLISHES`` or none of them) and of those it is handed
    (``reads``: its ``READS``), each read value published by a layer before
    it (:func:`layer_plan` refuses any other order; ``gpt._hidden`` carries
    them). ``depth`` is the layer's index in the published model where a
    constant of the layer follows from it (differential attention's
    ``lambda_init``) and the stack here is a selection of that model's
    layers."""
    mixer: Optional[str] = "attention"
    window: Optional[int] = None
    rope: bool = True
    ff: Optional[str] = "dense"
    publishes: Tuple[str, ...] = ()
    reads: Tuple[str, ...] = ()
    depth: Optional[int] = None

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
    # again (``gpt.SAVED_NAMES``, each name declared where it is born) and
    # recomputes the rest in backward: in bfloat16
    # 2E + 2HD + 4H + 2M bytes a token a layer where the input alone is 2E
    # (an expert block: no 2M, 6 bytes an expert parameter a layer and
    # 4 experts + 16 experts_per_token bytes a token; 4E more where the
    # branches' outputs are kept).
    remat: str = "none"                      # "none" | "full"
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
    # The router of an expert block, one of ``experts.ROUTERS``: "linear", one
    # matrix [embed, experts]; "mlp": a down-projection to router_dim plus
    # a learned vector times the down-projection of the expert block
    # before it (none for the first), an RMSNorm, two GELU layers of
    # router_dim and a matrix [router_dim, experts] (``experts._mlp_router``).
    router_kind: str = "linear"
    router_dim: int = 256
    # What an expert block's router reads, one of ``experts.ROUTER_READS``:
    # "ff_input", what the experts read (the normed stream after the mixer);
    # "block_input": the stream as it enters the block, un-normed, before
    # the mixer runs (``gpt._block``: the product under the scope
    # ``moe/router_early``; a linear router alone).
    router_reads: str = "ff_input"
    # The experts' form, one of ``parallel/moe.py::ACTIVATIONS``: gated by
    # "silu" or "relu" (three matrices an expert) or the un-gated squared
    # ReLU "relu2" (two, no ``w_gate``). A shared expert takes the same.
    expert_activation: str = "silu"
    # The routed experts run in a latent of this width (0: at the stream's):
    # the router reads the normed stream, ``u = h W_down_latent`` is what the
    # experts read, and their weighted sum goes through ``W_up_latent`` back
    # to the stream (``experts.apply``); a shared expert stays on the stream.
    moe_latent_dim: int = 0
    # A sublayer joins the residual stream as a_r (x + b_r) + a_h (f + b_h):
    # four learned vectors of embed_dim a sublayer, ones and zeros at
    # initialisation (``parts._residual``).
    residual_scaling: bool = False
    # The norms over the residual stream (before and after a branch, before
    # the head), one of ``NORM_KINDS``: "rms", one weight; "layer": the mean
    # removed, a weight and a bias (``parts._norm``; eps is norm_eps).
    norm_kind: str = "rms"
    # An "s6" mixer (Mamba-1's selective scan) has s6_inner channels (None:
    # twice embed_dim) of ssm_state states each, a convolution of ssm_conv
    # taps and a step size projected through s6_dt_rank dimensions (None:
    # embed_dim / 16); a "gmu" mixer gates the same s6_inner channels.
    s6_inner: Optional[int] = None
    s6_dt_rank: Optional[int] = None
    # A "kda" mixer (Kimi delta attention) has kda_heads heads with a key of
    # kda_key_dim and a value of kda_value_dim each, a convolution of
    # kda_conv taps and a scan in chunks of kda_chunk tokens. Its log decay
    # is one a key channel, from a gate bounded below at kda_lower_bound,
    # and its output goes under one sigmoid gate a head.
    kda_heads: int = 8
    kda_key_dim: int = 128
    kda_value_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64
    kda_lower_bound: float = -5.0
    # An "mla" mixer's output times sigmoid(h W_g), one gate a head, before
    # the output projection.
    mla_head_gate: bool = False
    # The router's choice limited to groups: the experts are router_groups
    # groups, a group's score the sum of its two largest leaning scores, and
    # a token's experts are chosen inside the router_groups_kept best groups
    # (1 and 1: one choice over all the scores).
    router_groups: int = 1
    router_groups_kept: int = 1
    # Training by diffusion over blocks (BD3-LM): every attention layer runs
    # under the block-diffusion mask at this block length (a power of two;
    # ``ops/flash_attention.py::Mask``), its mixer's scope ``attn_bd``. The
    # rows a rank holds are then the noised copy of a sequence and, after
    # it, the clean copy, and that doubling, the repeated positions and the
    # targets and weights of the noised half are the caller's batch
    # (``gpt.loss_and_aux``). None: causal attention, as ever.
    diffusion_block: Optional[int] = None
    # A looped stack: the layers run loop_passes times a step with the same
    # parameters, the norm before the head at the end of every pass, its
    # output both what the head and a learned exit gate (``exit_gate``: a
    # vector of embed_dim and a bias) read at that pass and what the next
    # pass starts from (``gpt._passes``). The loss is then the expected
    # cross-entropy under the gate's exit distribution over the passes less
    # exit_entropy_coef times that distribution's entropy, a token
    # (``gpt.loss_and_aux``). 1: each layer once, no gate, as ever.
    loop_passes: int = 1
    exit_entropy_coef: float = 0.0

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
    def s6_channels(self) -> int:
        """The channels an "s6" mixer scans and a "gmu" mixer gates."""
        return self.s6_inner or 2 * self.embed_dim



@functools.lru_cache(maxsize=None)
def layer_plan(cfg: GPTConfig) -> Tuple[LayerSpec, ...]:
    """One :class:`LayerSpec` a layer: ``cfg.layers`` where it is given,
    else what ``layer_kinds`` (the mixers; None: attention throughout),
    ``moe_every`` (every ``moe_every``-th block's feed-forward is the expert
    block), ``gated_mlp`` (the other blocks') and ``rope`` say. The one
    place those inputs are read. Whether a name is a mixer or a
    feed-forward the tables say, at the look-up (``gpt._sublayers``)."""
    kinds = cfg.layer_kinds
    if kinds is not None and len(kinds) != cfg.num_layers:
        raise ValueError(
            f"layer_kinds must name a mixer for each of the "
            f"{cfg.num_layers} layers, got {kinds!r}")
    if cfg.layers is None:
        return _under_loop(cfg, tuple(LayerSpec(
            mixer="attention" if kinds is None else kinds[i], rope=cfg.rope,
            ff="experts" if cfg.moe_every > 0
            and (i + 1) % cfg.moe_every == 0
            else "gated" if cfg.gated_mlp else "dense")
            for i in range(cfg.num_layers)))
    if kinds is not None or cfg.moe_every:
        raise ValueError("layers says each layer outright: leave "
                         "layer_kinds and moe_every unset beside it")
    plan = tuple(cfg.layers)
    _check_shared_values(plan)
    if len(plan) != cfg.num_layers or any(
            not isinstance(spec, LayerSpec) or not any(spec.sublayers)
            or (spec.window is not None
                and (spec.mixer not in _WINDOW_MIXERS or spec.window < 1))
            for spec in plan):
        raise ValueError(
            f"layers must hold a LayerSpec (a mixer, a feed-forward, "
            f"either of them None but not both, a window of at least one "
            f"key on one of {_WINDOW_MIXERS} alone: a CCA layer has none "
            f"yet, nor an MLA layer) for each of the {cfg.num_layers} "
            f"layers, got {plan!r}")
    return _under_loop(cfg, plan)


# The mixers that take ``LayerSpec.window``.
_WINDOW_MIXERS = ("attention", "diff_attention")


def _under_loop(cfg: GPTConfig, plan):
    """``plan`` as :func:`_under_diffusion` leaves it; with more than one
    pass only where nothing but the stream crosses layers and the rows are
    one copy of the sequence. What the one carry would hand from a pass to
    the next (a published value, an MLP router's state), and which half a
    pass's output would be under the block-diffusion mask, no configuration
    says, and none is guessed."""
    plan = _under_diffusion(cfg, plan)
    if cfg.loop_passes == 1:
        return plan
    if cfg.loop_passes < 1:
        raise ValueError(f"loop_passes must be at least 1, got "
                         f"{cfg.loop_passes}")
    for i, spec in enumerate(plan):
        for field in ("publishes", "reads"):
            if getattr(spec, field):
                raise ValueError(
                    f"loop_passes={cfg.loop_passes} beside layer {i}'s "
                    f"{field}={getattr(spec, field)}: what a pass hands the "
                    "next beside the stream is not said")
    if cfg.router_kind == "mlp" and any(spec.ff == "experts"
                                        for spec in plan):
        raise ValueError(
            f"loop_passes={cfg.loop_passes} beside router_kind='mlp': the "
            "router's state crosses expert blocks, and what the last block "
            "of a pass hands the first of the next is not said")
    if cfg.diffusion_block is not None:
        raise ValueError(
            f"loop_passes={cfg.loop_passes} beside diffusion_block="
            f"{cfg.diffusion_block}: a pass's output is rows of both copies "
            "of the sequence, and which the next pass reads is not said")
    return plan


def _under_diffusion(cfg: GPTConfig, plan):
    """``plan`` as it is; under ``cfg.diffusion_block`` only if every mixer
    is plain attention without a window: the mask lets a noised block see
    itself and the clean past, which a band would cut and a scan over the
    rows (a recurrent mixer, a convolution) would leak across."""
    if cfg.diffusion_block is not None and any(
            spec.mixer not in ("attention", None) or spec.window is not None
            for spec in plan):
        raise ValueError(
            f"diffusion_block={cfg.diffusion_block} is for stacks of "
            "'attention' mixers without a window (the block-diffusion mask "
            f"has no band, and no other mixer takes a mask), got {plan!r}")
    return plan


def _check_shared_values(plan) -> None:
    """Every value a layer reads was published by a layer before it, and a
    name is published once."""
    published = {}
    for i, spec in enumerate(plan):
        if not isinstance(spec, LayerSpec):
            continue
        missing = [name for name in spec.reads if name not in published]
        if missing:
            raise ValueError(f"layer {i} reads {missing} that no layer "
                             "before it publishes")
        for name in spec.publishes:
            if name in published:
                raise ValueError(f"layer {i} publishes {name!r}, which "
                                 f"layer {published[name]} already does")
            published[name] = i


NORM_KINDS = ("rms", "layer")
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

