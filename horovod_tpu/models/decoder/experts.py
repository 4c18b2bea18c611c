"""The ``"experts"`` feed-forward: the dropless expert layer of
:mod:`horovod_tpu.parallel.moe` with what a decoder puts around it. The
block may hold a share of its router's experts (``experts_held``,
``first_expert``), renormalise a token's weights, add a shared expert every
token goes through (under a sigmoid gate of its own or as it is), be as
wide as ``expert_dim`` where the dense feed-forward is ``mlp_dim``, and
score with a sigmoid under a selection bias that is state, not a parameter
(``router_bias``, :func:`update_router_bias`, :func:`trainable`). Its
router is one matrix or, under ``router_kind="mlp"``, an MLP on a
down-projection that adds the down-projection of the expert block before
it (:func:`_mlp_router`): that state leaves a block beside ``x`` and enters
the next. A linear router reads what the experts read or, under
``router_reads="block_input"``, the stream as it enters the block,
un-normed, before the mixer (:func:`early_router`); the experts' gate is
SiLU or, under ``expert_activation="relu"``, ReLU, or under ``"relu2"`` the
experts (a shared one too) are un-gated, two matrices and a squared ReLU.
With ``moe_latent_dim`` the routed experts run in a latent narrower than
the stream (:func:`apply`: one down-projection of the normed stream before
the dispatch, under the scope ``moe/latent_down``, one up-projection of the
weighted sum after the combine, ``moe/latent_up``; the router and a shared
expert read the stream). A bound ep axis shards the experts, tp their hidden
width; an MLP router and the selection bias are whole on every rank."""

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ...parallel.axes import axis_bound as _axis_bound
from ...parallel.moe import ACTIVATIONS, UNGATED, expert_hidden, moe_layer
from .config import GPTConfig
from .parts import _rmsnorm, _tp_psum, readings, subkeys

ROUTERS = ("linear", "mlp")
ROUTER_READS = ("ff_input", "block_input")
KEY, SCOPE = "moe", "moe"
# The routed experts' weighted sum in the latent where they run in one (2 L
# bytes a token a block, in token order): the up-projection's weight
# gradient reads it, and without it the recomputed copy ran every window of
# a share again (30 grouped matmuls a step in the compiled Nemotron step for
# 20 with it: PERF.md, Findings, PR 55). The sublayer's first products on
# the stream (PR 59), each made once a step with its name: the latent's
# down-projection, the routed experts' operand (2 L bytes a token a block:
# 16.8 MB in the Nemotron cell, ``moe_latent_ms`` 13.9 -> 12.0), and the
# shared expert's gate and up products before the activation (2 Ms bytes a
# token each: Nemotron's one 88 MB a block, ``moe_shared_ms`` 87.3 -> 76.3
# and ``tok_s_chip`` +5.6%; Moonlight's two 185 MB, +2.5%; Trinity's 67 MB,
# Qwen's 34 MB: ledger, PR 58 and PR 59; PERF.md, Findings, PR 58-59). An
# MLP router's hidden rows stay recomputed; what fixes the routing, and
# what lies in the sort's order, is ``parallel/moe.py``'s to name.
SAVED_NAMES = ("moe_latent_out", "moe_latent_in",
               "moe_shared_pre_activation")


def routers_with_carry(cfg: GPTConfig) -> list:
    """For each layer, whether its expert block's router takes a state: an
    MLP router's does from the expert block before it, so every one but
    the plan's first."""
    seen, out = False, []
    for spec in cfg.plan:
        experts = spec.ff == "experts" and cfg.router_kind == "mlp"
        out.append(experts and seen)
        seen = seen or experts
    return out


def _experts_gated(cfg: GPTConfig) -> bool:
    """Whether an expert (a shared one too) has a gate matrix: every form
    of ``parallel/moe.py::ACTIVATIONS`` but the un-gated ones."""
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


def _mlp_router_parameters(cfg: GPTConfig, keys, carry: bool, dense) -> dict:
    """An MLP router's table; ``carry`` (every expert block but the first)
    the vector on the state from the block before, at one."""
    E, R, k = cfg.embed_dim, cfg.router_dim, subkeys(keys, 4)

    def vector(fill):
        return lambda: fill((R,), jnp.float32)

    zeros, ones = vector(jnp.zeros), vector(jnp.ones)
    makers = {
        "down": lambda: dense(k(0), (E, R), E), "down_b": zeros,
        "norm": ones,
        "w1": lambda: dense(k(1), (R, R), R), "b1": zeros,
        "w2": lambda: dense(k(2), (R, R), R), "b2": zeros,
        "w3": lambda: dense(k(3), (R, cfg.num_experts), R),
    }
    if carry:
        makers["carry"] = ones
    return {name: (P(), make) for name, make in makers.items()}


def _parameters(cfg: GPTConfig, keys, spec, carry: bool, dense=None) -> dict:
    """The ``"moe"`` sub-tree's table. ``keys``: the layer's last four (the
    router's also seeds, folded, the shared expert's and the latent's)."""
    if cfg.router_kind not in ROUTERS:
        raise ValueError(f"router_kind must be one of {ROUTERS}, got "
                         f"{cfg.router_kind!r}")
    E, n_exp, held, Mx = (cfg.embed_dim, cfg.num_experts, _held(cfg),
                          cfg.expert_width)
    tp, ep = cfg.tp_axis, cfg.ep_axis
    # The experts' width in and out: the latent's, else the stream's.
    L = cfg.moe_latent_dim or E
    table = {
        "router": (P(), lambda: dense(keys[0], (E, n_exp), E))
        if cfg.router_kind == "linear"
        else _mlp_router_parameters(cfg, keys, carry, dense),
        "w_up": (P(ep, None, tp), lambda: dense(keys[1], (held, L, Mx), L)),
        "w_down": (P(ep, tp, None),
                   lambda: dense(keys[2], (held, Mx, L), Mx)),
    }
    # Two matrices an expert in an un-gated form, the shared one too.
    gated = _experts_gated(cfg)
    if gated:
        table["w_gate"] = (P(ep, None, tp),
                           lambda: dense(keys[3], (held, L, Mx), L))
    if cfg.moe_latent_dim:
        lk = subkeys(keys, 2, fold=2)
        table["latent_down"] = (P(), lambda: dense(lk(0), (E, L), E))
        table["latent_up"] = (P(), lambda: dense(lk(1), (L, E), L))
    if cfg.router_bias:
        # State every rank holds whole.
        table["router_bias"] = (P(),
                                lambda: jnp.zeros((n_exp,), jnp.float32))
    if cfg.shared_expert_dim:
        Ms, sk = cfg.shared_expert_dim, subkeys(keys, 4, fold=1)
        table["shared"] = {
            "w_up": (P(None, tp), lambda: dense(sk(1), (E, Ms), E)),
            "w_down": (P(tp, None), lambda: dense(sk(2), (Ms, E), Ms))}
        if gated:
            table["shared"]["w_gate"] = (
                P(None, tp), lambda: dense(sk(0), (E, Ms), E))
        if cfg.shared_expert_gate:
            table["shared"]["gate"] = (P(), lambda: dense(sk(3), (E,), E))
    return table


# init(keys, cfg, spec, carry, dense), specs(cfg, spec, carry)
init, specs = readings(_parameters)


def _shared_expert(cfg: GPTConfig, p, h):
    """The expert every token goes through, in the routed experts' form
    (``cfg.expert_activation``, ``parallel/moe.py::expert_hidden``):
    ``W_down (act(W_gate h) * W_up h)`` or, un-gated, ``W_down act(W_up
    h)``; under ``sigmoid(<h, w_g>)`` where the configuration gates it. The
    products before the activation carry a name (``SAVED_NAMES``): a
    checkpointed block makes them once."""
    hidden = expert_hidden(cfg.expert_activation, lambda name: checkpoint_name(
        jnp.einsum("bse,em->bsm", h, p[name].astype(cfg.dtype)),
        "moe_shared_pre_activation"))
    down = _tp_psum(jnp.einsum("bsm,me->bse", hidden,
                               p["w_down"].astype(cfg.dtype)), cfg)
    if not cfg.shared_expert_gate:
        return down
    open_ = jax.nn.sigmoid(jnp.einsum(
        "bse,e->bs", h, p["gate"].astype(cfg.dtype),
        preferred_element_type=jnp.float32))
    return (down.astype(jnp.float32) * open_[..., None]).astype(cfg.dtype)


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


def early_router(cfg: GPTConfig, m, x):
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
    # checkpoint keeps (``gpt._block`` puts a barrier on it).
    with jax.named_scope("moe"), jax.named_scope("router_early"):
        return x, jnp.dot(x.astype(jnp.float32),
                          m["router"].astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)


def apply(cfg: GPTConfig, spec, m, h, router_state=None, early=None):
    """``(y, aux, router state)`` of the expert block ``m`` on normed
    activations: the state an MLP router hands to the next expert block
    (``router_state``: what the one before handed to this), None under a
    linear router. ``early``: :func:`early_router`'s pair, where the
    router read the block's input and not ``h``."""
    router = dict(router_w=m["router"])
    if cfg.moe_latent_dim:
        # The experts' operand, apart from the router's: one [T, E] x [E, L]
        # product, kept by name (``SAVED_NAMES``: 2 L bytes a token), so
        # the backward pass does not make it again.
        with jax.named_scope("latent_down"):
            router["expert_in"] = checkpoint_name(jnp.einsum(
                "bse,el->bsl", h, m["latent_down"].astype(cfg.dtype)),
                "moe_latent_in")
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
        activation=cfg.expert_activation, router_groups=cfg.router_groups,
        router_groups_kept=cfg.router_groups_kept, **router)
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
