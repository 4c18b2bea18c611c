"""The expert layer: dropless top-k mixture of experts, gated (SiLU, or
ReLU where the caller says ``activation="relu"``: three matrices an expert)
or un-gated (``"relu2"``, the squared ReLU: two), at the tokens' width or in
a latent of the caller's making.

No reference analog — Horovod ships no expert parallelism; SURVEY.md §2.7 notes
``hvd.alltoall`` (``operations.cc:1055-1116``) is the enabling primitive users
would build expert routing on. This module is that layer, TPU-native. For
tokens ``h`` ``[T, d]``, router ``W_r`` ``[d, E]`` and experts ``W_gate,e``,
``W_up,e`` ``[d, m]``, ``W_down,e`` ``[m, d]``:

    r = h W_r (float32)        p = softmax(r)
    S_t = the k largest of p_t (ties to the lower index)
    y_t = sum_{e in S_t} p_{t,e} W_down,e( act(W_gate,e h_t) * (W_up,e h_t) )

``act`` is ``silu`` unless ``activation`` names another of
:data:`ACTIVATIONS` (``"relu"``: ``max(0, .)`` with ``relu'(0) = 0``;
``"relu2"``, one of :data:`UNGATED`: an expert is ``W_down,e relu(W_up,e
h_t)^2``, two matrices and no ``W_gate``); the form is
said once (:func:`expert_hidden`) and the un-windowed branch, a share's
windows, their backward rule and the caller's shared expert all take it
from there. **The experts' operand may be another than the router's**
(``expert_in``): a caller whose experts run in a latent hands in ``u = h
W`` ``[T, l]`` beside ``h``; the router, the scores and the choice are made
of ``h``, the sorted rows, a share's windows and the grouped matmuls are
``l`` wide, and ``y`` comes back ``[T, l]`` for the caller to project up
(``models/decoder/experts.py::apply``). The weights ``p_{t,e}`` are
not renormalised over ``S_t`` unless ``renormalize`` asks for ``p_{t,e} / sum_{e' in S_t} p_{t,e'}`` (the
gradient flows through the sum). With ``score="sigmoid"`` the scores are
independent gates and the choice may lean on a bias that is no parameter:

    p = sigmoid(r)             S_t = the k largest of p_t + b
    w_{t,e} = scale * p_{t,e} / (sum_{e' in S_t} p_{t,e'} + 1e-20)

(``bias`` ``b`` ``[E]`` enters the choice alone, so no gradient reaches it:
the caller moves it from the counts the layer returns, outside the loss,
``models/gpt.py::update_router_bias``; ``scale`` multiplies the weights
under either score; the division is ``renormalize``'s.) A router that is
more than one matrix (an MLP, a state carried from layer to layer:
``models/decoder/experts.py::_mlp_router``) is the caller's: it hands in ``r`` itself
(``logits``) and the layer takes it from there; so is a router that reads
something else than the experts do (the block's input before its mixer:
``models/gpt.py::_block``, ``router_reads="block_input"``). Everything after
the choice of experts is one code path for all of them. **No token is
dropped, whatever the routing**, and every shape is static: the ``T k``
token-expert pairs are sorted by expert, the tokens' rows gathered once in
that order, the three expert matrices applied as grouped matmuls over the
per-expert counts (``lax.ragged_dot``, which XLA's TPU backend compiles to a
grouped-matmul kernel of its own: ``ragged-dot-*`` in a device trace), and
the rows put back in token order and summed under their weights. Both
permutations are gathers in the forward and in the backward pass
(:func:`_permute`).

With ``axis`` bound (expert parallelism) every rank holds ``E / n`` experts
and the batch rides ``(dp, ep)``: the group's tokens are all-gathered, each
rank routes all of them, applies its own experts to the rows routed to them
(sorted first; rows of other ranks' experts stay zero) and a ``psum_scatter``
hands each rank the sum for its own tokens. At ``k`` of 8 over 4 ranks a
token's experts lie on 3.6 ranks on average, so this moves what an
all-to-all would and needs no capacity: a rank whose experts draw more rows
than the window below takes as many windows as hold them. ``tp_axis`` shards
the experts' width ``m``; the partial sums meet in one ``psum`` after the
combine.

**A rank's share without the mesh.** With no ``axis`` bound and fewer expert
matrices than the router is wide, the layer holds experts ``first_expert``
to ``first_expert + experts_local`` of ``E``: one rank's share of an
expert-parallel deployment, run alone. It routes over all ``E`` (weights,
auxiliary terms and counts are the whole router's), sorts its own experts'
rows first exactly as the bound branch does, and returns ``sum_{e in S_t,
e held}``: the partial sum that rank would hand to the exchange. Nothing
stands in for the absent ranks or for the exchange; the shares' outputs add
up to the whole layer's (``tests/test_moe_layer.py``).

**A share's rows.** Where some experts are elsewhere (either branch above)
the held experts' rows are the first ``sum(group_sizes)`` of the sort's
order, and the layer gathers, multiplies, masks and sums back to their
tokens a window of ``R`` rows of it at a time: ``R`` = :func:`share_rows`,
static, twice the even share ``T k experts_local / E`` up to the grouped
matmul's tile. From the sort on no tensor has more than ``R`` rows of the
tokens' width or the experts'. The first window holds every held row of a
routing within twice of even; how many rows are held is known on the chip
before any row moves, and a routing that sends more takes the next window
too, and the next, until every held row is taken (a ``lax.while_loop`` whose
body such a routing alone runs, a rank's own with no collective inside:
:func:`_held_experts`). One code path for every routing; no second branch.
Where ``R`` would be ``T k`` (every expert held, an ``ep`` axis of 2, a
batch under a tile) the layer works on all the rows at once, as a layer
that holds every expert does.

Gradients: the choice ``S_t`` is not differentiable; the router learns
through the weights ``p_{t,e}`` and through the two auxiliary terms returned
beside ``y`` (the load-balance term's token fractions are constants).

Under ``jax.checkpoint`` (``models/gpt.py``, ``remat="full"``) **the routing
is made once a step.** What fixes it carries names the policy keeps
(``checkpoint_name``, all five in :data:`SAVED_NAMES`): the router's outputs
``r`` (``"moe_router_logits"``, ``[T, E]`` float32, the layer's own product
or the caller's ``logits``, after the gather under a bound ``axis``; from
``r`` the softmax, its rule, ``router_z`` and ``load_balance`` are a pass
over ``[T, E]`` and need no product, from the scores ``router_z`` would want
``r`` again), a token's chosen experts and their scores before
renormalisation (``"moe_top_experts"``, ``"moe_top_weights"``, ``[T, k]``),
the sort's order and, where the layer works on all the rows at once, its
inverse (``"moe_order"``, ``"moe_order_inverse"``, ``[T k]`` integers): 4 E +
12 k to 16 k bytes a token a layer. The choice is made on
``stop_gradient(p)`` and the weights are read at the *named* indices
(:func:`_scores_at`): ``lax.top_k``'s own differentiation rule keeps the
primitive's index output, which is not the named value, and the full-row
sort would be made again for it. So the backward pass holds no router's
product (``dW_r`` is the recomputed norm's rows times the kept outputs'
cotangent; a caller whose router is an MLP still makes that again, for its
own rule), no top-k and no argsort, and ``counts``, ``group_sizes``, every
window's ``pair_of_row``, ``sizes`` and ``mine`` derive from kept values:
**the two passes agree on every row by construction**, on every backend.
(Before PR 54 the backward pass made the router again, on the chip not
always to the forward's choices, since XLA is free to round its input
otherwise in the two passes: 106 and 164 of 8192 tokens in two layers,
PERF.md, Findings, PR 53; and when one near-tie falls the other way every
row behind it in the sort moves by one.)

**The first products are made once a step too, where the layer works on all
its rows at once** (PR 59): the sorted rows the grouped matmuls read
(``"moe_rows"``, ``[T k, l]``) and the gate and up products before the
activation (``"moe_pre_activation"``, ``[T k, m]`` each; the one in an
un-gated form) carry names, and the recomputed copy of a checkpointed block
holds no gather of the rows and no grouped matmul: **9 grouped calls a
layer, three forward and six backward**, where it held 11. They lie in the
sort's order, and a buffer kept in the forward's order and read in another
gives gradients that are wrong by their own size (PERF.md, Findings, PR 28,
which ran the same names at +4.9% and could not ship them: the router made
again chose other experts on the chip); such a name is safe only because
the order it lies in, ``moe_order`` and its inverse, is kept with it (PR
54). Bytes a layer in bfloat16: 2 l + 4 m a pair (OLMoE 268 + 2 x 134 MB,
ZAYA1's share 3 x 67 MB); each pays the unfused ``reduce_precision`` pass
``jax.checkpoint`` puts on a kept value a Mosaic kernel makes or reads
(PERF.md, Findings, PR 56), and the gain is net of it (``tok_s_chip`` +6.6%
in ``olmoe-1b-7b_s4096``, +4.7% in ``zaya1-8b_s4096``: ledger, PR 58 and PR
59; PERF.md, Findings, PR 58-59).

**A share's window at 0 is made once a step too** (PR 66). The window at 0,
the only one a routing within twice of even takes, hands what its forward
pass made to :func:`_held_experts`' backward rule as residuals under the same
two names: its gathered and masked rows ``[R, l]`` and its gate and up
products before the activation ``[R, m]`` each, named inside the rule's
forward and not on a value the forward pass reads afterwards (the
convention of ``ops/kda.py::_scan_forward``: no ``reduce_precision`` pass
lands on the forward's own path). The backward rule of that window applies
the activation again and goes on from there (:func:`_kept_window_bwd`): no
gather of the rows and no gate or up product a second time, **9 grouped
calls for the window at 0 where the rule held 11**. They lie in the sort's
order, whose first ``R`` entries the window reads in both passes from the
kept ``moe_order``. Bytes a layer in bfloat16, ``R (2 l + 4 m)``: Qwen 126 MB,
SDAR 235, Moonlight 239, Trinity 268, SmallThinker 403 (Nemotron's latent
and Ling's share under 50). **The loop's windows name nothing**: a routing
that sends a share more than ``R`` rows takes them, how many is known on the
chip alone, and the backward rule makes each of them again from the rule's
inputs by design; nothing of theirs outlives them. Outside ``jax.checkpoint``
the names are inert and the residuals live from the forward pass to the
backward, as autodiff's own would.

What the layer still makes again for its backward pass, un-windowed: the
activation alone (a pass over ``[T k, m]``), **so long as the caller's
backward pass has no use for the layer's output**. The down
projection and the weighted sum have a backward pass of their own
(:func:`_down_and_combine`) that needs no expert's output, so their
recomputation is dead code (a share's window at 0 applies its activation
again inside the backward rule, the loop's windows their whole forward, and
all of them are dead code in the recomputed copy altogether) where the
caller only adds ``y`` to its stream.
A caller that norms ``y`` or scales it by a parameter reads ``y`` in its own
backward pass, and then the recomputed copy runs the layer to its end, the
down product, the sum back to tokens and every window of a share, unless
the caller keeps ``y``: ``models/gpt.py::_block`` does, under the name
``"branch_out"`` (``[tokens, d]`` in token order, not the sort's), exactly
where its block has such a norm or scale. The three expert tensors in the
compute dtype carry a name too (``"moe_expert_matrices"``, 6 bytes an expert
parameter in bfloat16), so the cast is made once.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import runtime
from ..ops.collectives import pvary
from .axes import axis_bound as _axis_bound, axis_size as _axis_size

GROUPED_MATMUL = "ragged_dot"
# A rank's share works on its rows a window of this many times its even share
# of the T k at a time. Why two: the busiest single expert of 512 reads
# 2.1-2.25 times the mean (ledger, PR 31) and the sum over a rank's 32 experts
# spreads far less, so an even router's rows fit one window; a router that
# favours the rank's experts pays a window more for each even share's twice.
SHARE_HEADROOM = 2
ROW_TILE = 512      # the grouped matmul's tile of rows
# The experts' form, by the name a caller gives (``moe_layer``'s
# ``activation``, ``GPTConfig.expert_activation``): the activation, and
# whether it gates an up product (three matrices an expert, ``act(W_gate h)
# * W_up h``) or is applied to the up product itself (two matrices and no
# ``w_gate``, ``act(W_up h)``). ``jax.nn.relu``'s derivative at 0 is 0, so
# the squared ReLU's is too.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda t: jnp.square(jax.nn.relu(t))}
UNGATED = ("relu2",)
# What this module hands ``checkpoint_name``, for a ``jax.checkpoint`` around
# the caller to keep (the docstring's "Under ``jax.checkpoint``"): the three
# expert matrices in the compute dtype, a cast's output, and what fixes the
# routing (PR 54): the router's outputs ``[T, E]`` float32 (the layer's own
# product or the caller's ``logits`` alike), a token's chosen experts and
# their scores ``[T, k]``, the sort's order and, un-windowed, its inverse ``[T
# k]``: 4 E + 12 k to 16 k bytes a token a layer (33.5 + 2.0 MB a layer in
# the Qwen cell, the dearest; 0.5 MB in ZAYA1's). With them the backward pass
# makes no router's product, no full-row sort and no argsort again and
# differentiates the routing the forward pass used, whatever a router made
# again would have chosen (on the chip not always the same: PERF.md,
# Findings, PR 53 and PR 54). And, where the layer works on all its rows at
# once (PR 59), what lies in that kept order and is dear to make again: the
# sorted rows ``[T k, l]`` and the gate and up products before the activation
# ``[T k, m]`` (2 l + 4 m bytes a pair in bfloat16: 537 MB in OLMoE's one
# layer, 201 MB in each of ZAYA1's six; a checkpointed layer then holds 9
# grouped calls for 11; ``tok_s_chip`` +6.6% and +4.7%: ledger, PR 58 and PR
# 59). A share's window at 0 keeps the same two of its ``R`` rows, as the
# residuals of ``_held_experts``' rule (PR 66: ``R (2 l + 4 m)`` bytes a
# layer, Qwen 126 MB, SDAR 235, Moonlight 239, Trinity 268, SmallThinker 403;
# its backward rule then holds 9 grouped calls for 11). The activation stays
# recomputed, and so does every window of the loop, which no even routing
# runs.
SAVED_NAMES = ("moe_expert_matrices", "moe_router_logits", "moe_top_experts",
               "moe_top_weights", "moe_order", "moe_order_inverse",
               "moe_rows", "moe_pre_activation")


def expert_hidden(activation, product):
    """An expert's hidden rows in the form ``activation`` names, from
    ``product(name)``, the expert's input times its matrix ``name``:
    ``act(gate) * up``, or for an un-gated form ``act(up)``. The one place
    the form is applied: the routed experts, a share's windows, their
    backward rules (autodiff of this) and the caller's shared expert
    (``models/decoder/experts.py::_shared_expert``) all come here."""
    act = ACTIVATIONS[activation]
    if activation in UNGATED:
        return act(product("w_up"))
    return act(product("w_gate")) * product("w_up")


def kept_groups(leaning, groups: int, kept: int):
    """The leaning scores ``[T, E]`` (float32, outside the differentiated
    path) with every expert outside a token's ``kept`` best groups at
    ``-inf``: the ``E`` experts are ``groups`` groups of neighbours, a
    group's score is the sum of its two largest leaning scores, the ``kept``
    best groups are kept (DeepSeek-V3's ``noaux_tc`` choice, as remembered).
    A ``lax.top_k`` over the result is the choice inside the kept groups;
    ties go to the lower index, groups and experts alike."""
    tokens, experts = leaning.shape
    by_group = leaning.reshape(tokens, groups, experts // groups)
    score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)              # [T, G]
    best = lax.top_k(score, kept)[1]                                 # [T, kept]
    keep = jnp.any(jax.nn.one_hot(best, groups, dtype=jnp.bool_), axis=1)
    return jnp.where(keep[..., None], by_group, -jnp.inf).reshape(
        tokens, experts)


def share_rows(tokens: int, top_k: int, held: int, experts: int) -> int:
    """Rows a layer holding ``held`` of ``experts`` gathers, multiplies and
    puts back at a time for ``tokens`` tokens: ``SHARE_HEADROOM`` times the
    even share, up to a multiple of ``ROW_TILE``, and never more than
    ``tokens top_k``."""
    pairs = tokens * top_k
    even = -(-SHARE_HEADROOM * pairs * held // experts)
    return min(pairs, -(-even // ROW_TILE) * ROW_TILE)


@jax.custom_vjp
def _permute(x, perm, inv):
    """``x[perm]`` for a permutation of rows and its inverse: the cotangent
    goes back as the gather ``g[inv]``, where autodiff would scatter-add."""
    return x[perm]


def _permute_fwd(x, perm, inv):
    return x[perm], inv


def _permute_bwd(inv, g):
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _scores_at(probs, top_e):
    """``probs[t, top_e[t, j]]``, ``[T, k]``: the chosen experts' scores,
    picked out by a one-hot product (exact; XLA's gather of ``[T, k]`` from
    ``[T, E]`` and its scatter back took 1.4 ms a layer a pass on the v5e,
    my chip run, PR 35). Its gradient is ``lax.top_k``'s own: the cotangent
    put back at the chosen columns."""
    return jnp.sum(jax.nn.one_hot(top_e, probs.shape[-1], dtype=probs.dtype)
                   * probs[:, None, :], axis=-1)


def _grouped(lhs, w, group_sizes, mine):
    """``lhs``'s rows times their groups' matrices; with ``mine`` (expert
    parallelism) the rows outside every group, which the grouped matmul
    leaves unwritten, at zero."""
    out = lax.ragged_dot(lhs, w, group_sizes)
    return out if mine is None else jnp.where(mine, out, 0)


def _hidden(activation, rows, w_gate, w_up, group_sizes, mine, keep=False):
    """The experts' hidden rows by groups (:func:`expert_hidden`):
    ``act(rows W_gate) * (rows W_up)``, or ``act(rows W_up)`` in an un-gated
    form, whose ``w_gate`` is None; and the products it was made of, before
    the activation, by their matrix's name. ``keep``: the products carry the
    name ``"moe_pre_activation"`` (the layer's all-rows branch; a window at
    0's are named as :func:`_held_experts`' residuals)."""
    matrices, products = {"w_gate": w_gate, "w_up": w_up}, {}

    def product(name):
        out = _grouped(rows, matrices[name], group_sizes, mine)
        products[name] = checkpoint_name(out, "moe_pre_activation") if keep \
            else out
        return products[name]

    return expert_hidden(activation, product), products


def _grouped_bwd(lhs, w, group_sizes, mine, g):
    """The cotangents of :func:`_grouped`'s ``lhs`` and ``w`` for its
    output's, both by the grouped matmul's own transpose rules."""
    if mine is not None:
        g = jnp.where(mine, g, 0)
    d_lhs, = jax.linear_transpose(
        lambda t: lax.ragged_dot(t, w, group_sizes), lhs)(g)
    d_w, = jax.linear_transpose(
        lambda t: lax.ragged_dot(lhs, t, group_sizes), w)(g)
    return d_lhs, d_w


def _down_products_bwd(hidden, w_down, p_rows, g_rows, group_sizes, mine):
    """The down projection's backward products for sorted rows under their
    weights ``p_rows`` and the output's cotangent ``g_rows``: ``u = g_rows
    W_down^T`` and the cotangents of ``hidden`` and ``W_down``, both by the
    grouped matmul's own transpose rules."""
    with jax.named_scope("experts"):
        u, = jax.linear_transpose(
            lambda h: lax.ragged_dot(h, w_down, group_sizes), hidden)(g_rows)
        if mine is not None:
            u = jnp.where(mine, u, 0)
        d_w_down, = jax.linear_transpose(
            lambda w: lax.ragged_dot((p_rows * hidden).astype(hidden.dtype),
                                     w, group_sizes), w_down)(g_rows)
        d_hidden = (p_rows * u).astype(hidden.dtype)
    return u, d_hidden, d_w_down


@jax.custom_vjp
def _down_and_combine(hidden, w_down, top_p, order, inv, group_sizes, mine):
    """``y_t = sum_j p_{t,j} (hidden W_down)[inv[t k + j]]``, ``[T, d]``
    float32: the down projection of the sorted rows, the rows put back in
    token order and summed under their weights in float32.

    Its backward pass needs the experts' outputs for one thing, the weights'
    gradient ``<g_t, (hidden W_down)_row>``, and takes that number as
    ``<g_row W_down^T, hidden_row>`` from the product it makes anyway for
    ``hidden``'s gradient: under ``jax.checkpoint`` the down projection and
    the gather that puts its rows back are not made again."""
    return _down_and_combine_fwd(hidden, w_down, top_p, order, inv,
                                 group_sizes, mine)[0]


def _down_and_combine_fwd(hidden, w_down, top_p, order, inv, group_sizes,
                          mine):
    with jax.named_scope("experts"):
        out_rows = _grouped(hidden, w_down, group_sizes, mine)       # [Tk, d]
    with jax.named_scope("combine"):
        back = out_rows[inv].reshape(*top_p.shape, -1)
        y = jnp.sum(back.astype(jnp.float32) * top_p[:, :, None], axis=1)
    return y, (hidden, w_down, top_p, order, inv, group_sizes, mine)


def _down_and_combine_bwd(residuals, g):
    hidden, w_down, top_p, order, inv, group_sizes, mine = residuals
    top_k = top_p.shape[1]
    with jax.named_scope("combine"):
        g_rows = g.astype(hidden.dtype)[order // top_k]              # [Tk, d]
        p_rows = top_p.reshape(-1)[order][:, None]
        if mine is not None:
            g_rows = jnp.where(mine, g_rows, 0)
    u, d_hidden, d_w_down = _down_products_bwd(hidden, w_down, p_rows,
                                               g_rows, group_sizes, mine)
    with jax.named_scope("combine"):
        d_top_p = jnp.sum(u.astype(top_p.dtype) * hidden.astype(top_p.dtype),
                          axis=1)[inv].reshape(top_p.shape)
    return d_hidden, d_w_down, d_top_p, None, None, None, None


_down_and_combine.defvjp(_down_and_combine_fwd, _down_and_combine_bwd)


def _to_tokens(vals, pair_of_row, tokens, top_k):
    """``[tokens, w]``, float32 at least: each token's sum of the rows
    ``vals`` ``[R, w]`` of its pairs ``pair_of_row`` (token ``pair //
    top_k``). Rows that are nobody's come in at zero.

    A scatter-add: 2.3 ms for 20,480 float32 rows of 2048 on a v5e, against
    9.0 ms for the same sum by gathers alone (the rows sorted by token,
    neighbours added in doubling steps, each token's last row read; PERF.md,
    Findings, PR 32; ``scripts/moe_layer_time.py`` times both)."""
    wide = jnp.promote_types(vals.dtype, jnp.float32)
    return jnp.zeros((tokens, vals.shape[1]), wide).at[
        pair_of_row // top_k].add(vals.astype(wide))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take(x, pair_of_row, top_k):
    """``x[pair_of_row // top_k]``: the tokens' rows of ``R`` pairs of the
    sort's order, with no ``[T k, d]`` copy of the batch; the cotangent goes
    back through :func:`_to_tokens`."""
    return x[pair_of_row // top_k]


def _take_fwd(x, pair_of_row, top_k):
    # Nothing of x but its length and dtype is needed again.
    return x[pair_of_row // top_k], (pair_of_row, x[:, :0])


def _take_bwd(top_k, residuals, g):
    pair_of_row, like = residuals
    return _to_tokens(g, pair_of_row, like.shape[0], top_k).astype(
        like.dtype), None


_take.defvjp(_take_fwd, _take_bwd)


@jax.custom_vjp
def _down_and_combine_window(hidden, w_down, top_p, pair_of_row,
                             group_sizes, mine):
    """:func:`_down_and_combine` for ``R`` rows of the sort's order alone:
    ``y_t`` sums the token's rows among them. The same rule backward; no
    tensor has ``T k`` rows."""
    return _down_and_combine_window_fwd(hidden, w_down, top_p, pair_of_row,
                                        group_sizes, mine)[0]


def _down_and_combine_window_fwd(hidden, w_down, top_p, pair_of_row,
                                 group_sizes, mine):
    with jax.named_scope("experts"):
        out_rows = _grouped(hidden, w_down, group_sizes, mine)       # [R, d]
    with jax.named_scope("combine"):
        p_rows = top_p.reshape(-1)[pair_of_row][:, None]
        y = _to_tokens(out_rows.astype(jnp.float32) * p_rows, pair_of_row,
                       *top_p.shape)
    return y, (hidden, w_down, top_p, pair_of_row, group_sizes, mine)


def _down_and_combine_window_bwd(residuals, g):
    hidden, w_down, top_p, pair_of_row, group_sizes, mine = residuals
    tokens, top_k = top_p.shape
    with jax.named_scope("combine"):
        g_rows = jnp.where(
            mine, g.astype(hidden.dtype)[pair_of_row // top_k], 0)   # [R, d]
        p_rows = top_p.reshape(-1)[pair_of_row][:, None]
    u, d_hidden, d_w_down = _down_products_bwd(hidden, w_down, p_rows,
                                               g_rows, group_sizes, mine)
    with jax.named_scope("combine"):
        d_p_rows = jnp.sum(u.astype(top_p.dtype) * hidden.astype(top_p.dtype),
                           axis=1, keepdims=True)
        # A row's number goes to its pair's column of its token.
        d_top_p = _to_tokens(
            d_p_rows * jax.nn.one_hot(pair_of_row % top_k, top_k,
                                      dtype=top_p.dtype),
            pair_of_row, tokens, top_k)
    return d_hidden, d_w_down, d_top_p, None, None, None


_down_and_combine_window.defvjp(_down_and_combine_window_fwd,
                                _down_and_combine_window_bwd)


def _window_rows(window_rows, lo, order, group_sizes):
    """Rows ``lo`` to ``lo + window_rows`` of the sort's order: their pairs
    ``[R]``, how many of them each held expert has ``[experts_local]``, and
    which are a held expert's at all ``[R, 1]``."""
    pair_of_row = lax.dynamic_slice(order, (lo,), (window_rows,))
    ends = jnp.cumsum(group_sizes)
    sizes = jnp.clip(jnp.minimum(ends, lo + window_rows)
                     - jnp.maximum(ends - group_sizes, lo), 0)
    mine = (lo + jnp.arange(window_rows) < ends[-1])[:, None]
    return pair_of_row, sizes, mine


@functools.partial(jax.jit, static_argnums=(0, 1))
def _window(window_rows, activation, lo, xt, w_gate, w_up, w_down, top_p,
            order, group_sizes):
    """What rows ``lo`` to ``lo + window_rows`` of the sort's order add to a
    share's partial sum, ``[T, d]`` float32: the tokens' rows gathered, the
    held experts applied to those of their rows that lie in the window, the
    rows summed back to their tokens under their weights; and, for the
    window at 0's backward rule, what is dear to make again: the gathered
    and masked rows ``[R, l]`` and the gate and up products before the
    activation ``[R, m]`` by their matrix's name, which a loop's window
    drops. (Jitted so that JAX traces it once for the window at 0 and
    the loop's: a layer's second trace of it cost the cell's step 0.3 s of
    lowering.)"""
    with jax.named_scope("dispatch"):
        pair_of_row, sizes, mine = _window_rows(window_rows, lo, order,
                                                group_sizes)
        rows = _take(xt, pair_of_row, top_p.shape[1])                # [R, d]
    with jax.named_scope("experts"):
        rows = jnp.where(mine, rows, 0)
        hidden, products = _hidden(activation, rows, w_gate, w_up, sizes,
                                   mine)
    return _down_and_combine_window(hidden, w_down, top_p, pair_of_row,
                                    sizes, mine), (rows, products)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kept_window_bwd(window_rows, activation, lo, args, kept, g):
    """The cotangents of :func:`_window`'s ``xt``, ``w_gate``, ``w_up``,
    ``w_down`` and ``top_p`` for its sum's cotangent ``g``, from the rows
    and products its forward pass made (``kept``): the activation applied
    again, then the down product's rule, the gate and up products'
    transposes and :func:`_take`'s rule. No gather of the rows and no gate
    or up product. (Jitted as :func:`_window` is, for one trace a shape and
    because XLA's grouped-matmul kernels keep the program's ``op_name`` only
    inside a called function: bare, a device trace reads their 38 ms a step
    of Moonlight's as unscoped and not as backward; my chip run, PR 66.)"""
    xt, w_gate, w_up, w_down, top_p, order, group_sizes = args
    rows, products = kept
    with jax.named_scope("dispatch"):
        pair_of_row, sizes, mine = _window_rows(window_rows, lo, order,
                                                group_sizes)
    with jax.named_scope("experts"):
        hidden, hidden_bwd = jax.vjp(
            lambda made: expert_hidden(activation, made.__getitem__),
            products)
    d_hidden, d_w_down, d_top_p, *_ = _down_and_combine_window_bwd(
        (hidden, w_down, top_p, pair_of_row, sizes, mine), g)
    with jax.named_scope("experts"):
        matrices = {"w_gate": w_gate, "w_up": w_up}
        d_w, d_rows = dict.fromkeys(matrices), []
        for name, d in hidden_bwd(d_hidden)[0].items():
            d_lhs, d_w[name] = _grouped_bwd(rows, matrices[name], sizes, mine,
                                            d)
            d_rows.append(d_lhs)
        d_rows = jnp.where(mine, functools.reduce(jnp.add, d_rows), 0)
    with jax.named_scope("dispatch"):
        d_xt = _to_tokens(d_rows, pair_of_row, xt.shape[0],
                          top_p.shape[1]).astype(xt.dtype)
    return d_xt, d_w["w_gate"], d_w["w_up"], d_w_down, d_top_p


def _windows(window_rows, group_sizes, first, window):
    """``first(0)``'s sum for the window at 0 and, while held rows lie
    beyond the windows taken, ``window(lo)`` for the next one, added up,
    beside whatever else ``first`` hands back: one window for a routing
    whose held rows number ``window_rows`` at most, the loop's body never
    run; ``ceil(held rows / window_rows)`` windows for any other. The whole
    is under the scope ``windows`` and each window, the one at 0 and the
    loop's, under ``window``: a device trace counts the windows a step took
    by them (``benchmarks/layer_metrics/moe_windows_per_step.py``)."""
    held = jnp.sum(group_sizes)

    def scoped(one, lo):
        with jax.named_scope("window"):
            return one(lo)

    def more(carry):
        return carry[0] < held

    def next_window(carry):
        lo, total = carry
        return lo + window_rows, jax.tree.map(jnp.add, total,
                                              scoped(window, lo))

    with jax.named_scope("windows"):
        total, rest = scoped(first, jnp.zeros((), held.dtype))
        return lax.while_loop(
            more, next_window,
            (jnp.asarray(window_rows, held.dtype), total))[1], rest


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(window_rows, activation, xt, w_gate, w_up, w_down, top_p,
                  order, group_sizes):
    """A share's partial sum ``[T, d]`` float32 for pairs sorted with the
    held experts' first (``order``, padded to whole windows), :func:`_window`
    by window of ``window_rows`` rows of the order until every held row is
    taken: no token is dropped whatever the routing, and no tensor has more
    than ``window_rows`` rows.

    How many windows is known on the chip alone, so the loop is a
    ``lax.while_loop`` in the forward and in the backward rule alike. **The
    window at 0, the only one an even routing takes, is made once a step**:
    its gathered rows and its gate and up products are the rule's residuals
    under the names ``"moe_rows"`` and ``"moe_pre_activation"``, which a
    checkpointed block keeps (:data:`SAVED_NAMES`), and its backward pass
    reads them (:func:`_kept_window_bwd`). The loop's windows make their
    forward again from the rule's inputs: nothing of theirs outlives them.
    Windows after the first add their cotangents in the cotangents' own
    dtypes."""
    return _held_experts_fwd(window_rows, activation, xt, w_gate, w_up,
                             w_down, top_p, order, group_sizes)[0]


def _held_experts_fwd(window_rows, activation, *args):
    def first(lo):
        # Named as residuals only, here and not inside the jitted window:
        # the forward pass reads no kept value, and a checkpoint's policy
        # meets the names in the block's own jaxpr.
        y, (rows, products) = _window(window_rows, activation, lo, *args)
        return y, (checkpoint_name(rows, "moe_rows"),
                   {name: checkpoint_name(made, "moe_pre_activation")
                    for name, made in products.items()})

    y, kept = _windows(
        window_rows, args[-1], first,
        lambda lo: _window(window_rows, activation, lo, *args)[0])
    return y, (args, kept)


def _held_experts_bwd(window_rows, activation, residuals, g):
    args, kept = residuals
    *wrt, order, group_sizes = args

    def cotangents(lo):
        return jax.vjp(lambda *a: _window(window_rows, activation, lo, *a,
                                          order, group_sizes)[0], *wrt)[1](g)

    return (*_windows(
        window_rows, group_sizes,
        lambda lo: (_kept_window_bwd(window_rows, activation, lo, args,
                                     kept, g), None),
        cotangents)[0], None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def moe_layer(x, router_w, w_gate, w_up, w_down, top_k: int,
              axis: Optional[str] = None, tp_axis: Optional[str] = None,
              dtype: Any = jnp.bfloat16, first_expert: int = 0,
              renormalize: bool = False, score: str = "softmax",
              bias=None, scale: float = 1.0, probe: bool = False,
              logits=None, router_kind: str = "linear",
              router_state: bool = False,
              activation: str = "silu",
              expert_in=None, router_groups: int = 1,
              router_groups_kept: int = 1) -> Tuple[jnp.ndarray, dict]:
    """Dropless top-``top_k`` expert layer (module docstring has the math).

    Args:
      x: ``[..., d]`` activations (this rank's batch/sequence shard).
      router_w: ``[d, num_experts]`` router weights (replicated, fp32);
        None where the caller made the router's outputs itself (``logits``).
      w_gate, w_up: ``[experts_local, d, m_local]`` — the ep-axis shard of
        the global ``[num_experts, d, m]`` tensors (and tp shard of ``m``);
        ``w_gate`` None for an un-gated form (``activation``). With
        ``expert_in`` their ``d`` is its width, not ``x``'s.
      w_down: ``[experts_local, m_local, d]``.
      top_k: experts per token.
      axis: expert-parallel mesh axis (None/unbound ⇒ all experts local).
      tp_axis: tensor-parallel axis sharding the expert width, if any.
      first_expert: with no ``axis`` bound and ``experts_local <
        num_experts``, the first expert held (static).
      renormalize: divide a token's ``top_k`` weights by their sum.
      score: ``"softmax"`` over the router's outputs or ``"sigmoid"`` of
        each.
      bias: ``[num_experts]`` float32 added to the scores for the choice of
        experts alone (None: none); no gradient reaches it.
      scale: a constant on a token's weights.
      probe: ``aux`` also holds what the router read and gave, for a check
        that holds its product to a reference fed the same activations.
      logits: ``[..., num_experts]`` float32, the router's outputs ``r`` for
        ``x``'s tokens, made by the caller (``router_w`` is then None); the
        scores, the choice, the weights, the auxiliary terms and the counts
        are made of them here as of the layer's own product.
      router_kind, router_state: what the caller says of the router behind
        ``logits`` (its kind; whether it took a state from the layer
        before), for the layer's trace record alone
        (``hvdtpu_spmd_moe_layer_traces_total``).
      activation: the experts' form, one of :data:`ACTIVATIONS` (static).
      expert_in: ``[..., l]``, what the experts read where that is not what
        the router reads (a projection of ``x`` to a latent of another
        width, the caller's: ``models/decoder/experts.py``), one row for
        each of ``x``'s; None: ``x``. The sorted rows, a share's windows and
        ``y`` are then ``l`` wide and ``x`` is the router's operand alone.
      router_groups, router_groups_kept: the choice limited to groups
        (:func:`kept_groups`; 1 and 1: one choice over all the scores, the
        same program as before the arguments were).

    Returns ``(y, aux)``, ``y`` shaped as the experts' operand and typed
    (``dtype``) as the activations, and over the tokens routed together (this rank's, or the ep
    group's): ``aux["load_balance"]`` = ``E sum_e f_e P_e`` with ``f_e`` the
    share of tokens whose ``S_t`` holds ``e`` and ``P_e = mean_t p_{t,e}``;
    ``aux["router_z"]`` = ``mean_t logsumexp(r_t)^2``; ``aux["counts"]``
    ``[E]`` int32, tokens per expert; with ``probe``, ``aux["router_input"]``
    ``[T, d]`` float32 (the router's product's own operand; ``x`` where the
    caller handed in ``logits``, whatever its product read) and
    ``aux["router_logits"]`` ``[T, E]`` float32 (``r``).
    """
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"expert layer: score {score!r} is neither "
                         "'softmax' nor 'sigmoid'")
    if activation not in ACTIVATIONS:
        raise ValueError(f"expert layer: activation {activation!r} is none "
                         f"of {tuple(ACTIVATIONS)}")
    if (w_gate is None) != (activation in UNGATED):
        raise ValueError(
            f"expert layer: activation {activation!r} takes "
            f"{'no gate matrix' if activation in UNGATED else 'a gate matrix'}"
            ", an expert is two matrices or three")
    if (router_w is None) == (logits is None):
        raise ValueError("expert layer: the router's matrix or the router's "
                         "outputs, one of the two")
    d = x.shape[-1]
    ep = _axis_bound(axis)
    experts_local = w_up.shape[0]
    num_experts = logits.shape[-1] if router_w is None else router_w.shape[1]
    if not 1 <= router_groups_kept <= router_groups \
            or num_experts % router_groups \
            or top_k > router_groups_kept * (num_experts // router_groups) \
            or (router_groups > 1 and num_experts // router_groups < 2):
        raise ValueError(
            f"expert layer: {router_groups_kept} of {router_groups} groups "
            f"of a router {num_experts} wide, {top_k} a token: the groups "
            "divide the experts, hold two or more each, and those kept hold "
            "a token's experts")
    if ep and experts_local * _axis_size(axis) != num_experts:
        raise ValueError(
            f"expert layer: {_axis_size(axis)} ranks of {experts_local} "
            f"experts under a router {num_experts} wide")
    if not ep and not 0 <= first_expert <= num_experts - experts_local:
        raise ValueError(
            f"expert layer: experts {first_expert} to "
            f"{first_expert + experts_local} of a router {num_experts} wide")
    # Some experts are elsewhere: on the axis's other ranks, or on ranks
    # this program does not run.
    share = ep or experts_local < num_experts
    xt = x.reshape(-1, d)
    # The experts' operand: the router's own unless the caller made another.
    ut = xt if expert_in is None \
        else expert_in.reshape(-1, expert_in.shape[-1])
    if logits is not None:
        logits = logits.reshape(-1, num_experts).astype(jnp.float32)
    if ep:
        xt = lax.all_gather(xt, axis, axis=0, tiled=True)
        ut = xt if expert_in is None \
            else lax.all_gather(ut, axis, axis=0, tiled=True)
        if logits is not None:
            logits = lax.all_gather(logits, axis, axis=0, tiled=True)
    T = xt.shape[0]
    # The rows the layer works on at a time: all T k, or a share's window.
    window_rows = share_rows(T, top_k, experts_local, num_experts) if share \
        else T * top_k
    windowed = window_rows < T * top_k
    runtime.note_traced(
        "hvdtpu_spmd_moe_layer_traces_total", experts=num_experts,
        top_k=top_k, ep=_axis_size(axis), grouped_matmul=GROUPED_MATMUL,
        held=experts_local, rows=window_rows, score=score,
        bias=int(bias is not None), router=router_kind,
        state=int(router_state), activation=activation,
        groups=router_groups, groups_kept=router_groups_kept)

    with jax.named_scope("router"):
        # The product's own operand: a probe hands out this value and not
        # ``xt`` (the compiler may feed the product the activations before
        # their rounding to ``xt``'s type; then it feeds the probe the same).
        router_in = xt.astype(jnp.float32)
        if logits is None:
            logits = jnp.dot(router_in, router_w.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)        # [T, E]
        logits = checkpoint_name(logits, "moe_router_logits")
        probs = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        # The choice is made outside the differentiated path and kept by
        # name, and the weights are the scores' own at the *named* indices:
        # ``lax.top_k``'s own rule would keep its index output, which is not
        # the named value, and a checkpointed block would sort every row
        # again for it. The bias leans the choice and is in nothing else.
        leaning = lax.stop_gradient(
            probs if bias is None else probs + bias)
        if router_groups > 1:
            with jax.named_scope("groups"):
                leaning = kept_groups(leaning, router_groups,
                                      router_groups_kept)
        top_e = checkpoint_name(lax.top_k(leaning, top_k)[1],
                                "moe_top_experts")                   # [T, k]
        top_p = checkpoint_name(_scores_at(probs, top_e), "moe_top_weights")
        if renormalize:
            total = jnp.sum(top_p, axis=-1, keepdims=True)
            # Sigmoid scores can all be nothing; a softmax's k largest not.
            top_p = top_p / (total + 1e-20 if score == "sigmoid" else total)
        if scale != 1.0:
            top_p = top_p * scale
        counts = jnp.sum(jax.nn.one_hot(top_e, num_experts, dtype=jnp.int32),
                         axis=(0, 1))                                # [E]
        load_balance = num_experts * jnp.sum(
            counts.astype(jnp.float32) / T * jnp.mean(probs, axis=0))
        router_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    with jax.named_scope("dispatch"):
        expert_of_pair, group_sizes, mine = top_e.reshape(-1), counts, None
        if share:
            # This rank's experts first in the order: their rows are then
            # the first sum(group_sizes) of the T k. Rows of other ranks'
            # experts lie outside every group: a grouped matmul leaves them
            # unwritten, so they are held at zero, and with them their
            # cotangents.
            first = lax.axis_index(axis) * experts_local if ep \
                else first_expert
            expert_of_pair = (expert_of_pair - first) % num_experts
            group_sizes = lax.dynamic_slice(counts, (first,),
                                            (experts_local,))
            if not windowed:
                mine = (jnp.arange(T * top_k)
                        < jnp.sum(group_sizes))[:, None]
        order = checkpoint_name(jnp.argsort(expert_of_pair, stable=True),
                                "moe_order")
        if not windowed:
            inv = checkpoint_name(jnp.argsort(order), "moe_order_inverse")
            rows = _permute(jnp.repeat(ut.astype(dtype), top_k, axis=0),
                            order, inv)                              # [Tk, l]

    with jax.named_scope("experts"):
        w_gate, w_up, w_down = (
            w if w is None
            else checkpoint_name(w.astype(dtype), "moe_expert_matrices")
            for w in (w_gate, w_up, w_down))
        if not windowed:
            if mine is not None:
                rows = jnp.where(mine, rows, 0)
            # In the sort's order, which is kept with them (``SAVED_NAMES``).
            rows = checkpoint_name(rows, "moe_rows")
            hidden, _ = _hidden(activation, rows, w_gate, w_up, group_sizes,
                                mine, keep=True)
    if _axis_bound(tp_axis):
        # Each tp rank's share of the weights' gradient is a sum over its
        # part of the width; autodiff adds them where this cast is.
        top_p = pvary(top_p, tp_axis)
    if windowed:
        y = _held_experts(
            window_rows, activation, ut.astype(dtype), w_gate, w_up, w_down,
            top_p, jnp.pad(order, (0, -order.shape[0] % window_rows)),
            group_sizes)
    else:
        y = _down_and_combine(hidden, w_down, top_p, order, inv, group_sizes,
                              mine)
    with jax.named_scope("combine"):
        if _axis_bound(tp_axis):
            y = lax.psum(y, tp_axis)        # row-parallel expert width
        if ep:
            y = lax.psum_scatter(y, axis, scatter_dimension=0, tiled=True)
        y = y.astype(dtype)
    aux = {"load_balance": load_balance, "router_z": router_z,
           "counts": counts}
    if probe:
        aux.update(router_input=router_in, router_logits=logits)
    return y.reshape(x.shape if expert_in is None else expert_in.shape), aux
