"""The expert layer: dropless top-k mixture of SiLU-gated experts.

No reference analog — Horovod ships no expert parallelism; SURVEY.md §2.7 notes
``hvd.alltoall`` (``operations.cc:1055-1116``) is the enabling primitive users
would build expert routing on. This module is that layer, TPU-native. For
tokens ``h`` ``[T, d]``, router ``W_r`` ``[d, E]`` and experts ``W_gate,e``,
``W_up,e`` ``[d, m]``, ``W_down,e`` ``[m, d]``:

    r = h W_r (float32)        p = softmax(r)
    S_t = the k largest of p_t (ties to the lower index)
    y_t = sum_{e in S_t} p_{t,e} W_down,e( silu(W_gate,e h_t) * (W_up,e h_t) )

The weights ``p_{t,e}`` are not renormalised over ``S_t`` unless
``renormalize`` asks for ``p_{t,e} / sum_{e' in S_t} p_{t,e'}`` (the
gradient flows through the sum). **No token is
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
all-to-all would and needs no capacity. ``tp_axis`` shards the experts'
width ``m``; the partial sums meet in one ``psum`` after the combine.

**A rank's share without the mesh.** With no ``axis`` bound and fewer expert
matrices than the router is wide, the layer holds experts ``first_expert``
to ``first_expert + experts_local`` of ``E``: one rank's share of an
expert-parallel deployment, run alone. It routes over all ``E`` (weights,
auxiliary terms and counts are the whole router's), sorts its own experts'
rows first exactly as the bound branch does, and returns ``sum_{e in S_t,
e held}``: the partial sum that rank would hand to the exchange. Nothing
stands in for the absent ranks or for the exchange; the shares' outputs add
up to the whole layer's (``tests/test_moe_layer.py``). It still sorts and
gathers all ``T k`` rows though only ``experts_local / E`` of them are its
own.

Gradients: the choice ``S_t`` is not differentiable; the router learns
through the weights ``p_{t,e}`` and through the two auxiliary terms returned
beside ``y`` (the load-balance term's token fractions are constants).

Under ``jax.checkpoint`` (``models/gpt.py``, ``remat="full"``) the layer makes
again, for its backward pass, the router, the sort, the sorted rows, and the
gate and up products with their activation, and nothing else. The down
projection and the weighted sum have a backward pass of their own
(:func:`_down_and_combine`) that needs no expert's output, so their
recomputation is dead code; and the three expert tensors in the compute dtype
carry a name a checkpoint policy can keep (``checkpoint_name``:
``"moe_expert_matrices"``, 6 bytes an expert parameter in bfloat16, in
``gpt.SAVED_NAMES``), so the cast is made once. **Nothing whose rows lie in
the sort's order is named**: the backward pass makes the router again, on
the chip not always to the forward's choices (XLA is free to round its input
otherwise in the two passes), and when one near-tie falls the other way every
row behind it in the sort moves by one. A buffer kept in the forward's order
and read in the recomputed one gives gradients that are wrong by their own
size (PERF.md, Findings, PR 28; the top-k's two outputs kept with it hold the
order still).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.collectives import pvary
from .axes import axis_bound as _axis_bound, axis_size as _axis_size

GROUPED_MATMUL = "ragged_dot"


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


def _grouped(lhs, w, group_sizes, mine):
    """``lhs``'s rows times their groups' matrices; with ``mine`` (expert
    parallelism) the rows outside every group, which the grouped matmul
    leaves unwritten, at zero."""
    out = lax.ragged_dot(lhs, w, group_sizes)
    return out if mine is None else jnp.where(mine, out, 0)


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
    with jax.named_scope("experts"):
        # Both products by the grouped matmul's own transpose rules.
        u, = jax.linear_transpose(
            lambda h: lax.ragged_dot(h, w_down, group_sizes), hidden)(g_rows)
        if mine is not None:
            u = jnp.where(mine, u, 0)
        d_w_down, = jax.linear_transpose(
            lambda w: lax.ragged_dot((p_rows * hidden).astype(hidden.dtype),
                                     w, group_sizes), w_down)(g_rows)
        d_hidden = (p_rows * u).astype(hidden.dtype)
    with jax.named_scope("combine"):
        d_top_p = jnp.sum(u.astype(top_p.dtype) * hidden.astype(top_p.dtype),
                          axis=1)[inv].reshape(top_p.shape)
    return d_hidden, d_w_down, d_top_p, None, None, None, None


_down_and_combine.defvjp(_down_and_combine_fwd, _down_and_combine_bwd)


def moe_layer(x, router_w, w_gate, w_up, w_down, top_k: int,
              axis: Optional[str] = None, tp_axis: Optional[str] = None,
              dtype: Any = jnp.bfloat16, first_expert: int = 0,
              renormalize: bool = False) -> Tuple[jnp.ndarray, dict]:
    """Dropless top-``top_k`` expert layer (module docstring has the math).

    Args:
      x: ``[..., d]`` activations (this rank's batch/sequence shard).
      router_w: ``[d, num_experts]`` router weights (replicated, fp32).
      w_gate, w_up: ``[experts_local, d, m_local]`` — the ep-axis shard of
        the global ``[num_experts, d, m]`` tensors (and tp shard of ``m``).
      w_down: ``[experts_local, m_local, d]``.
      top_k: experts per token.
      axis: expert-parallel mesh axis (None/unbound ⇒ all experts local).
      tp_axis: tensor-parallel axis sharding the expert width, if any.
      first_expert: with no ``axis`` bound and ``experts_local <
        num_experts``, the first expert held (static).
      renormalize: divide a token's ``top_k`` weights by their sum.

    Returns ``(y, aux)``, ``y`` shaped and typed (``dtype``) as the
    activations, and over the tokens routed together (this rank's, or the ep
    group's): ``aux["load_balance"]`` = ``E sum_e f_e P_e`` with ``f_e`` the
    share of tokens whose ``S_t`` holds ``e`` and ``P_e = mean_t p_{t,e}``;
    ``aux["router_z"]`` = ``mean_t logsumexp(r_t)^2``; ``aux["counts"]``
    ``[E]`` int32, tokens per expert.
    """
    d = x.shape[-1]
    ep = _axis_bound(axis)
    experts_local = w_up.shape[0]
    num_experts = router_w.shape[1]
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
    from .. import runtime
    recorder = runtime.recorder()
    if recorder is not None:
        recorder.note_moe_layer(num_experts, top_k, _axis_size(axis),
                                GROUPED_MATMUL, experts_local)

    xt = x.reshape(-1, d)
    if ep:
        xt = lax.all_gather(xt, axis, axis=0, tiled=True)
    T = xt.shape[0]

    with jax.named_scope("router"):
        logits = jnp.dot(xt.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)            # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = lax.top_k(probs, top_k)                       # [T, k]
        if renormalize:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
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
            mine = (jnp.arange(T * top_k) < jnp.sum(group_sizes))[:, None]
        order = jnp.argsort(expert_of_pair, stable=True)
        inv = jnp.argsort(order)
        rows = _permute(jnp.repeat(xt.astype(dtype), top_k, axis=0),
                        order, inv)                                  # [Tk, d]

    with jax.named_scope("experts"):
        w_gate, w_up, w_down = (
            checkpoint_name(w.astype(dtype), "moe_expert_matrices")
            for w in (w_gate, w_up, w_down))
        if mine is not None:
            rows = jnp.where(mine, rows, 0)
        hidden = (jax.nn.silu(_grouped(rows, w_gate, group_sizes, mine))
                  * _grouped(rows, w_up, group_sizes, mine))
    if _axis_bound(tp_axis):
        # Each tp rank's share of the weights' gradient is a sum over its
        # part of the width; autodiff adds them where this cast is.
        top_p = pvary(top_p, tp_axis)
    y = _down_and_combine(hidden, w_down, top_p, order, inv, group_sizes,
                          mine)
    with jax.named_scope("combine"):
        if _axis_bound(tp_axis):
            y = lax.psum(y, tp_axis)        # row-parallel expert width
        if ep:
            y = lax.psum_scatter(y, axis, scatter_dimension=0, tiled=True)
        y = y.astype(dtype)
    return y.reshape(x.shape), {"load_balance": load_balance,
                                "router_z": router_z, "counts": counts}
