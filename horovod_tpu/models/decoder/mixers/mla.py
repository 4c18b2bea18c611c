"""The ``"mla"`` mixer: latent attention. A query head is a no-position
part of ``head_dim`` beside a rotary part of ``mla_rope_dim``, keys and
values come from an RMS-normed latent of ``mla_kv_rank`` (a key head's
no-position part and a value head of ``mla_value_dim`` each), and one rotary
key a token is shared by all heads, so the scores are over ``head_dim +
mla_rope_dim`` dimensions and the values of another width. It runs under
``attn`` and through ``parts._attention``; a bound tp axis holds a shard of
its heads (``wq``, ``wkv_b`` and ``wo`` by head, the down-projection and
the latent's norm whole on every rank). Under ``mla_head_gate`` the
attention's output is multiplied a head by ``sigmoid(h W_g)``, ``W_g`` ``[E,
H]``, before the output projection (scope ``mla_gate``).

The turn between its ``[B, S, H, D]`` and the flash kernels' ``[B, H, S, D]``
is this mixer's to place, as it is the attention mixer's
(``mixers/attention.py::apply`` says what the two forms cost): at a batch of
two or more it asks for ``heads_major``, and XLA folds the turn of k, v, the
output and every cotangent into ``mla_rope``'s fusions, v's slice, the head
gate and ``W_o``'s product, where the merged entry made a copy of each; at a
batch of one the merge is a bitcast and it asks for nothing. q alone keeps
its copy (``_value_sequence_minor``): folded, the concatenation wrote
192-wide tiles of the kernels' layout at twice a copy's price, and the step
XLA scheduled round it no longer prefetched the shared expert's input, 10 ms
a step in two products outside the mixer, so all of it folded lost 2.0% of
Moonlight's tokens a second where this form gains 0.7%; q's cotangent pinned
as the attention mixer pins it lost the same prefetch (PERF.md, Findings,
PR 72)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from .... import runtime
from ....ops.attention import rope
from ..config import GPTConfig, LayerSpec
from ..parts import _attention, _norm, _tp_psum, readings, subkeys

KEY, NORM, SAVED_NAMES = "mla", "mla_norm", ()


def scope(spec: LayerSpec) -> str:
    """``attn``, its own parts ``mla_proj`` and ``mla_rope`` inside."""
    return "attn"


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    """The query projection ``[E, H, no-position | rotary]``, the
    down-projection to ``[latent | the shared rotary key]``, the latent's
    norm, the up-projection ``[rank, H, key's no-position part | value]``
    and the output projection."""
    E, H, rank, tp = cfg.embed_dim, cfg.num_heads, cfg.mla_kv_rank, cfg.tp_axis
    nope, rot, value = cfg.head_dim, cfg.mla_rope_dim, cfg.mla_value_dim
    k = subkeys(keys, 5 if cfg.mla_head_gate else 4)
    # One sigmoid gate a head on the attention's output, by head under tp.
    gate = {"w_gate": (P(None, tp), lambda: dense(k(4), (E, H), E))} \
        if cfg.mla_head_gate else {}

    return {
        **gate,
        "wq": (P(None, tp, None),
               lambda: dense(k(0), (E, H, nope + rot), E)),
        "wkv_a": (P(), lambda: dense(k(1), (E, rank + rot), E)),
        "kv_norm": (P(), lambda: norm((rank,))),
        "wkv_b": (P(None, tp, None),
                  lambda: dense(k(2), (rank, H, nope + value), rank)),
        "wo": (P(tp, None, None),
               lambda: dense(k(3), (H, value, E), H * value)),
    }


init, specs = readings(_parameters)


def _sequence_minor(q):
    return with_layout_constraint(q, Layout(major_to_minor=(0, 2, 3, 1)))


@jax.custom_vjp
def _value_sequence_minor(q):
    """``q``, ``[B, S, H, D]``, written with the sequence minor in memory
    (``[B, H, D, S]``, the layout XLA gave it under the merged entry, no
    lane of a 192-wide head padded), so that one copy turns it into the
    kernels' layout; its cotangent is left to fold (``apply``)."""
    return _sequence_minor(q)


_value_sequence_minor.defvjp(lambda q: (_sequence_minor(q), None),
                             lambda _, dq: (dq,))


def apply(cfg: GPTConfig, spec: LayerSpec, p, h, positions):
    """Latent attention (MLA, as training runs it: keys and values
    decompressed a head) on normed activations ``h`` ``[B, S, E]``, ``H``
    heads, ``dn = head_dim``, ``dr = mla_rope_dim``, ``dv = mla_value_dim``,
    ``r = mla_kv_rank``: ``q_h = [qn_h (dn) | qr_h (dr)] = h W_q``; ``[c0 (r)
    | kr0 (dr)] = h W_kv_a``; ``c = RMSNorm(c0)``; ``[kn_h (dn) | v_h (dv)] =
    c W_kv_b``; where ``spec.rope`` says so the rotary embedding on all
    ``dr`` dimensions of ``qr_h`` and of ``kr0``, **one** rotary key a token
    that every head shares; ``k_h = [kn_h | kr]``; the attention
    ``_attention`` picks, scores over ``dn + dr`` dimensions scaled by one
    over its root, values ``dv`` wide; under ``mla_head_gate`` times ``sigmoid((h W_g)_h)``
    a head; ``W_o``. No bias. Under a bound tp
    axis a rank holds a shard of the heads (``W_q``, ``W_kv_b``, ``W_o``)
    and makes the latent and the shared key whole."""
    nope, rot, rank = cfg.head_dim, cfg.mla_rope_dim, cfg.mla_kv_rank
    runtime.note_traced(
        "hvdtpu_spmd_mla_traces_total", heads=cfg.num_heads, nope_dim=nope,
        rope_dim=rot, value_dim=cfg.mla_value_dim, kv_rank=rank,
        q_rank="none", gate="head" if cfg.mla_head_gate else "none")
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
            # As in ``attention.apply``: every attention scales by one
            # over the root of the query's width, the rest goes onto q.
            q = q * (cfg.attention_multiplier * float(np.sqrt(nope + rot)))
    heads_major = q.shape[0] > 1
    if heads_major:
        q = _value_sequence_minor(q)
    attn = _attention(cfg, q, k, v, heads_major=heads_major)
    if cfg.mla_head_gate:
        with jax.named_scope("mla_gate"):
            open_ = jax.nn.sigmoid(jnp.einsum(
                "bse,eh->bsh", h, p["w_gate"].astype(cfg.dtype),
                preferred_element_type=jnp.float32))
            attn = (attn.astype(jnp.float32)
                    * open_[..., None]).astype(cfg.dtype)
    with jax.named_scope("mla_proj"):
        o = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(cfg.dtype))
    return _tp_psum(o, cfg)
