"""The ``"dense"`` and ``"gated"`` feed-forwards: two matrices and a GELU,
or ``silu(gate) * up`` through three; the hidden width column-parallel over
a bound tp axis, the down-projection row-parallel followed by one ``psum``.
The matrices stay at the layer's root. The same names as the expert block's
module (``experts.py``), which ``gpt.FEED_FORWARDS`` holds beside it."""

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .config import GPTConfig, LayerSpec
from .parts import _tp_psum, readings

KEY, SCOPE = None, "mlp"
# The pre-activation (of a gated one the up product; the gate is made
# again): the block's widest product, 2 M bytes a token a layer.
SAVED_NAMES = ("ffn_pre_activation",)


def _parameters(cfg: GPTConfig, keys, spec: LayerSpec, carry,
                dense=None) -> dict:
    E, M, tp = cfg.embed_dim, cfg.mlp_dim, cfg.tp_axis
    table = {"w_up": (P(None, tp), lambda: dense(keys[1], (E, M), E)),
             "w_down": (P(tp, None), lambda: dense(keys[2], (M, E), M))}
    if spec.ff == "gated":
        table["w_gate"] = (P(None, tp), lambda: dense(keys[3], (E, M), E))
    return table


# init(keys, cfg, spec, carry, dense) (the layer's last four keys), specs(cfg,
# spec, carry)
init, specs = readings(_parameters)


def apply(cfg: GPTConfig, spec: LayerSpec, lp, h, router_state=None,
          early=None):
    """``(y, None, the router state it was given)``."""
    up = jnp.einsum("bse,em->bsm", h, lp["w_up"].astype(cfg.dtype))
    up = checkpoint_name(up, "ffn_pre_activation")
    if spec.ff == "gated":
        gate = jnp.einsum("bse,em->bsm", h, lp["w_gate"].astype(cfg.dtype))
        up = jax.nn.silu(gate) * up
    else:
        up = jax.nn.gelu(up)
    down = jnp.einsum("bsm,me->bse", up, lp["w_down"].astype(cfg.dtype))
    return _tp_psum(down, cfg), None, router_state
