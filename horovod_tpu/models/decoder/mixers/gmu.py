"""The ``"gmu"`` mixer: a Gated Memory Unit (Ren et al., arXiv:2507.06607):
the layer computes no memory of its own but gates, token by token, the scan
output ``m`` an earlier state-space layer published (``READS``; ``mixers/
s6.py::PUBLISHES``): ``(silu(h W_1) * m) W_2``. Two matrices; the hidden
width column-parallel over a bound tp axis would need ``m``'s channels
sharded as well, which no producer does yet, so a bound tp axis is refused
with the producer's."""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config import GPTConfig, LayerSpec
from ..parts import _refuse_bound_axes, readings

KEY, NORM, SAVED_NAMES = "gmu", "gmu_norm", ()
READS = ("s6_scan",)


def scope(spec: LayerSpec) -> str:
    return "gmu"


def _parameters(cfg: GPTConfig, keys=None, dense=None, norm=None) -> dict:
    E, C = cfg.embed_dim, cfg.s6_channels
    return {"in_proj": (P(), lambda: dense(keys[0], (E, C), E)),
            "out_proj": (P(), lambda: dense(keys[1], (C, E), C))}


init, specs = readings(_parameters)


def apply(cfg: GPTConfig, spec: LayerSpec, p, h, positions, memory):
    """``(silu(h W_1) * memory) W_2`` on normed activations ``h`` ``[B, S,
    E]`` and the published scan output ``memory`` ``[B, S, s6_inner]``."""
    _refuse_bound_axes(cfg, "gated-memory")
    f32 = jnp.float32
    gate = jnp.einsum("bse,ec->bsc", h, p["in_proj"].astype(cfg.dtype))
    gated = (jax.nn.silu(gate.astype(f32)) * memory.astype(f32)).astype(
        cfg.dtype)
    return jnp.einsum("bsc,ce->bse", gated, p["out_proj"].astype(cfg.dtype))
