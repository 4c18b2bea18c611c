"""``gpt._attention``'s table (``models/decoder/parts.py``), read from the
traced program: which kernel and which exchange a value of ``GPTConfig.attention`` puts there, with the sp
axis bound and without, and that nothing but ``"dense"`` reaches the dense
reference. Traced (``jax.make_jaxpr``), never run: the Pallas interpreter
that the CPU lowers a kernel through keeps no kernel name.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.ops.flash_attention import KERNEL_FWD

B, S = 2, 16
FLASH = rf"\bname={KERNEL_FWD}\b"
RING, ULYSSES = r"\bppermute\b", r"\ball_to_all\b"
# The dense reference's float32 logits, [B, H, S, S]; sharded or not, no
# other value of the tiny model has this shape.
LOGITS = rf"f32\[{B},4,{S},{S}\]"


def _trace(attention: str, sp_bound: bool) -> str:
    """The jaxpr of ``gpt.forward``, whole or under a bound sp=2 axis."""
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=1, num_heads=4,
                        num_kv_heads=2, head_dim=8, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, tp_axis=None, sp_axis="sp",
                        attention=attention)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((B, S), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def forward(p, t, pos):
        return gpt.forward(p, t, pos, cfg)

    if sp_bound:
        seq = P(None, "sp")
        forward = jax.shard_map(forward, mesh=hvd.mesh(),
                                in_specs=(P(), seq, seq), out_specs=seq)
    return str(jax.make_jaxpr(forward)(params, tokens, positions))


# attention, sp bound -> (patterns in the program, patterns not in it), or
# the words the ValueError says.
TABLE = {
    ("flash", False): ([FLASH], [RING, ULYSSES, LOGITS]),
    ("flash", True): "'flash' is local attention.*'ring' or 'ulysses'",
    ("dense", False): ([LOGITS], [FLASH, RING, ULYSSES]),
    ("dense", True): "'dense' is local attention.*'ring' or 'ulysses'",
    ("ring", False): ([FLASH], [RING, ULYSSES, LOGITS]),
    ("ring", True): ([RING], [FLASH, ULYSSES, LOGITS]),
    ("ulysses", False): ([FLASH], [RING, ULYSSES, LOGITS]),
    ("ulysses", True): ([FLASH, ULYSSES], [RING, LOGITS]),
}


@pytest.mark.parametrize("attention, sp_bound", list(TABLE))
def test_attention_table(make_runtime, attention, sp_bound):
    make_runtime(mesh_shape={"dp": 4, "sp": 2})
    want = TABLE[attention, sp_bound]
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            _trace(attention, sp_bound)
        return
    text = _trace(attention, sp_bound)
    present, absent = want
    for pattern in present:
        assert re.search(pattern, text), pattern
    for pattern in absent:
        assert not re.search(pattern, text), pattern


@pytest.mark.parametrize("attention", ["ulysses_flash", "bogus"])
@pytest.mark.parametrize("sp_bound", [False, True])
def test_unknown_attention_raises(make_runtime, attention, sp_bound):
    make_runtime(mesh_shape={"dp": 4, "sp": 2})
    with pytest.raises(ValueError, match=f"unknown attention '{attention}'"):
        _trace(attention, sp_bound)
