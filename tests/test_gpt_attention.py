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


# ---- where the turn to the kernels' layout goes (PR 70) --------------------

# batch, head_dim, a head's norm on q and k -> what the attention mixer asks of
# ``flash_attention`` (``heads_major``: rank4) and whether it asks for q's
# cotangent sequence-minor (a ``layout_constraint`` in the backward pass):
# rank 4 at a batch of two or more with heads of one lane tile, and the
# cotangent pinned there unless a head's norm stands between it and q's
# projection.
TURNS = [
    (1, 128, False, "rank3", False),
    (2, 128, False, "rank4", True),
    (4, 128, False, "rank4", True),
    (2, 128, True, "rank4", False),
    (1, 128, True, "rank3", False),
    (2, 64, False, "rank3", False),
    (2, 64, True, "rank3", False),
    (2, 256, False, "rank3", False),
    (4, 256, True, "rank3", False),
]


def _the_turn(cfg, batch):
    """``(whether the traced gradient pins a layout, the flash entry's
    layouts by the counter)`` of ``cfg``'s one layer at ``batch`` sequences
    of 256 rows; and the loss and every parameter's gradient are the dense
    reference's whichever way the turn goes."""
    import dataclasses
    import numpy as np
    rows = 256
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, rows), 0, 64)
    positions = jnp.broadcast_to(jnp.arange(rows), (batch, rows))

    def loss(p, cfg):
        out = gpt.forward(p, tokens, positions, cfg)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    flash = jax.grad(lambda p: loss(p, cfg))
    pinned = "layout_constraint" in str(jax.make_jaxpr(flash)(params))
    fam = hvd.metrics()["hvdtpu_spmd_flash_layout_traces_total"]
    layouts = {(labels["layout"], int(labels["head_dim"]),
                int(labels["batch"])) for _, labels, _ in fam["samples"]}
    dense = jax.grad(lambda p: loss(
        p, dataclasses.replace(cfg, attention="dense")))
    got, want = jax.jit(flash)(params), jax.jit(dense)(params)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, r, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(r).max()) + 1e-9)
    return pinned, layouts


@pytest.mark.parametrize("batch, head_dim, head_norm, layout, pinned", TURNS)
def test_the_mixer_places_the_turn(make_runtime, batch, head_dim, head_norm,
                                   layout, pinned):
    """The attention mixer's choice by what it can see (its batch, its
    heads' width, its own norms), read from the counter and the traced
    gradient."""
    make_runtime(devices=jax.devices()[:1])
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=1, num_heads=4,
                        num_kv_heads=2, head_dim=head_dim, embed_dim=32,
                        mlp_dim=64, dtype=jnp.float32, tp_axis=None,
                        sp_axis=None, attention="flash",
                        qk_head_norm=head_norm)
    assert _the_turn(cfg, batch) == (pinned, {(layout, head_dim, batch)})


@pytest.mark.parametrize("rotary", [True, False], ids=["rope", "no-rope"])
@pytest.mark.parametrize("batch, layout",
                         [(1, "rank3"), (2, "rank4"), (4, "rank4")])
def test_the_mla_mixer_places_the_turn(make_runtime, batch, layout, rotary):
    """The latent-attention mixer's choice by its batch alone (PR 72): rank
    4 at two or more sequences whatever its rotations, a key head ``head_dim
    + mla_rope_dim`` wide beside a value head of another width, and there
    q's value pinned sequence-minor (the one ``layout_constraint``: q keeps
    its copy; PERF.md, Findings, PR 72)."""
    make_runtime(devices=jax.devices()[:1])
    nope, rot = 32, 16
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=1, num_heads=4,
                        head_dim=nope, mla_rope_dim=rot, mla_value_dim=24,
                        mla_kv_rank=16, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, tp_axis=None, sp_axis=None,
                        attention="flash", layers=(gpt.LayerSpec(
                            mixer="mla", rope=rotary, ff="gated"),))
    assert _the_turn(cfg, batch) == (batch > 1,
                                     {(layout, nope + rot, batch)})


@pytest.mark.parametrize("mixer", ["cca", "diff_attention"])
def test_the_other_mixers_have_not_asked(mixer):
    """CCA's q and k come out of a Mosaic kernel of its own and a
    differential layer's keys are 64 beside 128: unmeasured at rank 4
    (PERF.md, section 7), so their files do not name ``heads_major`` and
    their cells keep the parent's program."""
    import importlib
    import inspect
    module = importlib.import_module(
        f"horovod_tpu.models.decoder.mixers.{mixer}")
    assert "heads_major" not in inspect.getsource(module)
