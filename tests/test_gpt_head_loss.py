"""The GPT's head and loss as one rule over blocks of token rows (ISSUE 41,
``models/gpt.py::_head_loss``): the training loss and every gradient equal
``forward()`` and a plain float32 cross-entropy, whatever the blocks, the
mask, the head's kind, the logits' scaling and the compute dtype; the global
mean under a bound ``sp`` or ``ep`` axis; and no array of ``B S V`` elements
in the training step, which ``forward()`` alone still returns."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt

VOCAB, BATCH = 64, 2
# 16 rows' float32 logits over VOCAB, and four rows more: with it
# ``head_loss_rows`` gives three blocks of 16 for 48 tokens (16 divides them)
# and for 44 (no multiple of 8 within 20 does: the last block is the 12 left).
SMALL = 4 * VOCAB * 20


def tiny(**more) -> gpt.GPTConfig:
    return gpt.GPTConfig(**{**dict(
        vocab_size=VOCAB, num_layers=1, num_heads=2, num_kv_heads=1,
        head_dim=8, embed_dim=16, mlp_dim=32, tp_axis=None, sp_axis=None,
        attention="dense", dtype=jnp.float32), **more})


def dense_loss(params, tokens, targets, positions, cfg):
    """The reference by name: ``forward()``'s float32 logits ``[B, S, V]``
    and a plain cross-entropy over the targets that are not -1."""
    logits = gpt.forward(params, tokens, positions, cfg)
    assert logits.dtype == jnp.float32
    assert logits.shape == tokens.shape + (cfg.vocab_size,)
    mask = targets != -1
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[..., None], axis=-1)[..., 0]
    return (-jnp.sum(jnp.where(mask, picked, 0.0))
            / jnp.maximum(jnp.sum(mask), 1))


def with_budget(budget: int, f):
    """``f`` traced with a block's float32 logits held to ``budget`` bytes
    (the rule reads it while JAX traces, never in a step)."""
    @functools.wraps(f)
    def traced(*args):
        old = gpt._HEAD_LOSS_BLOCK_BYTES
        gpt._HEAD_LOSS_BLOCK_BYTES = budget
        try:
            return f(*args)
        finally:
            gpt._HEAD_LOSS_BLOCK_BYTES = old
    return traced


def batch(seq: int, masked: str):
    rng = np.random.default_rng(seq)
    tokens = rng.integers(0, VOCAB, (BATCH, seq), dtype=np.int32)
    targets = np.roll(tokens, -1, -1).reshape(-1)
    if masked == "some":
        targets[rng.random(targets.size) < 0.3] = -1
    elif masked == "a_block":       # the second block of 16 rows, whole
        targets[16:32] = -1
    elif masked == "all":
        targets[:] = -1
    positions = np.broadcast_to(np.arange(seq, dtype=np.int32), (BATCH, seq))
    return tokens, targets.reshape(BATCH, seq), positions


@functools.lru_cache(maxsize=None)
def compiled_pair(tied: bool, scaling: float, dtype: str, seq: int,
                  budget: int):
    """``(cfg, params, the rule's loss and gradients, the reference's)``,
    jitted once a configuration: the mask is data."""
    cfg = tiny(tie_embeddings=tied, logits_scaling=scaling,
               dtype=jnp.dtype(dtype))
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    got = jax.jit(with_budget(budget, jax.value_and_grad(
        lambda p, *d: gpt.loss_fn(p, *d, cfg))))
    want = jax.jit(jax.value_and_grad(lambda p, *d: dense_loss(p, *d, cfg)))
    return cfg, params, got, want


def assert_close(got, want, dtype: str):
    # float32: the two sum in another order. bfloat16: the rule rounds
    # softmax - onehot before the mean's 1/n, autodiff after it, and sums the
    # blocks' weight gradients in float32.
    rtol, atol = (2e-5, 2e-6) if dtype == "float32" else (3e-2, 1.5e-2)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), w, rtol=rtol,
            atol=atol * max(float(np.abs(w).max()), 1e-6),
            err_msg=jax.tree_util.keystr(path))


# 48 tokens in blocks of 16; 44 in two of 16 and one of 12; 48 in one block.
BLOCKS = {"divides": (24, SMALL, 16, 3), "remainder": (22, SMALL, 16, 3),
          "one_block": (24, gpt._HEAD_LOSS_BLOCK_BYTES, 48, 1)}


@pytest.mark.parametrize("masked", ["none", "some", "a_block", "all"])
@pytest.mark.parametrize("blocks", list(BLOCKS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("scaling", [1.0, 8.0])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_loss_and_gradients_match_forward_and_cross_entropy(
        tied, scaling, dtype, blocks, masked):
    """The loss and the gradient of every parameter: the layers and norms
    that produce the rows, ``lm_head``, and the tied ``embed`` with both of
    its contributions (the lookup's and the head's)."""
    seq, budget, rows, count = BLOCKS[blocks]
    assert gpt.head_loss_rows(BATCH * seq, VOCAB) == BATCH * seq  # shipped
    assert with_budget(budget, gpt.head_loss_rows)(BATCH * seq, VOCAB) == rows
    assert -(-BATCH * seq // rows) == count
    cfg, params, got, want = compiled_pair(tied, scaling, dtype, seq, budget)
    data = batch(seq, masked)
    (loss, grads), (want_loss, want_grads) = got(params, *data), want(
        params, *data)
    # bfloat16: XLA may keep a product's float32 where the program rounds
    # it (``xla_allow_excess_precision``), in one compiled form and not in
    # the other.
    np.testing.assert_allclose(
        float(loss), float(want_loss),
        rtol=2e-6 if dtype == "float32" else 3e-4, atol=1e-7)
    assert ("lm_head" in grads) != tied
    assert_close(grads, want_grads, dtype)
    if masked == "all":
        assert float(loss) == 0.0
        assert all(not np.asarray(g).any() for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("tokens,vocab,rows", [
    # The benchmark's cells: granite, starcoder2, olmoe, qwen3-next, trinity
    # and olmo-hybrid (a rank's tokens a step, its vocabulary).
    (8192, 100352, 2048), (8192, 49152, 2048), (8192, 50304, 2048),
    (16384, 18992, 2048), (16384, 25024, 2048), (8192, 12544, 2048),
    # A vocabulary whose 2048 rows of float32 logits pass the budget, and
    # divisors that are no power of two.
    (8192, 262144, 1024), (6000, 262144, 1000), (1000, 2 ** 20, 200),
    (10000, 49152, 2000),
    # No multiple of 8 that fits divides: an even split, the last one short.
    (8190, 100352, 2048), (5001, 64, 1672),
    # One block: the tests' sizes, and a vocabulary no eight rows fit.
    (512, 512, 512), (48, 64, 48), (2048, 64, 2048), (4, 2 ** 26, 4),
    (64, 2 ** 26, 8),
])
def test_rows_follow_from_tokens_and_vocabulary_alone(tokens, vocab, rows):
    got = gpt.head_loss_rows(tokens, vocab)
    assert got == rows
    fits = max(8, min(gpt._HEAD_LOSS_MOST_ROWS,
                      gpt._HEAD_LOSS_BLOCK_BYTES // (4 * vocab)))
    assert got <= fits and (got == tokens or got % 8 == 0)
    blocks = -(-tokens // got)
    assert blocks == -(-tokens // fits) or tokens % got == 0
    assert blocks * got - tokens < 8 * blocks


def test_the_rule_takes_no_option():
    """The block is a function of shapes: no field of the configuration, no
    variable of the environment, no model's name."""
    fields = {f.name for f in dataclasses.fields(gpt.GPTConfig)}
    assert not any(word in name for name in fields
                   for word in ("head_loss", "block_rows", "rows_per_block",
                                "logits_block", "loss_block"))
    import inspect
    source = inspect.getsource(gpt.head_loss_rows) + inspect.getsource(
        gpt._head_loss_fwd) + inspect.getsource(gpt._head_loss_block)
    assert "environ" not in source and "cfg" not in source


@pytest.mark.parametrize("blocks", list(BLOCKS))
def test_every_block_after_the_first_waits_for_the_one_before(blocks):
    """The blocks have one schedule, at every count of them: a block's rows
    wait for the running sum of the weight gradient over the blocks before
    it, so the rule's forward holds a barrier a block but the first, and
    none where the tokens fit one block."""
    seq, _, rows, count = BLOCKS[blocks]
    x, w = jnp.zeros((BATCH * seq, 16)), jnp.zeros((16, VOCAB))
    targets = jnp.zeros((BATCH * seq,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda x, w: gpt._head_loss_fwd(
        x, w, targets, None, False, 1.0, rows))(x, w)
    assert sum(eqn.primitive.name == "optimization_barrier"
               for eqn in jaxpr.eqns) == count - 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("factor", [3.5, -0.25])
def test_a_cotangent_other_than_one_scales_both_gradients(factor, dtype):
    cfg = tiny(tie_embeddings=True, logits_scaling=8.0,
               dtype=jnp.dtype(dtype))
    params = gpt.init_params(jax.random.PRNGKey(1), cfg)
    data = batch(24, "some")
    grads = jax.jit(with_budget(SMALL, jax.grad(
        lambda p: factor * gpt.loss_fn(p, *data, cfg))))(params)
    want = jax.jit(jax.grad(
        lambda p: factor * dense_loss(p, *data, cfg)))(params)
    assert_close(grads, want, dtype)


@pytest.mark.parametrize("masked", ["none", "some", "a_block"])
@pytest.mark.parametrize("rows", [48, 16, 4],
                         ids=["one_block", "three_blocks", "twelve_chained"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_weights_that_are_no_constants_receive_their_cotangent(
        tied, rows, masked):
    """The rule against ``jax.grad`` of a plain float32 weighted
    cross-entropy, at a cotangent other than one: the rows, the matrix and
    **the weights** (``g`` times each row's cross-entropy, nothing on a
    masked row), whatever the blocks. ``each`` is those cross-entropies, and
    carries no gradient."""
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(VOCAB, 16) if tied else (16, VOCAB)),
                    jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 2.0, 48), jnp.float32)
    targets = jnp.asarray(batch(24, masked)[1].reshape(-1))

    def plain(x, w, weights):
        logp = jax.nn.log_softmax(gpt._logits(x, w, tied, 2.0))
        picked = jnp.take_along_axis(
            logp, jnp.where(targets >= 0, targets, 0)[:, None], axis=-1)[:, 0]
        return -2.5 * jnp.sum(jnp.where(targets >= 0, weights * picked, 0.0))

    def rule(x, w, weights):
        total, each = gpt._head_loss_rows(x, w, targets, weights, tied, 2.0,
                                          rows)
        # ``each`` is a reading: what it is multiplied by here reaches no
        # gradient.
        return 2.5 * total + 0.0 * jnp.sum(each), each

    (got, each), grads = jax.jit(jax.value_and_grad(
        rule, argnums=(0, 1, 2), has_aux=True))(x, w, weights)
    want, want_grads = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2)))(x, w, weights)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for g, wg in zip(grads, want_grads):
        np.testing.assert_allclose(g, wg, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(grads[2], 2.5 * each, rtol=1e-6)
    assert not np.asarray(grads[2])[np.asarray(targets) < 0].any()
    # The sum alone is the rule's sum, and constants take no cotangent.
    np.testing.assert_allclose(
        gpt._head_loss(x, w, targets, weights, tied, 2.0, rows) * 2.5, want,
        rtol=2e-6)


@pytest.mark.parametrize("budget", [SMALL, gpt._HEAD_LOSS_BLOCK_BYTES],
                         ids=["three_blocks", "one_block"])
def test_with_the_expert_terms_added_under_has_aux(budget):
    """``value_and_grad(loss_and_aux, has_aux=True)`` of a sparse decoder:
    the cross-entropy is the reference's, the router's two terms are added
    to it, and the gradients are those of the reference's sum."""
    cfg = tiny(moe_every=1, num_experts=4, experts_per_token=2,
               load_balance_coef=0.01, router_z_coef=0.001)
    params = gpt.init_params(jax.random.PRNGKey(2), cfg)
    data = batch(24, "some")

    def reference(p):
        terms = gpt.loss_and_aux(p, *data, cfg)[1]
        return (dense_loss(p, *data, cfg)
                + cfg.load_balance_coef * terms["load_balance"]
                + cfg.router_z_coef * terms["router_z"])

    (loss, aux), grads = jax.jit(with_budget(budget, jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True)))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(reference))(params)
    np.testing.assert_allclose(float(aux["cross_entropy"]),
                               float(dense_loss(params, *data, cfg)),
                               rtol=2e-6)
    assert float(loss) > float(aux["cross_entropy"])
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    assert_close(grads, want_grads, "float32")


# ---- the global mean under a bound sp or ep axis ----------------------------

@pytest.mark.parametrize("budget", [4 * VOCAB * 8, gpt._HEAD_LOSS_BLOCK_BYTES],
                         ids=["blocks_of_8", "one_block"])
@pytest.mark.parametrize("axis", ["sp", "ep"])
def test_sharded_global_mean_is_the_unsharded_one(make_runtime, axis, budget):
    """Four ranks, each the rule over its own rows (16 of 64; the masked
    targets are spread unevenly over them): the loss every rank returns and
    the gradients are the unsharded reference's."""
    make_runtime(mesh_shape={axis: 4}, devices=jax.devices()[:4])
    more = (dict(sp_axis="sp", attention="ring") if axis == "sp" else
            dict(ep_axis="ep", moe_every=1, num_experts=4,
                 experts_per_token=2, load_balance_coef=0.0,
                 router_z_coef=0.0))
    cfg = tiny(num_heads=4, num_kv_heads=4, **more)
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, VOCAB, (4, 16), dtype=np.int32)
    targets = np.roll(tokens, -1, -1)
    targets[0, :] = -1          # a whole rank's rows under ep
    targets[:, :5] = -1         # most of a rank's under sp
    targets[2, 9:] = -1
    positions = np.broadcast_to(np.arange(16, dtype=np.int32), (4, 16))
    ref = dataclasses.replace(cfg, attention="dense", sp_axis=None,
                              ep_axis=None)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: dense_loss(p, tokens, targets, positions, ref)))(params)

    data = P(None, "sp") if axis == "sp" else P("ep")
    specs = gpt.param_specs(cfg)
    loss, grads = hvd.run_step(
        with_budget(budget, jax.value_and_grad(
            lambda p, *d: gpt.loss_fn(p, *d, cfg))),
        in_specs=(specs, data, data, data),
        out_specs=(P(), specs))(params, tokens, targets, positions)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-4,
                                   atol=2e-5 * float(jnp.abs(w).max()))


# ---- no [B, S, V] array in the training step --------------------------------

def sizes_in(jaxpr) -> list:
    """The element count of every value in ``jaxpr`` and, recursively, in
    the jaxprs its equations hold (a rule's two sides, a jitted call)."""
    sizes = [v.aval.size for v in jaxpr.invars + jaxpr.outvars
             if hasattr(v.aval, "size")]
    for eqn in jaxpr.eqns:
        sizes += [v.aval.size for v in eqn.outvars if hasattr(v.aval, "size")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes += sizes_in(sub)
    return sizes


def largest_in_hlo(text: str) -> int:
    shapes = re.findall(r"\b(?:f32|bf16|f16|s32|pred)\[([0-9,]+)\]", text)
    return max(int(np.prod([int(d) for d in dims.split(",")]))
               for dims in shapes)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("seq", [64, 60], ids=["divides", "remainder"])
def test_training_step_holds_no_array_of_b_s_v_elements(tied, seq):
    """``B S V`` is 128 x 1024 here, eight times the largest parameter, and
    a block holds 32 rows: nothing in the traced or the compiled gradient is
    as large as a quarter of the whole logits, which ``forward()`` alone
    still makes."""
    vocab, budget = 1024, 4 * 1024 * 32
    cfg = tiny(vocab_size=vocab, tie_embeddings=tied, logits_scaling=8.0)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.zeros((BATCH, seq), np.int32)
    data = (tokens, tokens, tokens)
    whole = BATCH * seq * vocab
    step = with_budget(budget, jax.value_and_grad(
        lambda p: gpt.loss_fn(p, *data, cfg)))
    assert max(sizes_in(jax.make_jaxpr(step)(params).jaxpr)) <= whole // 4
    compiled = jax.jit(step).lower(params).compile().as_text()
    assert largest_in_hlo(compiled) <= whole // 4

    logits = jax.make_jaxpr(
        lambda p: gpt.forward(p, tokens, tokens, cfg))(params)
    assert max(sizes_in(logits.jaxpr)) == whole
    assert logits.out_avals[0].shape == (BATCH, seq, vocab)
    assert logits.out_avals[0].dtype == jnp.float32
