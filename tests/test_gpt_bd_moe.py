"""``models/gpt.py`` trained by diffusion over blocks (SDAR's block:
grouped-query attention with a norm a head under the block-diffusion mask,
an expert block of which this rank holds a share; the noised and the clean
copy of a sequence as one pass of ``2 L`` rows; the loss on the masked
tokens weighted by ``1 / t`` over the data tokens, the head over the noised
half alone) against the plain reference the benchmark keeps
(``benchmarks/reference/gpt_bd_moe_dp.py``): the tiny twin of the benchmark's
configuration, float32, seeded. And that nothing leaks: no noised row sees
its own clean block, no clean row a noised token."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu.models import gpt  # noqa: E402

from benchmarks.reference import gpt_bd_moe_dp as reference  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "configs",
                       "sdar-30b-a3b-chat.json")) as f:
    TWIN = json.load(f)
BATCH, L, BLOCK = 2, 32, TWIN["block_length"]
MASK_ID = TWIN["vocab_size"] - 1
FIRST = 4       # rank 1 of 4: a share that does not start at expert 0
MODEL = dict(block=BLOCK, top_k=TWIN["num_experts_per_tok"],
             first_expert=FIRST, rope_theta=float(TWIN["rope_theta"]),
             norm_eps=TWIN["rms_norm_eps"])
COEF = 0.01     # ten times the twin's: the term's gradient has to show


def twin(**kw):
    c = TWIN
    return gpt.GPTConfig(**{**dict(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        embed_dim=c["hidden_size"], mlp_dim=c["moe_intermediate_size"],
        dtype=jnp.float32, tp_axis=None, sp_axis=None, attention="flash",
        moe_every=1, num_experts=c["published"]["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        experts_held=c["num_experts"], first_expert=FIRST,
        renormalize_experts=c["norm_topk_prob"], load_balance_coef=COEF,
        qk_head_norm=True, norm_eps=c["rms_norm_eps"],
        rope_theta=float(c["rope_theta"]), diffusion_block=BLOCK), **kw})


def seeded(cfg, seed=0):
    """Parameters with norm weights off one, so that a norm left out
    shows."""
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
            layer[name] = 1 + 0.2 * jax.random.normal(next(key),
                                                      layer[name].shape)
    return params


def noised(seed=1, length=L):
    return reference.noised_batch(np.random.default_rng(seed), BATCH, length,
                                  BLOCK, TWIN["noise_eps"], MASK_ID)


def program_loss(cfg, params, data):
    tokens, targets, positions, weights = data
    return gpt.loss_and_aux(params, tokens, targets, positions, cfg, -1,
                            weights, targets.size)


def assert_trees_close(got, want, rtol=2e-4, atol=2e-6):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("attention, remat", [
    ("dense", "none"), ("flash", "none"), ("flash", "full")])
def test_decoder_matches_the_reference(attention, remat):
    """Loss, its parts, every parameter's gradient."""
    cfg = twin(attention=attention, remat=remat)
    params, data = seeded(cfg), noised()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: program_loss(cfg, p, data), has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.shard_loss(p, *data, load_balance_coef=COEF,
                                           **MODEL), has_aux=True))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["cross_entropy"], ref["cross_entropy"],
                               rtol=1e-5)
    np.testing.assert_allclose(aux["load_balance"], ref["load_balance"],
                               rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"],
                                  np.asarray(ref["counts"], np.int32))
    # Every one of the 2 L rows is routed, the clean as the noised.
    assert int(aux["counts"].sum()) == cfg.num_layers * BATCH * 2 * L \
        * cfg.experts_per_token
    assert_trees_close(grads, ref_grads)


def test_first_adamw_step_matches_the_reference():
    cfg = twin()
    params, data = seeded(cfg), noised()
    lr, decay, eps = 1e-3, 1e-2, 1e-8
    opt = optax.adamw(lr, eps=eps, weight_decay=decay)
    grads = jax.jit(jax.grad(lambda p: program_loss(cfg, p, data)[0]))(params)
    updates, _ = jax.jit(lambda g, p: opt.update(g, opt.init(p), p))(
        grads, params)
    with jax.default_matmul_precision("highest"):
        ref_grads = jax.jit(jax.grad(lambda p: reference.shard_loss(
            p, *data, load_balance_coef=COEF, **MODEL)[0]))(params)
    np.testing.assert_allclose(
        float(optax.global_norm(updates)),
        reference.adamw_first_update_norm(params, ref_grads, lr, decay, eps),
        rtol=1e-3)


# What the reference must notice: each is one of the method's or the
# configuration's own mechanisms left out of the program's side.
@pytest.mark.parametrize("change", [
    "causal-mask", "no-weights", "divisor-the-masked", "shifted",
    "head-on-the-clean-half", "no-renormalisation", "no-head-norm",
    "other-share", "other-block"])
def test_the_reference_notices(change):
    cfg = twin(**{"causal-mask": dict(diffusion_block=None),
                  "no-renormalisation": dict(renormalize_experts=False),
                  "no-head-norm": dict(qk_head_norm=False),
                  "other-share": dict(first_expert=0),
                  "other-block": dict(diffusion_block=2 * BLOCK)}.get(
                      change, {}))
    params, data = seeded(twin()), noised()
    if change == "no-head-norm":
        params = {**params, "layers": [
            {k: v for k, v in layer.items() if k not in ("q_norm", "k_norm")}
            for layer in params["layers"]]}
    tokens, targets, positions, weights = data
    masked = targets != -1
    got = {
        "no-weights": lambda: gpt.loss_and_aux(
            params, tokens, targets, positions, cfg, -1, None,
            targets.size),
        "divisor-the-masked": lambda: gpt.loss_and_aux(
            params, tokens, targets, positions, cfg, -1, weights),
        "shifted": lambda: gpt.loss_and_aux(
            params, tokens, np.roll(targets, -1, axis=1), positions, cfg, -1,
            weights, targets.size),
        "head-on-the-clean-half": lambda: gpt.loss_and_aux(
            params, np.roll(tokens, L, axis=1), targets, positions, cfg, -1,
            weights, targets.size),
        # Without ``diffusion_block`` short targets raise: the causal
        # program is handed the clean half's as none.
        "causal-mask": lambda: gpt.loss_and_aux(
            params, tokens, np.pad(targets, ((0, 0), (0, L)),
                                   constant_values=-1), positions, cfg, -1,
            np.pad(weights, ((0, 0), (0, L))), targets.size),
    }.get(change, lambda: program_loss(cfg, params, data))
    got = jax.jit(got)()[0]
    assert masked.any() and not masked.all()
    want = _reference_loss()
    assert abs(float(got) - float(want)) > 1e-3 * float(want), (got, want)


@functools.cache
def _reference_loss():
    """The reference's loss on ``seeded(twin())`` and ``noised()``, which no
    change of ``test_the_reference_notices`` moves: made once for the nine."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: reference.shard_loss(
            p, *noised(), load_balance_coef=COEF, **MODEL)[0])(seeded(twin()))


@functools.partial(jax.jit, static_argnums=0)
def _noised_logits(cfg, params, tokens, positions):
    return gpt.forward(params, tokens, positions, cfg)[:, :L]


@functools.partial(jax.jit, static_argnums=0)
def _hidden(cfg, params, tokens, positions):
    return gpt._hidden(params, tokens, positions, cfg)[0]


@pytest.mark.parametrize("attention", ["flash", "dense"])
def test_nothing_leaks(attention):
    """A change to clean block ``b`` leaves the logits of the noised blocks
    up to and including ``b`` as they are (and moves a later one's); a
    change to any noised token leaves every clean row's hidden state as it
    is; a change to noised block ``b`` moves no other noised block."""
    cfg = twin(attention=attention)
    params = seeded(cfg)
    tokens, _, positions, _ = noised()
    base_logits = _noised_logits(cfg, params, tokens, positions)
    base_hidden = _hidden(cfg, params, tokens, positions)
    b = 3
    rows = slice(b * BLOCK, (b + 1) * BLOCK)

    def changed(at):
        other = np.array(tokens)
        other[:, at] = (other[:, at] + 1) % MASK_ID
        return other

    # Clean block b: the clean rows are [L, 2 L).
    clean = changed(slice(L + b * BLOCK, L + (b + 1) * BLOCK))
    logits = _noised_logits(cfg, params, clean, positions)
    np.testing.assert_array_equal(logits[:, :(b + 1) * BLOCK],
                                  base_logits[:, :(b + 1) * BLOCK])
    assert np.abs(logits[:, (b + 1) * BLOCK:]
                  - base_logits[:, (b + 1) * BLOCK:]).max() > 1e-4
    # Noised block b.
    noised_changed = changed(rows)
    hidden = _hidden(cfg, params, noised_changed, positions)
    np.testing.assert_array_equal(hidden[:, L:], base_hidden[:, L:])
    logits = _noised_logits(cfg, params, noised_changed, positions)
    others = np.r_[0:b * BLOCK, (b + 1) * BLOCK:L]
    np.testing.assert_array_equal(logits[:, others], base_logits[:, others])
    assert np.abs(logits[:, rows] - base_logits[:, rows]).max() > 1e-4
    # Every noised token at once: no clean row moves.
    hidden = _hidden(cfg, params, changed(slice(0, L)), positions)
    np.testing.assert_array_equal(hidden[:, L:], base_hidden[:, L:])


def test_the_references_logits_do_not_leak_either():
    cfg = twin()
    params = seeded(cfg)
    tokens, _, positions, _ = noised()
    b = 3
    other = np.array(tokens)
    at = slice(L + b * BLOCK, L + (b + 1) * BLOCK)
    other[:, at] = (other[:, at] + 1) % MASK_ID
    model = {k: v for k, v in MODEL.items()}
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda t: reference.noised_logits(
            params, t, positions, **model))
        base, moved = logits(tokens), logits(other)
        np.testing.assert_allclose(
            base, _noised_logits(cfg, params, tokens, positions), atol=2e-4)
    np.testing.assert_array_equal(moved[:, :(b + 1) * BLOCK],
                                  base[:, :(b + 1) * BLOCK])
    assert np.abs(moved - base).max() > 1e-4


@pytest.mark.parametrize("seed", [0, 7, 2147485001])
def test_the_jobs_noised_batch_is_the_references(seed):
    from benchmarks.jobs import gpt_bd_moe_dp as job

    args = (3, 64, BLOCK, TWIN["noise_eps"], MASK_ID)
    got = job._noised(np.random.default_rng(seed), *args)
    want = reference.noised_batch(np.random.default_rng(seed), *args)
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    tokens, targets, positions, weights = got
    masked = tokens[:, :64] == MASK_ID
    # The clean half is the data; a masked token is its target; one weight
    # a block, 1 / t with t in [eps, 1).
    np.testing.assert_array_equal(targets[masked], tokens[:, 64:][masked])
    assert (targets[~masked] == -1).all() and (tokens[:, 64:] < MASK_ID).all()
    np.testing.assert_array_equal(tokens[:, :64][~masked],
                                  tokens[:, 64:][~masked])
    blocks = weights.reshape(3, -1, BLOCK)
    assert (blocks == blocks[..., :1]).all()
    assert (weights > 1).all() and (weights <= 1 / TWIN["noise_eps"]).all()
    np.testing.assert_array_equal(positions[:, :64], positions[:, 64:])


def test_the_references_mask_is_the_three_clauses_pair_by_pair():
    keep = reference.block_diffusion_mask(12, 4)
    for q in range(24):
        for k in range(24):
            bq, bk = q % 12 // 4, k % 12 // 4
            assert keep[q, k] == (
                (q < 12 and k < 12 and bk == bq)
                or (q < 12 and k >= 12 and bk < bq)
                or (q >= 12 and k >= 12 and bk <= bq)), (q, k)


def _before(params, tokens, targets, positions, cfg):
    """The loss as ``loss_and_aux`` gave it before it took weights: the mean
    next-token cross-entropy over the targets kept, from the logits."""
    logp = jax.nn.log_softmax(gpt.forward(params, tokens, positions, cfg))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)


def test_without_weights_the_loss_is_what_it_was():
    """No weights, no divisor, targets as long as the tokens: the mean over
    the targets kept, and ones for weights with the count for divisor say
    the same."""
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, head_dim=8, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, tp_axis=None, sp_axis=None,
                        attention="flash")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 64)
    targets = jnp.roll(tokens, -1, axis=-1).at[:, -5:].set(-1)
    positions = jnp.broadcast_to(jnp.arange(48), (2, 48))
    f = lambda p, *more: gpt.loss_fn(p, tokens, targets, positions, cfg,
                                     *more)
    loss, grads = jax.jit(jax.value_and_grad(f))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _before(p, tokens, targets, positions, cfg)))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert_trees_close(grads, want_grads, rtol=1e-4, atol=1e-7)
    kept = float(jnp.sum(targets != -1))
    same, same_grads = jax.jit(jax.value_and_grad(
        lambda p: f(p, -1, jnp.ones(targets.shape), kept)))(params)
    np.testing.assert_allclose(same, loss, rtol=1e-6)
    assert_trees_close(same_grads, grads, rtol=1e-5, atol=1e-8)
    # Weights scale a target's term and its gradient; the divisor divides.
    doubled = gpt.loss_fn(params, tokens, targets, positions, cfg, -1,
                          2 * jnp.ones(targets.shape), 4 * kept)
    np.testing.assert_allclose(doubled, loss / 2, rtol=1e-6)


@pytest.mark.parametrize("block, rows", [(None, 24), (None, 47), (4, 16)])
def test_short_targets_are_the_noised_halfs_alone(block, rows):
    """Fewer targets than rows mean the noised half under
    ``diffusion_block``, half the rows, and nothing else: a causal model
    handed short targets raises by name and trains on no prefix."""
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=1, num_heads=4,
                        num_kv_heads=2, head_dim=8, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, tp_axis=None, sp_axis=None,
                        attention="dense", diffusion_block=block)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 48), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(48), (2, 48))
    with pytest.raises(ValueError, match="diffusion_block"):
        gpt.loss_fn(params, tokens, jnp.zeros((2, rows), jnp.int32),
                    positions, cfg)


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """What the reference does so that the timed step's own sample fits a
    chip (the logits of a few query rows at a time, each block's rows of the
    mask from the three clauses; the head a few rows at a time) changes no
    number: blocks of 8 query rows and 16 head rows against one block of
    each."""
    params, data = seeded(twin()), noised()
    def loss_and_grad():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(lambda p: reference.shard_loss(
                p, *data, load_balance_coef=COEF, **MODEL)[0]))(params)
    whole, whole_grad = loss_and_grad()
    monkeypatch.setattr(reference, "LOGIT_ELEMENTS", 8 * 2 * L)
    monkeypatch.setattr(reference, "HEAD_ROWS", 16)
    blocks, blocks_grad = loss_and_grad()
    np.testing.assert_allclose(blocks, whole, rtol=1e-6)
    assert_trees_close(blocks_grad, whole_grad, rtol=1e-4, atol=1e-7)


def test_the_planted_faults_are_what_they_say(monkeypatch):
    """The two faults of the mask that the cell's check must catch
    (``scripts/check_sweep.py --variants bd_own_clean_block
    bd_tile_dropped``, ``benchmarks/tests/test_bd_faults.py``): the leaky
    ``keep`` adds each noised row's own clean block and nothing else; the
    faulty table lacks one tile of the cell's 80, the last noised query
    tile's first clean key tile."""
    from horovod_tpu.ops import flash_attention as fa

    mask = fa.Mask(block_diffusion=4, half=16)
    at = jnp.arange(32)
    q, k = at[:, None], at[None, :]
    leak = np.asarray(reference.own_clean_block_keep(mask, q, k, 32)) \
        & ~np.asarray(mask.keep(q, k, 32))
    rows, keys = np.nonzero(leak)
    assert len(rows) == 16 * 4 and (rows < 16).all() \
        and (rows // 4 == (keys - 16) // 4).all()
    cell = fa.Mask(block_diffusion=4, half=8192)
    whole = {tuple(t[:2]) for t in cell.kept_tiles(16, 16, 1024, 1024).T}
    monkeypatch.setattr(fa.Mask, "tile_kept",
                        reference.tile_dropped(fa.Mask.tile_kept))
    left = {tuple(t[:2]) for t in cell.kept_tiles(16, 16, 1024, 1024).T}
    assert len(whole) == 80 and whole - left == {(7, 8)} and left < whole
