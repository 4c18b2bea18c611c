"""``models/gpt.py`` as a **looped** stack (Ouro's: the same sandwich-normed
blocks run ``loop_passes`` times a step, the final norm at the end of every
pass and carried on, a head and a learned exit gate after every pass, the
loss the exit distribution's expected cross-entropy less a coefficient times
its entropy) against the plain reference the benchmark keeps
(``benchmarks/reference/gpt_loop_dp.py``): the tiny twin of the benchmark's
configuration, float32, seeded. And what the loop must not cost: one
all-reduce a parameter under ``dp``, and with one pass the program it was."""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models import gpt  # noqa: E402

from benchmarks.reference import gpt_loop_dp as reference  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "configs",
                       "ouro-2.6b.json")) as f:
    TWIN = json.load(f)
BATCH, S, PASSES = 2, 32, TWIN["total_ut_steps"]
BETA = 0.3      # three times the twin's: the term's gradient has to show
MODEL = dict(passes=PASSES, beta=BETA, rope_theta=float(TWIN["rope_theta"]),
             norm_eps=TWIN["rms_norm_eps"])


def twin(**kw):
    c = TWIN
    return gpt.GPTConfig(**{**dict(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        embed_dim=c["hidden_size"], mlp_dim=c["intermediate_size"],
        dtype=jnp.float32, tp_axis=None, sp_axis=None, attention="flash",
        gated_mlp=True, norms="pre_post", norm_eps=c["rms_norm_eps"],
        rope_theta=float(c["rope_theta"]), loop_passes=PASSES,
        exit_entropy_coef=BETA), **kw})


def seeded(cfg, seed=0):
    """Parameters with norm weights off one, so that a norm left out shows,
    and a gate that leans on the state and is not at a half on average."""
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def off_one(w):
        return 1 + 0.2 * jax.random.normal(next(key), w.shape)

    params["out_norm"] = off_one(params["out_norm"])
    for layer in params["layers"]:
        for name in ("attn_norm", "mixer_post_norm", "mlp_norm",
                     "mlp_post_norm"):
            layer[name] = off_one(layer[name])
    if "exit_gate" in params:
        params["exit_gate"] = {"w": params["exit_gate"]["w"] * 10.0,
                               "b": jnp.float32(0.4)}
    return params


def batch(seed=1, vocab=TWIN["vocab_size"]):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (BATCH, S), dtype=np.int32)
    targets = np.roll(tokens, -1, -1)
    targets[:, -1] = -1
    targets[1, 5:9] = -1            # some targets masked inside a sequence
    positions = np.broadcast_to(np.arange(S, dtype=np.int32),
                                (BATCH, S)).copy()
    return tokens, targets, positions


def assert_trees_close(got, want, rtol=2e-4, atol=2e-6):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.fixture(scope="module")
def referenced():
    """The reference's loss, parts and gradient on the seeded twin, once a
    module."""
    params, data = seeded(twin()), batch()
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.jit(jax.value_and_grad(
            lambda p: reference.shard_loss(p, *data, **MODEL),
            has_aux=True))(params)
    return params, data, loss, parts, grads


def program(cfg, params, data):
    return jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True))(params)


@pytest.mark.parametrize("attention, remat", [
    ("dense", "none"), ("flash", "none"), ("flash", "full")])
def test_looped_decoder_matches_the_reference(referenced, attention, remat):
    """Loss, per-pass losses, mean exit distribution, entropy, every
    parameter's gradient, the exit gate's by itself."""
    params, data, ref_loss, ref, ref_grads = referenced
    (loss, aux), grads = program(twin(attention=attention, remat=remat),
                                 params, data)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    for name in ("cross_entropy", "pass_losses", "exit_probs",
                 "exit_entropy"):
        np.testing.assert_allclose(aux[name], ref[name], rtol=1e-5,
                                   err_msg=name)
    assert aux["pass_losses"].shape == aux["exit_probs"].shape == (PASSES,)
    np.testing.assert_allclose(np.sum(aux["exit_probs"]), 1.0, rtol=1e-6)
    # The gate is 65 of the twin's parameters: it is held by itself, and it
    # is not nothing (the rows' cross-entropies reach it through p).
    gate, ref_gate = grads.pop("exit_gate"), ref_grads["exit_gate"]
    assert float(jnp.abs(ref_gate["w"]).max()) > 1e-4
    assert_trees_close(gate, ref_gate, rtol=1e-4, atol=1e-7)
    assert_trees_close(grads, {k: v for k, v in ref_grads.items()
                               if k != "exit_gate"})


def test_first_adamw_step_matches_the_reference(referenced):
    params, data, _, _, ref_grads = referenced
    lr, decay, eps = 1e-3, 1e-2, 1e-8
    opt = optax.adamw(lr, eps=eps, weight_decay=decay)
    (_, _), grads = program(twin(), params, data)
    updates, _ = jax.jit(lambda g, p: opt.update(g, opt.init(p), p))(
        grads, params)
    moved = float(optax.global_norm(updates))
    want = reference.adamw_first_update_norm(params, ref_grads, lr, decay,
                                             eps)
    np.testing.assert_allclose(moved, want, rtol=1e-4)


def test_the_reference_a_sequence_at_a_time_is_the_reference_whole(
        referenced, caplog):
    """``loss_and_grad`` (what the job's check calls: ``jax.grad`` over the
    Python loops of compiled parts, not compiled as a whole) gives what the
    whole shard compiled as one program gives, and **compiles a block forward and backward once a
    signature, whatever the passes, the layers and the sequences** (two
    signatures here: the seeded norm weights are float64 under the tests'
    x64, so a sequence's first call reads float32 rows and hands on
    float64; 64 calls a direction in all)."""
    params, data, ref_loss, ref, ref_grads = referenced
    # Two shards of the batch's two sequences, the second's the other way
    # round: the mean over shards of equal means is the mean.
    sharded = [np.stack([x, x[::-1]]) for x in data]
    with jax.log_compiles(), caplog.at_level("WARNING", logger="jax"), \
            jax.default_matmul_precision("highest"):
        loss, parts, grads = reference.loss_and_grad(params, *sharded,
                                                     **MODEL)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    for name, want in ref.items():
        np.testing.assert_allclose(parts[name], want, rtol=1e-5,
                                   err_msg=name)
    assert_trees_close(grads, ref_grads, rtol=1e-4, atol=1e-6)
    compiled = [r.getMessage() for r in caplog.records
                if "Finished XLA compilation of jit(block)" in r.getMessage()]
    assert len(compiled) == 4, compiled


def test_the_loop_is_an_unrolled_stack_of_copies(referenced):
    """T x L layers with copied parameters, the final norm between the
    copies written out: the same states, and a shared layer's gradient is
    the sum of its T copies'."""
    params, data, *_ = referenced
    tokens, _, positions = data
    cfg = twin(attention="dense")
    layers = cfg.num_layers
    one_pass = dataclasses.replace(cfg, loop_passes=1, exit_entropy_coef=0.0)

    def unrolled_states(copies):
        """``copies``: T x L layer dictionaries, each its own leaf."""
        states, x = [], None
        for t in range(PASSES):
            p = {**params, "layers": copies[t * layers:(t + 1) * layers]}
            if t == 0:
                x = gpt._hidden(p, tokens, positions, one_pass)[0]
            else:
                # A pass from a state: the blocks and the norm, no embedding.
                for spec, lp in zip(one_pass.plan, p["layers"]):
                    x = gpt._block(one_pass, spec, lp, x, positions)[0]
                x = gpt._norm(one_pass, x, params["out_norm"])
            states.append(x)
        return states

    def score(states):
        # Every pass's state weighs in, later passes more.
        return sum((t + 1) * jnp.sum(jnp.sin(s)) for t, s in
                   enumerate(states))

    copies = list(params["layers"]) * PASSES
    looped = jax.jit(lambda: gpt._passes(params, tokens, positions, cfg)[0])()
    for got, want in zip(looped, jax.jit(unrolled_states)(copies),
                         strict=True):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    shared = jax.jit(jax.grad(lambda layers_: score(gpt._passes(
        {**params, "layers": layers_}, tokens, positions, cfg)[0])))(
            params["layers"])
    by_copy = jax.jit(jax.grad(lambda c: score(unrolled_states(c))))(copies)
    summed = [jax.tree.map(lambda *g: sum(g), *by_copy[i::layers])
              for i in range(layers)]
    assert_trees_close(shared, summed, rtol=1e-4, atol=1e-5)
    # No copy's gradient is nothing: every pass reaches every layer.
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(by_copy))


def test_exit_probabilities_add_up_and_the_entropy_is_theirs():
    rng = np.random.default_rng(3)
    rows, width = 24, 16
    x = jnp.asarray(rng.normal(size=(PASSES * rows, width)), jnp.float32)
    # A gate that closes and one that opens, beside ordinary ones.
    x = x.at[0].mul(40.0).at[1].mul(-40.0)
    gate = {"w": jnp.asarray(rng.normal(size=width), jnp.float32),
            "b": jnp.float32(-0.2)}
    p, entropy = gpt._exit_distribution(gpt._exit_scores(gate, x, PASSES))
    assert p.shape == (PASSES, rows) and entropy.shape == (rows,)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(x @ gate["w"] + gate["b"]).reshape(PASSES, rows)
    want = [lam[0], lam[1] * (1 - lam[0]),
            lam[2] * (1 - lam[0]) * (1 - lam[1]),
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])]
    np.testing.assert_allclose(p, jnp.stack(want), rtol=1e-5, atol=1e-7)
    safe = jnp.where(p > 0, p, 1.0)
    np.testing.assert_allclose(entropy, -jnp.sum(p * jnp.log(safe), axis=0),
                               rtol=1e-5, atol=1e-6)
    assert np.all(np.isfinite(entropy))
    # Its gradient is finite where a gate has closed.
    grads = jax.grad(lambda g: jnp.sum(gpt._exit_distribution(
        gpt._exit_scores(g, x, PASSES))[1]))(gate)
    assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(grads))


def test_one_pass_is_the_stack_as_it_was():
    """No gate among the parameters, no new part in ``aux``, and the loss
    and gradients of a one-pass configuration whatever the coefficient."""
    cfg = twin(loop_passes=1, exit_entropy_coef=0.0, attention="dense")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    assert "exit_gate" not in params
    assert "exit_gate" not in gpt.param_specs(cfg)
    assert set(gpt.param_specs(twin())["exit_gate"]) == {"w", "b"}
    data = batch()
    (loss, aux), grads = program(cfg, params, data)
    assert set(aux) == {"cross_entropy"}
    np.testing.assert_array_equal(loss, aux["cross_entropy"])
    # The plain float32 cross-entropy of forward()'s logits.
    tokens, targets, positions = data

    def plain(p):
        logp = jax.nn.log_softmax(gpt.forward(p, tokens, positions, cfg))
        keep = targets != -1
        picked = jnp.take_along_axis(
            logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)

    want, want_grads = jax.jit(jax.value_and_grad(plain))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert_trees_close(grads, want_grads, rtol=1e-4, atol=1e-6)


def test_forward_gives_the_last_passes_logits(referenced):
    params, data, *_ = referenced
    tokens, _, positions = data
    cfg = twin(attention="dense")
    with jax.default_matmul_precision("highest"):
        states = reference.pass_states(
            params, tokens, positions, PASSES, MODEL["rope_theta"],
            MODEL["norm_eps"])
        want = states[-1] @ params["lm_head"]
    np.testing.assert_allclose(gpt.forward(params, tokens, positions, cfg),
                               want, rtol=2e-4, atol=2e-5)


def test_a_looped_stack_takes_no_weights_of_a_caller():
    cfg = twin(attention="dense")
    params, (tokens, targets, positions) = seeded(cfg), batch()
    with pytest.raises(ValueError, match="loop_passes=4"):
        gpt.loss_and_aux(params, tokens, targets, positions, cfg, -1,
                         jnp.ones(targets.shape, jnp.float32))


@pytest.mark.parametrize("more, named", [
    (dict(layers=(gpt.LayerSpec(mixer="s6", ff=None,
                                publishes=("s6_scan_out",)),
                  gpt.LayerSpec(mixer="gmu", ff="dense",
                                reads=("s6_scan_out",)))), "publishes"),
    (dict(moe_every=1, router_kind="mlp"), "router_kind='mlp'"),
    (dict(diffusion_block=4), "diffusion_block=4"),
    (dict(loop_passes=0), "at least 1"),
])
def test_layer_plan_refuses_what_a_pass_cannot_hand_on(more, named):
    with pytest.raises(ValueError, match=re.escape(named)):
        gpt.layer_plan(twin(**{"gated_mlp": False, **more}))
    # The same stack in one pass is a plan.
    if more.get("loop_passes") != 0:
        assert gpt.layer_plan(twin(**{"gated_mlp": False, **more,
                                      "loop_passes": 1}))


# ---- under dp: one all-reduce a parameter, remat, the kept bytes ------------

def _dp_step(cfg):
    def step(params, tokens, targets, positions):
        loss, grads = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, positions, cfg))(params)
        # The mean over the ranks of each rank's loss and gradient.
        return (hvd.allreduce(loss, op=hvd.Average),
                jax.tree.map(lambda g: g / hvd.size(), grads))
    specs = gpt.param_specs(cfg)
    return hvd.run_step(step, in_specs=(specs,) + (hvd.batch_spec(0),) * 3,
                        out_specs=(hvd.REPLICATED, specs))


def _all_reduces(cfg, params, data) -> int:
    """``all_reduce`` operations in the lowered step, before XLA's combiner
    merges them: one a mark's transpose."""
    text = _dp_step(cfg).lower(params, *data).as_text()
    return len(re.findall(r"\bstablehlo\.all_reduce\b", text))


def test_one_all_reduce_a_parameter_under_dp(make_runtime):
    """Four passes read every layer four times, and the gradient of each
    parameter crosses the ranks once: as many all-reduces as at one pass,
    and the exit gate's two."""
    make_runtime(mesh_shape={"dp": 4}, devices=jax.devices()[:4])
    data = batch()
    data = tuple(np.concatenate([x, x]) for x in data)      # 4 sequences
    looped = twin(attention="dense")
    once = dataclasses.replace(looped, loop_passes=1, exit_entropy_coef=0.0)
    params = seeded(looped)
    plain = {k: v for k, v in params.items() if k != "exit_gate"}
    leaves = len(jax.tree.leaves(plain))
    at_one = _all_reduces(once, plain, data)
    assert at_one >= leaves
    assert _all_reduces(looped, params, data) == at_one + 2
    # And the step is the mean of the ranks' own: loss and gradients.
    loss, grads = _dp_step(looped)(params, *data)
    alone = [program(looped, params, tuple(x[i:i + 1] for x in data))
             for i in range(4)]
    np.testing.assert_allclose(
        loss, np.mean([float(one[0][0]) for one in alone]), rtol=1e-5)
    assert_trees_close(grads, jax.tree.map(
        lambda *g: sum(g) / 4, *(one[1] for one in alone)),
        rtol=5e-4, atol=1e-6)


def test_remat_keeps_a_pass_of_bytes_a_pass(make_runtime):
    """``remat="full"`` gives the loss and gradients of ``"none"``.
    ``hvdtpu_spmd_remat_saved_bytes_total`` counts what a **traced** block
    keeps, and JAX traces a block once for all its applications (the same
    function, specification and shapes): the family reads at four passes
    what it reads at one, as it reads one layer's for a stack of equal
    layers; that a step keeps it once an application, T x L times, is the
    compiler's account (``tok_step_memory_gib``), not this family's."""
    make_runtime(mesh_shape={"dp": 1}, devices=jax.devices()[:1])
    # JAX keeps what it traced of a checkpointed block, and the policy that
    # counts the bytes runs when it traces.
    jax.clear_caches()
    data = batch()

    def kept(cfg, params):
        before = {labels["name"]: value for _, labels, value in
                  hvd.metrics().get("hvdtpu_spmd_remat_saved_bytes_total",
                                    {}).get("samples", [])}
        out = program(cfg, params, data)
        after = {labels["name"]: value for _, labels, value in
                 hvd.metrics()["hvdtpu_spmd_remat_saved_bytes_total"][
                     "samples"]}
        return out, {name: value - before.get(name, 0)
                     for name, value in after.items()}

    looped = twin(remat="full")
    params = seeded(looped)
    ((loss, _), grads), at_four = kept(looped, params)
    once = dataclasses.replace(looped, loop_passes=1, exit_entropy_coef=0.0)
    _, at_one = kept(once, {k: v for k, v in params.items()
                            if k != "exit_gate"})
    assert at_one and set(at_four) == set(at_one)
    assert at_four == at_one
    (want, _), want_grads = program(twin(remat="none"), params, data)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert_trees_close(grads, want_grads, rtol=1e-5, atol=2e-6)
    samples = {(labels["passes"], labels["layers"]) for _, labels, _ in
               hvd.metrics()["hvdtpu_spmd_loop_passes_total"]["samples"]}
    assert samples == {(str(PASSES), str(looped.num_layers))}
