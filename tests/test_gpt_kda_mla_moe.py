"""``models/gpt.py`` as Ling-3.0-flash's layers (Kimi delta attention: a
delta-rule state that decays a key channel, from a gate bounded below, an
RMSNorm a head under one sigmoid gate a head; latent attention whose output
goes under one sigmoid gate a head; a dense SiLU-gated feed-forward, then
expert sublayers under a sigmoid router with a selection bias that chooses
inside the best groups of experts, renormalised and scaled weights and an
ungated shared expert; a rank's share of the experts) against the plain
reference the benchmark keeps (``benchmarks/reference/gpt_kda_mla_moe_dp.py``:
KDA one token a step, the grouped choice written plainly): float32, tiny
sizes, seeded.
"""

import collections
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec
from horovod_tpu.models.decoder import experts
from horovod_tpu.models.decoder.mixers import kda as kda_mixer
from horovod_tpu.ops import kda as kda_ops
from horovod_tpu.parallel.moe import kept_groups

from benchmarks.reference import gpt_kda_mla_moe_dp as reference

B, S, EMBED = 2, 64, 32
HEADS, NOPE, ROT, VALUE, RANK, KDA_DIM = 4, 8, 4, 8, 16, 8
EXPERTS, HELD, TOP_K, SCALE, GROUPS, KEPT = 16, 4, 3, 2.5, 4, 2
RATE = 0.001
MODEL = dict(top_k=TOP_K, route_scale=SCALE, first_expert=0, groups=GROUPS,
             kept=KEPT, rope_theta=6e6, norm_eps=1e-6, lower_bound=-5.0)


def ling(**kw):
    """KDA + dense, KDA + experts, MLA + experts."""
    plan = (LayerSpec(mixer="kda", ff="gated"),
            LayerSpec(mixer="kda", ff="experts"),
            LayerSpec(mixer="mla", ff="experts"))
    return gpt.GPTConfig(**{**dict(
        vocab_size=64, num_layers=3, num_heads=HEADS, head_dim=NOPE,
        mla_rope_dim=ROT, mla_value_dim=VALUE, mla_kv_rank=RANK,
        mla_head_gate=True, kda_heads=HEADS, kda_key_dim=KDA_DIM,
        kda_value_dim=KDA_DIM, kda_chunk=32, embed_dim=EMBED, mlp_dim=48,
        expert_dim=16, shared_expert_dim=16, shared_expert_gate=False,
        dtype=jnp.float32, tp_axis=None, sp_axis=None, attention="dense",
        layers=plan, num_experts=EXPERTS, experts_per_token=TOP_K,
        experts_held=HELD, first_expert=0, router_score="sigmoid",
        router_bias=True, renormalize_experts=True, route_scale=SCALE,
        router_groups=GROUPS, router_groups_kept=KEPT, rope_theta=6e6,
        norm_eps=1e-6), **kw})


@functools.lru_cache(maxsize=None)
def _seeded(held, seed):
    cfg = ling(experts_held=held)

    def make():
        params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
        key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

        def off(leaf, by):
            return leaf + by * jax.random.normal(next(key), leaf.shape,
                                                 leaf.dtype)

        for layer in params["layers"]:
            layer["mlp_norm"] = off(layer["mlp_norm"], 0.2)
            if "kda" in layer:
                layer["kda_norm"] = off(layer["kda_norm"], 0.2)
                for name in ("norm", "dt_bias"):
                    layer["kda"][name] = off(layer["kda"][name], 0.2)
            else:
                layer["mla_norm"] = off(layer["mla_norm"], 0.2)
            if "moe" in layer:
                layer["moe"]["router_bias"] = off(
                    layer["moe"]["router_bias"], 0.05)
        return params

    return jax.jit(make)()


def _data(seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 64, (B, S)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return tokens, jnp.roll(tokens, -1, axis=1), positions


def _tree_close(got, want, rtol):
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    for (path, a), b in zip(flat_got, flat_want, strict=True):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) <= rtol * scale, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("held", [HELD, EXPERTS])
def test_loss_and_gradient_match_the_reference(held):
    """A rank's share and every expert held: loss, counts, every leaf's
    gradient (the biases' is zero on both sides)."""
    cfg, params = ling(experts_held=held), _seeded(held, 3)
    data = _data()
    (loss, aux), grad = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        (want, ref_aux), ref_grad = jax.jit(jax.value_and_grad(
            lambda p: reference.shard_loss(p, *data, **MODEL),
            has_aux=True))(params)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    np.testing.assert_array_equal(np.asarray(aux["counts"]),
                                  np.asarray(ref_aux["counts"]))
    _tree_close(grad, ref_grad, 3e-4)
    for layer in grad["layers"]:
        if "moe" in layer:
            assert not np.any(np.asarray(layer["moe"]["router_bias"]))


def test_a_training_step_matches_the_references_first_step(make_runtime):
    """The job's step (masked AdamW, then the biases' update) through
    ``hvd.run_step`` on one device against the reference's loss, update norm
    and updated biases."""
    make_runtime(devices=jax.devices()[:1])
    cfg, params = ling(), _seeded(HELD, 5)
    adamw = dict(lr=1e-3, weight_decay=1e-4, eps=1e-8)
    opt = hvd.DistributedOptimizer(optax.masked(optax.adamw(
        adamw["lr"], eps=adamw["eps"], weight_decay=adamw["weight_decay"]),
        gpt.trainable))

    def train(params, opt_state, data):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        counts = hvd.allreduce(aux["counts"], op=hvd.Sum)
        return gpt.update_router_bias(
            optax.apply_updates(params, updates), counts, RATE), opt_state, \
            hvd.allreduce(loss, op=hvd.Average), counts

    step = hvd.run_step(train, in_specs=(hvd.REPLICATED, hvd.REPLICATED,
                                         hvd.batch_spec(0)),
                        out_specs=hvd.REPLICATED)
    data = _data(1)
    placed = hvd.replicate(params)
    new, _, loss, counts = step(placed, hvd.replicate(opt.init(params)),
                                hvd.shard_batch(data))
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_counts, grad = reference.loss_and_grad(
            params, *(x[None] for x in data), **MODEL)
    assert abs(float(loss) - ref_loss) <= 2e-5 * abs(ref_loss)
    np.testing.assert_array_equal(np.asarray(counts), ref_counts)
    moved = jax.tree.map(jnp.subtract, new, params)
    for layer in moved["layers"]:
        if "moe" in layer:
            layer["moe"].pop("router_bias")
    want = reference.adamw_first_update_norm(params, grad, **adamw)
    assert abs(float(optax.global_norm(moved)) - want) <= 2e-3 * want
    for got, ref in zip(reference.biases(new), reference.updated_biases(
            params, ref_counts, RATE), strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-7)


def test_the_shares_of_a_small_deployment_add_up():
    """Four ranks of four experts each, the same router, bias and groups:
    the parts all ranks give, the shared expert counted once, sum to the
    uncut layer's output."""
    whole_cfg = ling(experts_held=EXPERTS)
    m = _seeded(EXPERTS, 7)["layers"][1]["moe"]
    h = jnp.asarray(np.random.default_rng(2).standard_normal((B, S, EMBED)),
                    jnp.float32)
    whole, aux, _ = experts.apply(whole_cfg, None, m, h)
    no_shared = {k: v for k, v in m.items() if k != "shared"}
    parts = []
    for rank in range(EXPERTS // HELD):
        cfg = ling(experts_held=HELD, first_expert=rank * HELD,
                   shared_expert_dim=0 if rank else 16)
        mine = {**(no_shared if rank else m), **{
            name: m[name][rank * HELD:(rank + 1) * HELD]
            for name in ("w_gate", "w_up", "w_down")}}
        part, part_aux, _ = experts.apply(cfg, None, mine, h)
        np.testing.assert_array_equal(np.asarray(part_aux["counts"]),
                                      np.asarray(aux["counts"]))
        parts.append(part)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)


def _numpy_choice(leaning, top_k, groups, kept):
    """The grouped choice written plainly, a token at a time; a tie goes to
    the lower index, groups and experts alike (a stable sort)."""
    out = []
    for row in np.asarray(leaning, np.float64):
        by_group = row.reshape(groups, -1)
        score = np.sort(by_group, axis=1)[:, -2:].sum(axis=1)
        best = np.argsort(-score, kind="stable")[:kept]
        masked = np.full_like(row, -np.inf).reshape(groups, -1)
        masked[best] = by_group[best]
        out.append(np.argsort(-masked.reshape(-1), kind="stable")[:top_k])
    return np.stack(out)


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_the_grouped_choice_against_a_plain_numpy_one(ties):
    rng = np.random.default_rng(4)
    leaning = rng.standard_normal((96, 32)).astype(np.float32)
    if ties:
        # A few values, so that groups tie and experts tie.
        # (Plus 0.0: no -0.0, which a sort tells from 0.0.)
        leaning = np.round(leaning) + 0.0
    want = _numpy_choice(leaning, 5, 8, 3)
    got = jax.lax.top_k(kept_groups(jnp.asarray(leaning), 8, 3), 5)[1]
    np.testing.assert_array_equal(np.asarray(got), want)
    ref = reference.grouped_choice(jnp.asarray(leaning), 5, 8, 3)
    np.testing.assert_array_equal(np.asarray(ref), want)


def test_the_bounded_gate_lies_above_its_bound_and_the_mixer_refuses_axes():
    cfg = ling()
    p = _seeded(HELD, 3)["layers"][0]["kda"]
    f = 50.0 * jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, 8, HEADS * KDA_DIM)), jnp.float32)
    g = kda_mixer.log_decay(cfg, p, f)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.9 and float(g.max()) > -0.1


@pytest.mark.parametrize("axis", ["tp", "sp"])
def test_the_kda_mixer_refuses_a_bound_axis_by_name(make_runtime, axis):
    from jax.sharding import PartitionSpec as P
    hvd_ = make_runtime(mesh_shape={axis: 2}, devices=jax.devices()[:2])
    cfg = ling(**{f"{axis}_axis": axis})
    params = _seeded(HELD, 3)
    data = _data()

    def loss(p, *d):
        return gpt.loss_fn(p, *d, cfg)

    with pytest.raises(ValueError, match="Kimi-delta-attention layer runs"):
        jax.shard_map(loss, mesh=hvd_.mesh(), in_specs=P(), out_specs=P(),
                      check_vma=False)(params, *data)


def _two_kda_layers(**kw):
    return ling(num_layers=2, layers=(LayerSpec(mixer="kda", ff="gated"),) * 2,
                **kw)


def test_a_checkpointed_block_runs_the_scans_forward_kernels_once(
        make_runtime, equations_of):
    """``remat="full"`` keeps every output of ``hvd_kda_fwd`` and
    ``hvd_kda_rec_fwd`` (the five operands, ``T`` and the entering states,
    named as the rule's residuals, and the mixer's ``kda_scan_out``), so the
    recomputed copy runs neither; the forward pass reads no kept tensor of
    the recurrence's order ``[c, B, H, ., .]``, so ``jax.checkpoint`` puts
    no ``reduce_precision`` on one (a pass over every kept byte on the
    chip)."""
    hvd_ = make_runtime(devices=jax.devices()[:1])
    cfg, layers = _two_kda_layers(remat="full"), 2
    data = _data(3)
    jaxpr = jax.make_jaxpr(
        lambda p: jax.value_and_grad(gpt.loss_fn)(p, *data, cfg))(
            gpt.init_params(jax.random.PRNGKey(0), cfg))
    equations = [eqn for eqn, _ in equations_of(jaxpr.jaxpr)]
    assert collections.Counter(
        eqn.params["name"] for eqn in equations
        if eqn.primitive.name == "pallas_call"
        and eqn.params["name"].startswith("hvd_kda_")) == dict.fromkeys(
        (kda_ops.KERNEL_FWD, kda_ops.KERNEL_REC_FWD, kda_ops.KERNEL_BWD,
         kda_ops.KERNEL_REC_BWD), layers)
    assert not [eqn for eqn in equations
                if eqn.primitive.name == "reduce_precision"
                and eqn.outvars[0].aval.ndim == 5]
    family = hvd_.metrics()["hvdtpu_spmd_remat_saved_bytes_total"]
    kept = {labels["name"]: value for _, labels, value in family["samples"]}
    # A block's bytes (layers alike share one trace), float32 throughout:
    # the operands [c, B, H, Q, V + 3 K + Q] and T, Q more a row (PR 68),
    # the entering states [c, B, H, V, K], the output [B, S, H V].
    chunks, q = S // cfg.kda_chunk, cfg.kda_chunk
    assert {name: kept.get(name) for name in (
        *kda_ops.SAVED_NAMES, *kda_mixer.SAVED_NAMES)} == {
        "kda_scan_operands":
            4 * chunks * B * HEADS * q * (4 * KDA_DIM + 2 * q),
        "kda_scan_entering": 4 * chunks * B * HEADS * KDA_DIM * KDA_DIM,
        "kda_scan_out": 4 * B * S * HEADS * KDA_DIM}


def test_full_remat_is_no_remat_to_the_last_gradient_leaf():
    """The kept tensors are the tensors that would have been made again:
    the loss and every gradient leaf under ``remat="full"`` against
    ``remat="none"``."""
    data = _data(5)
    params = gpt.init_params(jax.random.PRNGKey(4), _two_kda_layers())
    (want_loss, want), (loss, grads) = (
        jax.jit(jax.value_and_grad(
            lambda p, cfg=_two_kda_layers(remat=remat):
            gpt.loss_fn(p, *data, cfg)))(params)
        for remat in ("none", "full"))
    assert float(loss) == float(want_loss)
    _tree_close(grads, want, 0.0)


# sha256 of the StableHLO text (no source locations) that Moonlight's layer
# (an MLA mixer with no gate on its output) and a plain top-k expert layer
# (one group) lower to, as the parent of PR 63 lowered them: that PR gave
# ``mixers/mla.py`` a head-wise gate and ``moe_layer`` a choice limited to
# groups, both off for every configuration that was there. A change that
# means to alter either pins these anew. (PR 71 pinned "mla" anew: the head
# rule's one block lost the ``0 +`` that ``sum(dw)`` put before its weight
# gradient, three lines of the text and nothing of the layer's.)
LOWERED = {
    "mla": "d21768a1fcbd08709fe871061f10683960d4b94a50af15f3595c61f77c831e3e",
    "moe": "f48a361884a81ff19be82fd25020859858a4cf26fbc0f10d6dfaacb4e03725a9",
}


def _sha(fn, *args):
    return hashlib.sha256(
        jax.jit(fn).lower(*args).as_text().encode()).hexdigest()


def test_an_ungated_mla_layer_lowers_to_the_program_it_lowered_to():
    cfg = gpt.GPTConfig(
        vocab_size=64, num_layers=1, num_heads=2, head_dim=16, mla_rope_dim=8,
        mla_value_dim=16, mla_kv_rank=32, embed_dim=32, mlp_dim=64,
        dtype=jnp.bfloat16, tp_axis=None, sp_axis=None, attention="flash",
        layers=(LayerSpec(mixer="mla", ff="gated"),))
    params = jax.eval_shape(
        lambda: gpt.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    assert _sha(jax.grad(lambda p, t, pos: gpt.loss_fn(p, t, t, pos, cfg)),
                params, tok, tok) == LOWERED["mla"]


def test_one_group_lowers_to_the_program_it_lowered_to():
    from horovod_tpu.parallel.moe import moe_layer
    sd = jax.ShapeDtypeStruct
    x, r = sd((64, 32), jnp.bfloat16), sd((32, 8), jnp.float32)
    w, wd = sd((8, 32, 16), jnp.float32), sd((8, 16, 32), jnp.float32)
    b = sd((8,), jnp.float32)

    def moe(x, r, wg, wu, wd, b):
        y, _ = moe_layer(x, r, wg, wu, wd, top_k=2, score="sigmoid", bias=b,
                         renormalize=True, scale=2.5)
        return jnp.sum(y.astype(jnp.float32))

    assert _sha(jax.grad(moe, argnums=(0, 1, 2, 3, 4)), x, r, w, w, wd,
                b) == LOWERED["moe"]
