#!/usr/bin/env python3
"""Chip smoke: the compiled SPMD training path, once, on the TPU this process sees.

One process, no children. It drives every chip ``jax.devices()`` lists through
the entry points a user calls — ``hvd.init()`` -> ``hvd.shard_batch`` ->
``hvd.run_step`` -> ``hvd.DistributedOptimizer`` -> in-step XLA collectives ->
Pallas kernels — and holds what comes out to the repo's own references:

* leg A  ResNet-50 (all 50 layers, 1000 classes, bf16, 224x224, 64 images per
         chip) data-parallel, one ``step()`` per optimizer step: finite loss at
         every step, parameters changed, loss on the fixed batch lower at the
         end than at step 0, every chip used.
* leg B  the GPT path with the flash kernels compiled through Mosaic inside a
         training step, held to dense attention from the same parameters and
         batch; then S=4096 with ``remat="full"``, flash only.
* leg C  the nine Pallas entry points compiled; the quantizers' round trips
         inside their error bounds; ``compressed_allreduce`` held to the dense
         ``hvd.allreduce``; a data-parallel step whose gradients cross the
         wire 4-bit-quantized.

Any failed leg or assert ends the run with a non-zero exit code; nothing is
caught and reported as a field. The wall times it prints are observations for
the reader of the log, not metrics.

    python chip_smoke.py              # on the chip; fails on any other platform
    python chip_smoke.py --rehearsal  # tiny sizes, 4-device CPU mesh, Pallas
                                      # in interpret mode; proves control flow
                                      # only and says so on every line

Last line of standard output on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one run. ``FULL`` is what the chip runs; ``REHEARSAL`` is the
    same control flow at a size the CPU mesh finishes in about two minutes."""
    rehearsal: bool
    # Leg A: the reference recipe (docs/benchmarks.rst:38 — bs 64 per
    # accelerator). lr follows the linear-scaling rule, 0.1 per 256 images.
    resnet_image: int
    resnet_batch_per_chip: int
    resnet_lr_per_256: float
    resnet_window: int          # steady steps per timed window (two windows)
    # Leg B: the one GPT size the repo has trained.
    gpt_layers: int
    gpt_embed: int
    gpt_heads: int
    gpt_vocab: int
    gpt_batch: int              # global
    gpt_seq: int
    gpt_long_seq: int
    # Leg C.
    car_elems: int              # per-rank vector of the compressed allreduce
    mlp_features: tuple
    mlp_in: int
    mlp_batch_per_chip: int
    mlp_lr: float
    mlp_steps: int


FULL = Plan(rehearsal=False,
            resnet_image=224, resnet_batch_per_chip=64,
            resnet_lr_per_256=0.1, resnet_window=8,
            gpt_layers=6, gpt_embed=512, gpt_heads=8, gpt_vocab=32000,
            gpt_batch=8, gpt_seq=1024, gpt_long_seq=4096,
            car_elems=4 * 1024 * 1024,          # 16 MB of float32
            mlp_features=(1024, 1024, 10), mlp_in=784,
            mlp_batch_per_chip=64, mlp_lr=1e-3, mlp_steps=25)

REHEARSAL = Plan(rehearsal=True,
                 resnet_image=32, resnet_batch_per_chip=2,
                 resnet_lr_per_256=0.1, resnet_window=2,
                 gpt_layers=2, gpt_embed=64, gpt_heads=8, gpt_vocab=256,
                 gpt_batch=4, gpt_seq=256, gpt_long_seq=512,
                 car_elems=64 * 1024,
                 mlp_features=(32, 10), mlp_in=12,
                 mlp_batch_per_chip=8, mlp_lr=1e-2, mlp_steps=25)

# Tolerances, stated once.
#
# Flash vs dense attention, same bf16 q/k/v: dense rounds the logits and the
# probabilities to bf16 (8 mantissa bits, eps = 2**-8 = 3.9e-3) where the
# kernel keeps fp32, so per-element differences are a few eps and the loss,
# a mean over B*S tokens, moves by less than one eps. The gradient norm sums
# those differences through six layers.
FLASH_LOSS_RTOL = 4e-3
FLASH_GNORM_RTOL = 1e-2
# Two init-time losses of one model on different random tokens (S=1024 vs
# S=4096) both sit at ln(vocab) plus the logit variance term.
LONG_LOSS_RTOL = 5e-2
# 4-bit max-min, bucket 512, scatter-allgather: two quantization stages, each
# adding rounding noise of std unit/sqrt(12) with unit = range/15; a bucket of
# 512 normal samples spans about 6.3 sigma, so each stage costs ~0.12 of the
# signal's std and the two together ~0.17 in relative L2. 0.25 leaves room for
# the heavy-tailed buckets; an 8-bit result would sit at 0.01, a broken kernel
# at ~1.
CAR_4BIT_REL_BOUND = 0.25


def say(plan: Plan, msg: str) -> None:
    prefix = "[rehearsal platform: cpu] " if plan.rehearsal else ""
    print(prefix + msg, flush=True)


def assert_every_chip_used(sharded_leaf, replicated_tree, what: str) -> None:
    """The batch sits on n distinct devices and the outputs are replicated on
    all n — code that has only ever seen virtual devices could have left
    everything on ``jax.devices()[0]``."""
    import jax
    import horovod_tpu as hvd

    devices = set(hvd.mesh().devices.flat)
    n = len(devices)
    shard_devices = {s.device for s in sharded_leaf.addressable_shards}
    assert shard_devices == devices, \
        f"{what}: batch shards on {len(shard_devices)} of {n} devices"
    for leaf in jax.tree.leaves(replicated_tree):
        assert leaf.sharding.is_fully_replicated, \
            f"{what}: output not replicated ({leaf.sharding})"
        assert set(leaf.sharding.device_set) == devices, \
            f"{what}: output on {len(leaf.sharding.device_set)} of {n} devices"


def leg_a(plan: Plan) -> None:
    """Main path at full width: ResNet-50 data-parallel, written as
    examples/jax_synthetic_benchmark.py is."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50

    n = hvd.size()
    batch = plan.resnet_batch_per_chip * n
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    k_img, k_lbl, k_init = jax.random.split(jax.random.PRNGKey(SEED), 3)
    images = jax.random.normal(
        k_img, (batch, plan.resnet_image, plan.resnet_image, 3), jnp.bfloat16)
    labels = jax.random.randint(k_lbl, (batch,), 0, 1000)
    variables = model.init(k_init, images[:1], train=True)
    lr = plan.resnet_lr_per_256 * batch / 256
    opt = hvd.DistributedOptimizer(optax.sgd(lr, momentum=0.9))
    # Placed before the first step: un-placed init results are single-device
    # arrays, the step's outputs are replicated over the mesh, and jit
    # compiles the step a second time for the second kind of input.
    params, batch_stats, opt_state = hvd.replicate(
        (variables["params"], variables["batch_stats"],
         opt.init(variables["params"])))

    def train_step(p, bstats, s, data):
        imgs, lbls = data

        def loss_fn(q):
            logits, updates = model.apply(
                {"params": q, "batch_stats": bstats}, imgs, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), lbls).mean()
            return loss, updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        updates, s = opt.update(grads, s, p)
        new_stats = hvd.grouped_allreduce(new_stats, op=hvd.Average)
        return (optax.apply_updates(p, updates), new_stats, s,
                hvd.allreduce(loss, op=hvd.Average))

    step = hvd.run_step(
        train_step,
        in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.REPLICATED,
                  hvd.batch_spec(0)),
        out_specs=hvd.REPLICATED)
    data = hvd.shard_batch((images, labels))
    params0 = params

    t0 = time.perf_counter()
    params, batch_stats, opt_state, loss = step(
        params, batch_stats, opt_state, data)
    jax.block_until_ready((params, batch_stats, opt_state, loss))
    first_call_s = time.perf_counter() - t0
    losses = [loss]

    def window(fence) -> float:
        nonlocal params, batch_stats, opt_state
        t0 = time.perf_counter()
        for _ in range(plan.resnet_window):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, data)
            losses.append(loss)
        fence()
        return (time.perf_counter() - t0) / plan.resnet_window * 1e3

    # The same steady window fenced two ways: block_until_ready, and a
    # one-element value fetch from the last update (what a host that did not
    # trust block_until_ready would do). If they agree the fetch fence has
    # no reason to exist. The fetch runs once un-timed first: its slice is a
    # small program of its own, and compiling it is not part of a step.
    def fetch_one():
        return jax.device_get(jax.tree.leaves(params)[-1].reshape(-1)[:1])

    fetch_one()
    ms_block = window(lambda: jax.block_until_ready(
        (params, batch_stats, opt_state)))
    ms_fetch = window(fetch_one)

    losses = [float(v) for v in jax.device_get(losses)]
    assert all(math.isfinite(v) for v in losses), f"leg A: loss {losses}"
    assert losses[-1] < losses[0], \
        f"leg A: loss on the fixed batch did not fall: {losses}"
    moved = float(optax.global_norm(
        jax.tree.map(jnp.subtract, params, params0)))
    assert moved > 0, "leg A: parameters did not change"
    assert_every_chip_used(data[0], (params, batch_stats, opt_state, loss),
                           "leg A")
    if not plan.rehearsal:  # the CPU backend reports no memory statistics
        peaks = [d.memory_stats()["peak_bytes_in_use"]
                 for d in hvd.mesh().devices.flat]
        assert min(peaks) > 0 and max(peaks) <= 2 * min(peaks), \
            f"leg A: peak bytes per chip {peaks}"
        say(plan, "leg A peak_bytes_in_use per chip (GiB): "
            + " ".join(f"{p / 2**30:.2f}" for p in peaks))
    say(plan, f"leg A observations: first call (trace + compile + step 0) "
        f"{first_call_s:.1f} s; steady step wall "
        f"{ms_block:.1f} ms ending in block_until_ready, "
        f"{ms_fetch:.1f} ms ending in a one-element device_get "
        f"({plan.resnet_window} steps each, global batch {batch})")
    say(plan, f"leg A passed: ResNet-50 {plan.resnet_image}px "
        f"{plan.resnet_batch_per_chip}/chip on {n} chip(s), "
        f"{len(losses)} steps, loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"|params - params0| {moved:.3g}")


def leg_b(plan: Plan) -> None:
    """Flash attention compiled, in a training step, held to dense."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import gpt
    from horovod_tpu.ops import flash_attention as fa

    assert fa._use_interpret() == plan.rehearsal, \
        "leg B: flash kernels must compile through Mosaic on the chip"
    n = hvd.size()

    def config(attention: str, remat: str) -> gpt.GPTConfig:
        return gpt.GPTConfig(
            vocab_size=plan.gpt_vocab, num_layers=plan.gpt_layers,
            num_heads=plan.gpt_heads,
            head_dim=plan.gpt_embed // plan.gpt_heads,
            embed_dim=plan.gpt_embed, mlp_dim=4 * plan.gpt_embed,
            dtype=jnp.bfloat16, tp_axis=None, sp_axis=None,
            attention=attention, remat=remat)

    def batch_of(b: int, s: int):
        tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (b, s), 0,
                                    plan.gpt_vocab)
        targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        return hvd.shard_batch((tokens, targets, positions))

    def one_step(cfg: gpt.GPTConfig, params, batch):
        opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

        def train_step(p, s, data):
            tokens, targets, positions = data
            loss, grads = jax.value_and_grad(
                lambda q: gpt.loss_fn(q, tokens, targets, positions, cfg))(p)
            # Gradients of replicated parameters arrive summed over the
            # axis; the optimizer and this norm both take the mean.
            gnorm = optax.global_norm(grads) / hvd.size_in_step()
            updates, s = opt.update(grads, s, p)
            return (optax.apply_updates(p, updates), s,
                    hvd.allreduce(loss, op=hvd.Average), gnorm)

        step = hvd.run_step(
            train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)
        new_params, _, loss, gnorm = step(
            params, hvd.replicate(opt.init(params)), batch)
        assert_every_chip_used(batch[0], (new_params, loss, gnorm), "leg B")
        loss, gnorm = float(loss), float(gnorm)
        assert math.isfinite(loss) and math.isfinite(gnorm), \
            f"leg B: {cfg.attention} loss {loss} grad norm {gnorm}"
        return loss, gnorm

    params = hvd.replicate(gpt.init_params(jax.random.PRNGKey(SEED),
                                           config("dense", "none")))
    batch = batch_of(plan.gpt_batch, plan.gpt_seq)
    dense_loss, dense_gnorm = one_step(config("dense", "none"), params, batch)
    flash_loss, flash_gnorm = one_step(config("flash", "none"), params, batch)
    loss_rel = abs(flash_loss - dense_loss) / abs(dense_loss)
    gnorm_rel = abs(flash_gnorm - dense_gnorm) / abs(dense_gnorm)
    assert loss_rel <= FLASH_LOSS_RTOL, \
        f"leg B: flash loss {flash_loss} vs dense {dense_loss}"
    assert gnorm_rel <= FLASH_GNORM_RTOL, \
        f"leg B: flash grad norm {flash_gnorm} vs dense {dense_gnorm}"

    # The shape the kernel was written for: the dense path would hold a
    # [B, H, S, S] fp32 logits tensor per layer here.
    long_batch = max(2, n)
    long_loss, long_gnorm = one_step(
        config("flash", "full"), params,
        batch_of(long_batch, plan.gpt_long_seq))
    assert abs(long_loss - dense_loss) / dense_loss <= LONG_LOSS_RTOL, \
        f"leg B: S={plan.gpt_long_seq} loss {long_loss} vs {dense_loss}"
    say(plan, f"leg B passed: GPT L{plan.gpt_layers} d{plan.gpt_embed} "
        f"vocab {plan.gpt_vocab} on {n} chip(s); B{plan.gpt_batch} "
        f"S{plan.gpt_seq} loss dense {dense_loss:.5f} flash "
        f"{flash_loss:.5f} (rel {loss_rel:.2e} <= {FLASH_LOSS_RTOL}), "
        f"grad norm dense {dense_gnorm:.5f} flash {flash_gnorm:.5f} "
        f"(rel {gnorm_rel:.2e} <= {FLASH_GNORM_RTOL}); B{long_batch} "
        f"S{plan.gpt_long_seq} remat=full flash loss {long_loss:.5f} "
        f"grad norm {long_gnorm:.5f}")


def _compile_kernels(plan: Plan) -> None:
    """C(i): each of the nine Pallas entry points through
    ``.lower().compile()`` at one small shape. Off the TPU there is no
    Mosaic to compile for: the rehearsal executes them in interpret mode
    instead (the TPU-PRNG kernel has no interpreter and is left out)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.compression import pallas_kernels as pk
    from horovod_tpu.ops import flash_attention as fa

    interp = plan.rehearsal
    q = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
    x = jnp.zeros((8192,), jnp.float32)
    seed = jnp.zeros((), jnp.int32)
    levels = jnp.linspace(1.0, 0.0, 8, dtype=jnp.float32)
    codes = jnp.zeros((16, 512), jnp.uint8)
    per_bucket = jnp.zeros((16,), jnp.float32)
    codes_n = jnp.zeros((2, 16, 512), jnp.uint8)
    per_bucket_n = jnp.zeros((2, 16), jnp.float32)
    entry_points = {
        "flash_fwd_causal": (
            lambda a, b, c: fa.flash_attention(a, b, c, causal=True),
            (q, q, q)),
        "flash_bwd_dkdv_dq": (
            jax.grad(lambda a, b, c: fa.flash_attention(a, b, c)
                     .astype(jnp.float32).sum(), argnums=(0, 1, 2)),
            (q, q, q)),
        "flash_fwd_noncausal": (
            lambda a, b, c: fa.flash_attention(a, b, c, causal=False),
            (q, q, q)),
        "maxmin_quantize": (
            lambda v: pk.maxmin_quantize_pallas(v, 4, 512, interp), (x,)),
        "maxmin_quantize_stochastic": (
            lambda v, s: pk.maxmin_quantize_stochastic_pallas(v, 4, 512, s),
            (x, seed)),
        "norm_quantize": (
            lambda v, lv: pk.norm_quantize_pallas(v, lv, 512, False, interp),
            (x, levels)),
        "maxmin_dequantize": (
            lambda a, b, c: pk.maxmin_dequantize_pallas(a, b, c, 512, interp),
            (codes, per_bucket, per_bucket)),
        "maxmin_dequantize_sum": (
            lambda a, b, c: pk.maxmin_dequantize_sum_pallas(a, b, c, interp),
            (codes_n, per_bucket_n, per_bucket_n)),
        "norm_dequantize": (
            lambda a, lv, c: pk.norm_dequantize_pallas(a, lv, c, interp),
            (codes, levels, per_bucket)),
    }
    for name, (fn, args) in entry_points.items():
        if plan.rehearsal:
            if name == "maxmin_quantize_stochastic":
                continue
            jax.block_until_ready(jax.jit(fn)(*args))
        else:
            jax.jit(fn).lower(*args).compile()
    say(plan, f"leg C(i): {len(entry_points)} Pallas entry points "
        + ("executed in interpret mode (no Mosaic off the TPU; the TPU-PRNG "
           "kernel left out)" if plan.rehearsal
           else "compiled through Mosaic") + ": " + " ".join(entry_points))


def _quantizer_round_trips(plan: Plan) -> None:
    """The quantizers alone, through the path the backend selects (Pallas on
    the chip), each inside its error bound on one small input."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.compression import MaxMinQuantizer, NormalizedQuantizer

    x = jax.random.normal(jax.random.PRNGKey(SEED + 2), (20000,), jnp.float32)

    def worst(quantizer, key=None):
        payload, ctx = quantizer.compress(x, key)
        err = float(jnp.max(jnp.abs(quantizer.decompress(payload, ctx) - x)))
        return err, payload

    # Max-min, round to nearest: half a unit; stochastic: one unit.
    err, payload = worst(MaxMinQuantizer(bits=4))
    unit = float(np.max(payload["unit"]))
    assert err <= 0.5 * unit * (1 + 1e-4), f"maxmin: {err} vs unit {unit}"
    if not plan.rehearsal:  # the TPU hardware PRNG kernel
        err, payload = worst(MaxMinQuantizer(bits=4, stochastic=True),
                             jax.random.PRNGKey(SEED + 3))
        unit = float(np.max(payload["unit"]))
        assert err <= unit * (1 + 1e-4), \
            f"maxmin stochastic: {err} vs unit {unit}"
    # Norm-scaled, 8 uniform levels (bits=4, one for the sign): half the
    # level spacing of 1/7, times the bucket's norm.
    err, payload = worst(NormalizedQuantizer(bits=4))
    norm = float(np.max(payload["norm"]))
    assert err <= norm / 14 * (1 + 1e-4), f"norm-uniform: {err} vs {norm}"
    say(plan, "leg C(i): quantizer round trips inside their error bounds")


def _compressed_vs_dense_allreduce(plan: Plan) -> None:
    """C(ii): ``compressed_allreduce`` of one distinct vector per rank
    against the dense ``hvd.allreduce`` of the same array."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.compression import MaxMinQuantizer, compressed_allreduce

    n = hvd.size()
    host = np.random.RandomState(SEED).randn(n * plan.car_elems)\
        .astype(np.float32)
    x = hvd.shard_batch(host)          # rank r holds host[r*N:(r+1)*N]
    dense = hvd.allreduce(x, op=hvd.Average)
    comp = compressed_allreduce(x, compressor=MaxMinQuantizer(bits=4),
                                op=hvd.Average)
    assert_every_chip_used(x, (dense, comp), "leg C(ii)")
    dense, comp = np.asarray(dense), np.asarray(comp)
    expect = host.reshape(n, -1).mean(axis=0)
    np.testing.assert_allclose(dense, expect, rtol=1e-5, atol=1e-6)
    rel = float(np.linalg.norm(comp - dense) / np.linalg.norm(dense))
    assert rel < CAR_4BIT_REL_BOUND, f"leg C(ii): relative error {rel}"
    say(plan, f"leg C(ii): compressed_allreduce(4-bit max-min) vs dense "
        f"allreduce, {plan.car_elems * 4 / 2**20:.2f} MB float32 per rank, "
        f"{n} rank(s): relative L2 error {rel:.4f} < {CAR_4BIT_REL_BOUND}")


def _compressed_optimizer_step(plan: Plan) -> None:
    """C(iii): data-parallel steps whose gradients cross the wire 4-bit
    quantized. Compression engages on per-rank gradients, so the step
    differentiates against ``hvd.pvary(params)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.compression import MaxMinQuantizer
    from horovod_tpu.models import MLP

    n = hvd.size()
    batch = plan.mlp_batch_per_chip * n
    rng = np.random.RandomState(SEED + 4)
    x = rng.randn(batch, plan.mlp_in).astype(np.float32)
    y = rng.randint(0, plan.mlp_features[-1], size=(batch,))
    model = MLP(features=plan.mlp_features)
    params = model.init(jax.random.PRNGKey(SEED), jnp.asarray(x[:1]))
    opt = hvd.DistributedOptimizer(optax.adam(plan.mlp_lr),
                                   compression=MaxMinQuantizer(bits=4))
    params, opt_state = hvd.replicate((params, opt.init(params)))

    def train_step(p, s, data):
        def loss_fn(q):
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(q, data[0]), data[1]).mean()

        loss, grads = jax.value_and_grad(loss_fn)(hvd.pvary(p))
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

    step = hvd.data_parallel_step(train_step, donate_state=False)
    data = hvd.shard_batch((jnp.asarray(x), jnp.asarray(y)))
    losses = []
    for _ in range(plan.mlp_steps):
        params, opt_state, loss = step(params, opt_state, data)
        losses.append(loss)
    assert_every_chip_used(data[0], (params, opt_state, loss), "leg C(iii)")
    losses = [float(v) for v in jax.device_get(losses)]
    assert all(math.isfinite(v) for v in losses), f"leg C(iii): {losses}"
    assert losses[-1] < 0.7 * losses[0], \
        f"leg C(iii): loss under 4-bit gradients did not fall: {losses}"
    say(plan, f"leg C(iii): MLP {plan.mlp_features} trained "
        f"{plan.mlp_steps} steps under DistributedOptimizer(compression="
        f"MaxMinQuantizer(bits=4)), loss {losses[0]:.4f} -> {losses[-1]:.4f}")


def leg_c(plan: Plan) -> None:
    """Compression kernels compiled, alone and in a step."""
    from horovod_tpu.compression import MaxMinQuantizer

    assert MaxMinQuantizer()._pallas_enabled() == (not plan.rehearsal), \
        "leg C: the quantizers must take the Pallas path on the chip"
    _compile_kernels(plan)
    _quantizer_round_trips(plan)
    _compressed_vs_dense_allreduce(plan)
    _compressed_optimizer_step(plan)
    say(plan, "leg C passed")


def run_legs(plan: Plan) -> None:
    """All three legs over the runtime the caller initialised."""
    leg_a(plan)
    leg_b(plan)
    leg_c(plan)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny sizes on a 4-device CPU mesh, Pallas in interpret mode; "
             "checks control flow, says nothing about the chip")
    args = parser.parse_args(argv)
    plan = REHEARSAL if args.rehearsal else FULL

    if not os.path.isdir(os.path.join(HERE, "horovod_tpu")):
        sys.exit("chip_smoke: the program (horovod_tpu/) is not beside this "
                 "script; there is nothing to check")
    if plan.rehearsal:
        # Asked for by name, before JAX is imported: never what the run
        # falls back to when it finds no chip.
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")

    import jax
    import jaxlib

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(plan, f"chip_smoke: platform: {device['platform']} device_kind: "
        f"{device['kind']} devices: {device['count']} | jax "
        f"{jax.__version__} jaxlib {jaxlib.__version__} libtpu "
        f"{importlib.metadata.version('libtpu')}")
    wanted = "cpu" if plan.rehearsal else "tpu"
    if device["platform"] != wanted:
        sys.exit(f"chip_smoke: found platform {device['platform']!r} "
                 f"({device['kind']}, {device['count']} device(s)), need "
                 f"{wanted!r}; no result")

    import horovod_tpu as hvd

    t0 = time.perf_counter()
    hvd.init()
    run_legs(plan)
    hvd.shutdown()
    say(plan, f"chip_smoke: legs A, B and C passed in "
        f"{time.perf_counter() - t0:.0f} s wall (an observation)")
    result = {"ok": True, "device": device}
    if plan.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
