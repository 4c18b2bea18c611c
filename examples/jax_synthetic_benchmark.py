"""Synthetic ResNet throughput benchmark.

Reference: ``examples/tensorflow2_synthetic_benchmark.py`` /
``examples/pytorch_synthetic_benchmark.py`` — random data, fwd+bwd+step,
images/sec, with the fp16-allreduce knob (here bf16 end-to-end is the
TPU-native default; ``--fp32`` opts out). ``--model resnet101`` matches the
reference's published absolute-throughput row (tf_cnn_benchmarks resnet101
bs=64); ``--image-size`` shrinks the input for CPU smokes.

    python examples/jax_synthetic_benchmark.py --batch-size 32 --num-iters 20
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import (InceptionV3, ResNet18, ResNet34, ResNet50,
                                ResNet101, ResNet152, VGG16, VGG19)

MODELS = {"resnet18": ResNet18, "resnet34": ResNet34,
          "resnet50": ResNet50, "resnet101": ResNet101,
          "resnet152": ResNet152, "vgg16": VGG16, "vgg19": VGG19,
          "inception3": InceptionV3}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet50",
                        choices=sorted(MODELS))
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--batch-size", type=int, default=32,
                        help="per-chip batch size")
    parser.add_argument("--num-warmup-batches", type=int, default=5)
    parser.add_argument("--num-iters", type=int, default=20)
    parser.add_argument("--fp32", action="store_true",
                        help="compute in float32 instead of bfloat16")
    args = parser.parse_args()

    hvd.init()
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    n = hvd.size()
    model = MODELS[args.model](num_classes=1000, dtype=dtype)
    rng = jax.random.PRNGKey(0)
    batch = args.batch_size * n
    images = jax.random.normal(
        rng, (batch, args.image_size, args.image_size, 3), dtype)
    labels = jax.random.randint(rng, (batch,), 0, 1000)

    variables = model.init(rng, images[:1], train=True)
    params = variables["params"]
    # VGG has no batch norm; ResNets carry BN statistics.
    batch_stats = variables.get("batch_stats", {})
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    # Place the state on the mesh before the first step: fed un-placed
    # (single-device) arrays, jit compiles the step once for those and a
    # second time for the replicated arrays the step itself returns.
    params, batch_stats, opt_state = hvd.replicate(
        (params, batch_stats, opt.init(params)))

    def train_step(p, bstats, s, batch):
        imgs, lbls = batch

        def loss_fn(q):
            logits, updates = model.apply(
                {"params": q, "batch_stats": bstats}, imgs, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), lbls).mean()
            return loss, updates.get("batch_stats", {})

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        updates, s = opt.update(grads, s, p)
        # Average the BN statistics across shards so they come back
        # replicated (SyncBatchNorm semantics).
        new_stats = hvd.grouped_allreduce(new_stats, op=hvd.Average)
        return (optax.apply_updates(p, updates), new_stats, s,
                hvd.allreduce(loss, op=hvd.Average))

    step = hvd.run_step(
        train_step,
        in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.REPLICATED,
                  hvd.batch_spec(0)),
        out_specs=hvd.REPLICATED)
    data = hvd.shard_batch((images, labels))

    for _ in range(args.num_warmup_batches):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, data)
    jax.block_until_ready(params)

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, data)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0

    if hvd.rank() == 0:
        ips = batch * args.num_iters / dt
        print(f"{args.model}: total img/sec on {n} device(s): {ips:.1f} "
              f"({ips / n:.1f} per device)")
    hvd.shutdown()


if __name__ == "__main__":
    main()
