"""The user's side of a data-parallel ResNet training job.

Written against the public API only (``hvd.replicate``, ``hvd.shard_batch``,
``hvd.run_step``, ``hvd.DistributedOptimizer``, ``hvd.grouped_allreduce``),
after ``chip_smoke.py`` leg A: SGD with momentum, learning rate by the
linear-scaling rule, batch statistics averaged over the chips every step.
Host batches are uint8 NHWC, as a decoded image is, and become floats on the
device inside the step. State is donated to the step.
"""

from __future__ import annotations

import concurrent.futures
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.resnet import BottleneckResNetBlock, ResNet

from benchmarks import flops
from benchmarks.reference import resnet_dp as reference

# bfloat16 program against the float32 reference, at initialisation, on 8
# images a chip, through the timed step itself: the loss, the gradient as the
# optimizer received it from the exchange, and what the step added to the
# parameters. bfloat16 carries 8 bits of mantissa (eps 2**-8 = 3.9e-3); every
# activation of the 53 layers is rounded to it and batch normalisation over 8
# images renormalises after each, so the loss and the gradient norm move by
# several eps. On the chip, over 27 seeds, the loss was off by 1.8e-4 to
# 1.8e-2 and the gradient norm by 2.1e-4 to 3.0e-2, half of them under 5e-3
# (my chip runs, PR 22). The bounds leave about three times the worst seen.
# What they catch is a wrong scale: a skipped exchange or a wrong divisor
# misses the gradient norm by the number of chips, a wrong learning rate the
# update norm by its factor. A norm cannot see unbiased noise, such as a
# rounding of the gradient on the wire (PERF.md, Open questions).
LOSS_RTOL = 6e-2
GNORM_RTOL = 8e-2


def _prepare(images):
    """uint8 pixels to roughly unit-variance floats, on the device."""
    return (images.astype(jnp.float32) - 127.5) / 74.0


class Job:
    sample = "img"
    throughput_metric = "img_s_chip"

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.seed = config, seed
        self.chips = hvd.size()
        self.samples_per_step = traffic["global_batch"]
        if self.samples_per_step % self.chips:
            raise ValueError(f"global_batch {self.samples_per_step} does not "
                             f"divide over {self.chips} chips")
        self.image = config["image_size"]
        self.classes = config["num_classes"]
        self.stages = tuple(config["stage_sizes"])
        self.model = ResNet(
            stage_sizes=self.stages, block_cls=BottleneckResNetBlock,
            num_classes=self.classes, num_filters=config["num_filters"],
            dtype=jnp.dtype(config["compute_dtype"]))
        o = config["optimizer"]
        self.lr = o["lr_per_256"] * self.samples_per_step / 256
        self.opt = hvd.DistributedOptimizer(optax.sgd(
            self.lr, momentum=o["momentum"]))
        self.flops_per_sample = flops.resnet_train_flops(
            self.image, self.stages, config["num_filters"], self.classes,
            config["bottleneck_expansion"])
        self.kernel_costs: dict = {}
        self.step = hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.REPLICATED,
                      hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1, 2))
        # Set by ``check``, which makes the first call of the step.
        self.first_call_s = None

    def init_variables(self, key):
        return self.model.init(
            key, jnp.zeros((1, self.image, self.image, 3), jnp.uint8),
            train=True)

    @functools.cached_property
    def _variables(self) -> dict:
        """Made on the device in one jitted call, and placed before the
        first step: un-placed init results would compile the step twice."""
        return hvd.replicate(jax.jit(self.init_variables)(
            jax.random.PRNGKey(self.seed)))

    def _loss(self, params, batch_stats, images, labels):
        logits, updates = self.model.apply(
            {"params": params, "batch_stats": batch_stats}, _prepare(images),
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()
        return loss, updates["batch_stats"]

    def _train_step(self, params, batch_stats, opt_state, data):
        (loss, new_stats), grads = jax.value_and_grad(
            self._loss, has_aux=True)(params, batch_stats, *data)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        new_stats = hvd.grouped_allreduce(new_stats, op=hvd.Average)
        return (optax.apply_updates(params, updates), new_stats, opt_state,
                hvd.allreduce(loss, op=hvd.Average))

    @functools.cached_property
    def _opt_state(self):
        return hvd.replicate(jax.jit(self.opt.init)(self._variables["params"]))

    def state(self) -> tuple:
        """``(params, batch_stats, opt_state)``, replicated on the mesh."""
        return (self._variables["params"], self._variables["batch_stats"],
                self._opt_state)

    def _images(self, rng, n: int):
        """``n`` images of uniform random bytes (eight at a time: a numpy
        generator makes 64-bit words six times faster than bytes)."""
        size = n * self.image * self.image * 3
        words = rng.integers(0, 2**64, (-(-size // 8),), dtype=np.uint64)
        return words.view(np.uint8)[:size].reshape(
            n, self.image, self.image, 3)

    def _batch(self, rng, n: int):
        return (self._images(rng, n),
                rng.integers(0, self.classes, (n,), dtype=np.int32))

    def host_batches(self, n: int) -> list:
        rng = np.random.default_rng(self.seed)
        return [self._batch(rng, self.samples_per_step) for _ in range(n)]

    def check(self):
        """One call of the timed step itself, the executable the window runs
        (gradient exchange, batch-statistics exchange and update included),
        against the float32 reference on the same parameters and sample.
        The reference can hold a few images a chip and the step takes only
        the cell's batch, so each chip gets its sample tiled to its share of
        the batch: copies change neither the batch statistics nor the mean
        loss nor the mean gradient. The last normalisation of every block
        starts with scale 0, which would cut every residual branch out of
        the gradient: the check sets those scales to 1. The step is given
        copies, since it donates its state.

        The reference runs on the host, in a thread of its own, while the
        chips go on with set-up: float32 convolutions at highest precision
        take the TPU compiler two minutes a shard (AOT, sandbox, PR 22), the
        host a few seconds, in true float32. Returns the function that waits
        for it and gives the rows ``(what, program, reference, rtol)``."""
        per_chip = self.config["check"]["images_per_chip"]
        copies, rest = divmod(self.samples_per_step // self.chips, per_chip)
        if rest:
            raise ValueError(f"{per_chip} images do not tile a chip's batch")
        images, labels = self._batch(np.random.default_rng(self.seed + 1),
                                     self.chips * per_chip)
        shape = (self.chips, per_chip)
        images = images.reshape(shape + images.shape[1:])
        labels = labels.reshape(shape)
        params = hvd.replicate(jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.ones_like(x) if path[-1].key == "scale" else x,
            self._variables["params"]))
        host_params = jax.device_get(params)

        def on_host():
            # Both settings are thread-local.
            with jax.default_device(jax.devices("cpu")[0]), \
                    jax.default_matmul_precision("highest"):
                loss, grad = reference.loss_and_grad(
                    host_params, _prepare(images), labels, self.stages)
                return loss, reference.shards.norm(grad)

        pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        pending = pool.submit(on_host)
        batch = hvd.shard_batch((
            np.tile(images, (1, copies, 1, 1, 1)).reshape(
                (-1,) + images.shape[2:]),
            np.tile(labels, (1, copies)).reshape(-1)))
        state = hvd.replicate(jax.jit(lambda t: jax.tree.map(jnp.copy, t))(
            (params, self._variables["batch_stats"], self._opt_state)))
        t0 = time.perf_counter()
        new_params, _, new_opt, loss = self.step(*state, batch)
        loss.block_until_ready()
        self.first_call_s = time.perf_counter() - t0
        # Momentum's trace after its first step is the gradient it was given.
        gnorm, moved = jax.jit(lambda new, old, trace: (
            optax.global_norm(trace),
            optax.global_norm(jax.tree.map(jnp.subtract, new, old))))(
                new_params, params, new_opt[0].trace)
        loss, gnorm, moved = float(loss), float(gnorm), float(moved)

        def finish() -> list:
            try:
                ref_loss, ref_gnorm = pending.result()
            finally:
                pool.shutdown()
            # Momentum's first step adds -lr times the gradient.
            return [("loss", loss, ref_loss, LOSS_RTOL),
                    ("gradient norm after the exchange", gnorm, ref_gnorm,
                     GNORM_RTOL),
                    ("update norm", moved, self.lr * ref_gnorm, GNORM_RTOL)]

        return finish
