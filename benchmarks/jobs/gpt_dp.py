"""The user's side of a data-parallel GPT training job.

Written against the public API only (``hvd.replicate``, ``hvd.shard_batch``,
``hvd.run_step``, ``hvd.DistributedOptimizer``) and the program's own model
code (``models/gpt.py``), after ``chip_smoke.py`` leg B: AdamW with float32
moments, random tokens from the seed, next-token loss. The configuration
file uses the published ``config.json`` key names. State is donated to the
step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops
from benchmarks.reference import gpt_dp as reference

# bfloat16 program (flash kernels forward and backward, full recomputation)
# against the float32 reference at initialisation, one 1024-token sequence a
# chip, through the timed step itself: the loss, the gradient as the
# optimizer received it from the exchange, and what the step added to the
# parameters. The loss is a mean of 1024 token losses near ln(vocab), each
# off by a few bfloat16 eps (2**-8 = 3.9e-3) of its logits' spread, so it
# moves far less than one eps; the gradient norm sums rounding through six
# layers. On the chip, over 30 seeds, the loss was off by 8.5e-7 to 4.6e-5
# and the gradient norm by 5.0e-4 to 1.5e-3; over 13 the update norm by
# 2.4e-7 to 1.5e-5: AdamW's first step is lr times the gradient's sign, which
# rounding hardly moves (my chip runs, PR 22). The bounds leave nine, five
# and sixty-five times that (the update norm is off by 1e-4 at the
# rehearsal's tiny sizes); an 8-bit float (eps sixteen times bfloat16's)
# would miss the gradient norm's. What the norms catch beyond that is a wrong
# scale: a skipped exchange or a wrong divisor misses the gradient norm by
# the number of chips, a wrong learning rate the update norm by its factor.
# A norm cannot see unbiased noise, such as a rounding of the gradient on
# the wire (PERF.md, Open questions).
LOSS_RTOL = 4e-4
GNORM_RTOL = 8e-3
UPDATE_RTOL = 1e-3


def _batch(rng, shape, vocab):
    tokens = rng.integers(0, vocab, shape, dtype=np.int32)
    targets = np.roll(tokens, -1, axis=-1)
    targets[..., -1] = -1
    positions = np.broadcast_to(np.arange(shape[-1], dtype=np.int32),
                                shape).copy()
    return tokens, targets, positions


class Job:
    sample = "tok"
    throughput_metric = "tok_s_chip"

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.seed = config, seed
        self.chips = hvd.size()
        self.batch, self.seq = traffic["global_batch"], traffic["seq_len"]
        if self.batch % self.chips:
            raise ValueError(f"global_batch {self.batch} does not divide "
                             f"over {self.chips} chips")
        if self.seq > config["sliding_window"]:
            raise ValueError("full causal attention stands for the sliding "
                             f"window only up to {config['sliding_window']}")
        self.samples_per_step = self.batch * self.seq
        c = config
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"])
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        self.opt = hvd.DistributedOptimizer(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]))
        shape = dict(heads=self.cfg.num_heads, kv_heads=self.cfg.kv_heads,
                     head_dim=self.cfg.head_dim)
        self.flops_per_sample = flops.gpt_train_flops(
            self.seq, self.cfg.num_layers, self.cfg.embed_dim,
            mlp=self.cfg.mlp_dim, vocab=self.cfg.vocab_size, **shape)
        # What the flash kernels are asked for in one step on one chip:
        # every layer's forward (run again in the backward pass under full
        # recomputation) and every layer's backward.
        per_chip = self.batch // self.chips
        fwd = flops.flash_forward_cost(per_chip, self.seq, **shape)
        bwd = flops.flash_backward_cost(per_chip, self.seq, **shape)
        calls_fwd = self.cfg.num_layers * (2 if c["remat"] == "full" else 1)
        self.kernel_costs = {"flash": {
            # XLA's own target name for a Mosaic kernel. Today the flash
            # kernels are the only ones in this step and carry no name of
            # their own, so the three cannot be told apart by name.
            "match": r'custom_call_target="tpu_custom_call"',
            "ops": calls_fwd * fwd["ops"]
            + self.cfg.num_layers * bwd["ops"],
            "bytes": calls_fwd * fwd["bytes"]
            + self.cfg.num_layers * bwd["bytes"]}}
        self.step = hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1))
        # ``check`` leaves the first call of the step to the loop.
        self.first_call_s = None
        self.check_step = hvd.run_step(
            self._checked_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)

    def init_params(self, key):
        return gpt.init_params(key, self.cfg)

    @functools.cached_property
    def _params(self) -> dict:
        """Made on the device in one jitted call, in the type they are
        trained in, and placed before the first step."""
        return hvd.replicate(jax.jit(self.init_params)(
            jax.random.PRNGKey(self.seed)))

    def _loss(self, params, tokens, targets, positions):
        return gpt.loss_fn(params, tokens, targets, positions, self.cfg)

    def _train_step(self, params, opt_state, data):
        loss, grads = jax.value_and_grad(self._loss)(params, *data)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, op=hvd.Average))

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to three numbers:
        the loss, the norm of the gradient as the optimizer received it from
        the exchange (AdamW's first moment after its first step is
        ``1 - b1`` times that gradient), and the norm of what the step added
        to the parameters."""
        new_params, new_opt, loss = self._train_step(params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        return (loss,
                optax.global_norm(new_opt[0].mu) / (1 - self.adamw["b1"]),
                optax.global_norm(moved))

    @functools.cached_property
    def _opt_state(self):
        return hvd.replicate(jax.jit(self.opt.init)(self._params))

    def state(self) -> tuple:
        """``(params, opt_state)``, replicated on the mesh."""
        return self._params, self._opt_state

    def host_batches(self, n: int) -> list:
        rng = np.random.default_rng(self.seed)
        return [_batch(rng, (self.batch, self.seq), self.cfg.vocab_size)
                for _ in range(n)]

    def check(self):
        """One call of the timed step's own function (flash kernels forward
        and backward, gradient exchange and update included) on a sample the
        reference can hold, against the float32 reference on the same
        parameters and sample. All of it runs here, on the chips, and the
        reference first, before the optimizer state exists: its float32
        gradient would not fit beside it. Returns the function that gives
        the rows ``(what, program, reference, rtol)``."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = _batch(np.random.default_rng(self.seed + 1), shape,
                      self.cfg.vocab_size)
        per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
        with jax.default_matmul_precision("highest"):
            ref_loss, grad = reference.loss_and_grad(
                self._params, *(x.reshape(per_shard) for x in data))
        ref_gnorm = reference.shards.norm(grad)
        ref_moved = reference.adamw_first_update_norm(
            self._params, grad, self.adamw["lr"],
            self.adamw["weight_decay"], self.adamw["eps"])
        del grad
        loss, gnorm, moved = (float(x) for x in self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data)))
        rows = [("loss", loss, ref_loss, LOSS_RTOL),
                ("gradient norm after the exchange", gnorm, ref_gnorm,
                 GNORM_RTOL),
                ("update norm", moved, ref_moved, UPDATE_RTOL)]
        return lambda: rows
