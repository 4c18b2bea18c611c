"""The user's side of a data-parallel training job on a sparse decoder
(every block's feed-forward an expert layer), as ``gpt_dp`` is for the dense
one and sharing what is the same: AdamW with float32 moments, random tokens
from the seed, next-token loss, state donated to the step. The configuration
file uses the published ``config.json`` key names (OLMoE's).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops, flops_moe
from benchmarks.jobs import gpt_dp
from benchmarks.reference import gpt_moe_dp as reference

# bfloat16 program (flash kernels, the sorted grouped expert layer, full
# recomputation) against the float32 every-expert-on-every-token reference at
# initialisation, one 1024-token sequence a chip, through the timed step's
# own function. The router's product is float32 at the highest precision in
# both, but the program's router reads the block's activations rounded to
# bfloat16 (eps 2**-8), so a token's 8 experts differ from the reference's
# where its 8th and 9th probabilities lie within that rounding of each other:
# on the chip 20 to 36 of a sample's 8192 choices did (0.24% to 0.44%; half
# the summed difference of the two sides' per-expert counts, a lower bound).
# Each swaps one expert's output for another's at nearly the same weight,
# which moves the loss and the norms far less than the activations' rounding
# does. The load-balance term is E sum_e f_e P_e with f_e counted from the
# choices, so it feels them most. On the chip, over 16 seeds (my chip runs,
# PR 25), the loss was off by at most 2.5e-4, the load-balance term by
# 3.5e-4 to 1.0e-3, the z term (a mean of squared log-sum-exps of float32
# logits) by at most 2.0e-4, the gradient norm by 9.2e-4 to 1.35e-3, the
# update norm by 2e-6 to 8e-6 and once 3.3e-4. The bounds leave three to four
# times the worst seen (the rehearsal's tiny sizes come closer: 8e-4 on the
# loss, 7.7e-3 on the gradient norm at some seeds). What they catch: a router
# product in one bfloat16 pass moves the logits by 2**-9 of their size and
# with them the z term by several 1e-3 and an order more choices; a capacity
# that drops tokens (at this sample's routing the busiest expert gets 2.6 to
# 4.8 times the mean, so a capacity factor of 1.25 would drop most of its
# rows) moves the loss by percents; a skipped exchange or a wrong divisor
# misses the gradient norm by the number of chips, a wrong learning rate the
# update norm by its factor. A norm cannot see unbiased noise (PERF.md, Open
# questions).
LOSS_RTOL = 8e-4
LOAD_BALANCE_RTOL = 3e-3
ROUTER_Z_RTOL = 6e-4
GNORM_RTOL = 5e-3
UPDATE_RTOL = 1e-3


class Job(gpt_dp.Job):
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.seed = config, seed
        self.chips = hvd.size()
        self.batch, self.seq = traffic["global_batch"], traffic["seq_len"]
        if self.batch % self.chips:
            raise ValueError(f"global_batch {self.batch} does not divide "
                             f"over {self.chips} chips")
        if self.seq > config["max_position_embeddings"]:
            raise ValueError("the model's context is "
                             f"{config['max_position_embeddings']}")
        self.samples_per_step = self.batch * self.seq
        c = config
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"], moe_every=1,
            num_experts=c["num_experts"],
            experts_per_token=c["num_experts_per_tok"],
            load_balance_coef=c["router_aux_loss_coef"],
            router_z_coef=c["router_z_loss_coef"], qk_norm=True,
            norm_eps=c["rms_norm_eps"])
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        self.opt = hvd.DistributedOptimizer(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]))
        shape = dict(heads=self.cfg.num_heads, kv_heads=self.cfg.kv_heads,
                     head_dim=self.cfg.head_dim)
        experts = dict(embed=self.cfg.embed_dim, width=self.cfg.mlp_dim,
                       experts=self.cfg.num_experts)
        self.flops_per_sample = flops_moe.moe_train_flops(
            self.seq, self.cfg.num_layers, top_k=self.cfg.experts_per_token,
            vocab=self.cfg.vocab_size, **shape, **experts)
        # What one step asks of its kernels on one chip: every layer's
        # forward (run again in the backward pass under full recomputation)
        # and every layer's backward, which for the grouped matmuls is two
        # passes.
        per_chip = self.batch // self.chips
        calls_fwd = self.cfg.num_layers * (2 if c["remat"] == "full" else 1)
        fwd = flops.flash_forward_cost(per_chip, self.seq, **shape)
        bwd = flops.flash_backward_cost(per_chip, self.seq, **shape)
        one = flops_moe.grouped_matmul_pass_cost(
            per_chip * self.seq * self.cfg.experts_per_token, **experts)
        passes = calls_fwd + 2 * self.cfg.num_layers
        self.kernel_costs = {
            "flash": {
                # The three flash kernels by the names the program gives
                # them: the grouped matmuls are Mosaic kernels too.
                "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
                "ops": calls_fwd * fwd["ops"]
                + self.cfg.num_layers * bwd["ops"],
                "bytes": calls_fwd * fwd["bytes"]
                + self.cfg.num_layers * bwd["bytes"]},
            "grouped_matmul": {
                # XLA's own name for the kernel it compiles lax.ragged_dot
                # to (and for the small kernel that lays out its groups).
                "match": r"^ragged-dot-",
                "ops": passes * one["ops"], "bytes": passes * one["bytes"]}}
        self.step = hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1))
        self.first_call_s = None
        self.check_step = hvd.run_step(
            self._checked_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)
        # Tokens per expert on the check's sample, [layers, experts].
        self.expert_counts = None

    def _loss(self, params, tokens, targets, positions):
        return gpt.loss_and_aux(params, tokens, targets, positions, self.cfg)

    def _step_with_aux(self, params, opt_state, data):
        (loss, aux), grads = jax.value_and_grad(self._loss, has_aux=True)(
            params, *data)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, op=hvd.Average)), aux

    def _train_step(self, params, opt_state, data):
        return self._step_with_aux(params, opt_state, data)[0]

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, the two auxiliary terms in it, the norm of the gradient as the
        optimizer received it from the exchange (AdamW's first moment after
        its first step is ``1 - b1`` times that gradient), the norm of what
        the step added to the parameters, and the tokens each expert got."""
        (new_params, new_opt, loss), aux = self._step_with_aux(
            params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        return (loss,
                hvd.allreduce(aux["load_balance"], op=hvd.Average),
                hvd.allreduce(aux["router_z"], op=hvd.Average),
                optax.global_norm(new_opt[0].mu) / (1 - self.adamw["b1"]),
                optax.global_norm(moved),
                hvd.allreduce(aux["counts"], op=hvd.Sum))

    def check(self):
        """As ``gpt_dp``'s, with the two auxiliary terms as rows of their
        own and the experts' token counts kept for the load metric."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = gpt_dp._batch(np.random.default_rng(self.seed + 1), shape,
                             self.cfg.vocab_size)
        per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
        with jax.default_matmul_precision("highest"):
            ref_loss, ref, grad = reference.loss_and_grad(
                self._params, *(x.reshape(per_shard) for x in data),
                top_k=self.cfg.experts_per_token,
                norm_eps=self.cfg.norm_eps,
                load_balance_coef=self.cfg.load_balance_coef,
                router_z_coef=self.cfg.router_z_coef)
        ref_gnorm = reference.shards.norm(grad)
        ref_moved = reference.adamw_first_update_norm(
            self._params, grad, self.adamw["lr"],
            self.adamw["weight_decay"], self.adamw["eps"])
        del grad
        *numbers, counts = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data))
        loss, load_balance, router_z, gnorm, moved = map(float, numbers)
        self.expert_counts = np.asarray(counts)
        # How many of the sample's token-expert choices differ from the
        # reference's: half the summed difference of the counts bounds it
        # from below (a flip takes one from an expert and gives another one).
        self.choices_moved = int(np.abs(
            self.expert_counts - np.asarray(ref["counts"])).sum() // 2)
        rows = [("loss", loss, ref_loss, LOSS_RTOL),
                ("load-balance term", load_balance, ref["load_balance"],
                 LOAD_BALANCE_RTOL),
                ("router z term", router_z, ref["router_z"], ROUTER_Z_RTOL),
                ("gradient norm after the exchange", gnorm, ref_gnorm,
                 GNORM_RTOL),
                ("update norm", moved, ref_moved, UPDATE_RTOL)]
        return lambda: rows
