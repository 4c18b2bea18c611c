"""The user's side of a data-parallel training job on a **looped** decoder
(``model_type: ouro``, Ouro-2.6B; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): one stack of sandwich-normed multi-head
attention blocks with SiLU-gated feed-forwards run ``total_ut_steps`` times
a step on the same parameters, a head and a learned exit gate after every
pass, the loss the exit distribution's expected cross-entropy less ``beta``
times its entropy (the paper's first stage). As ``gpt_dp`` is for the plain
dense decoder and sharing what is the same: the public API alone
(``hvd.replicate``, ``hvd.shard_batch``, ``hvd.run_step``,
``hvd.DistributedOptimizer``) over ``models/gpt.py``, AdamW with float32
moments, random tokens from the seed, next-token targets, state donated to
the step.

**A sample is a data token**: ``samples_per_step``, the throughput and
``flops_per_sample`` count the tokens of the batch; a token's four passes
through the stack and the head are what the model costs, counted into it.
The configuration file uses the published ``config.json`` key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops, flops_loop
from benchmarks.jobs import gpt_dp, gpt_moe_dp
from benchmarks.jobs.gpt_latent_moe_hybrid_dp import _StepKeepingCounts
from benchmarks.reference import gpt_loop_dp as reference

# bfloat16 program (four passes of six sandwich-normed blocks on shared
# parameters, the flash kernels forward and backward, the head's one rule
# over 4 x the rows, the exit gate's float32 scores, full recomputation)
# against the float32 reference (T x L calls of one block program, S x S
# attention, T whole log-softmaxes, the products of the exit distribution
# written out) at initialisation, **five 1024-token sequences a chip**
# through the timed step's own function: 4 x 5120 rows are 10 blocks of the
# head's rule, past ``gpt._HEAD_LOSS_FREE_BLOCKS``, so the check runs the
# chained blocks and the running weight-gradient sum the timed step's 16
# blocks run (one sequence was 2 blocks, and never did). On the chip at the
# published widths (my chip runs, PR 69: ``scripts/check_sweep.py --workload
# ouro-2.6b_s4096`` on 14 seeds of the shipped program and 2 of
# ``--variants params_bf16``, 3 more seeds through ``benchmarks/run.py``;
# each fault of ``benchmarks/tests/test_loop_faults.py`` as a whole run, 7
# runs on 7 seeds; PERF.md, Findings, PR 69). A mean over 5115 targets reads
# steadier than one over 1023 did: every shipped reading is a third to a
# tenth of what one sequence read, and the limits came down with them.
#
# * loss (near 11.18 = ln 49152 and the untrained stack's spread, less
#   0.1 x 1.12 of entropy): off by 1.7e-6 to 3.3e-5 (17 seeds). **The last
#   pass given ``lambda^T`` times the product reads 7.7e-2.** 4.6 times the
#   worst shipped reading, 500 under the fault it is for.
# * each pass's mean cross-entropy (four rows, near 11.28 to 11.31): off by
#   5.9e-7 to 8.9e-5, the later passes the more (the second worst of 68
#   readings is 5.4e-5). **``N_out`` left out between passes** reads 1.2e-3
#   and 5.3e-4 on the second row (6.9e-4 and 8.6e-4 on the fourth) and as
#   shipped on the first. 3.4 times the worst shipped reading, 1.8 under the
#   fault's least; the entropy term and the gradient norm hold that fault
#   with more room. **A pass dropped shows here on some seeds only** (5.6e-4
#   and 2.9e-5 on the fourth row: at initialisation the third and fourth
#   passes' means over 5115 targets can lie that close); the gradient's
#   norm holds it, below.
# * entropy term (beta H, near 0.11): off by 4.3e-5 to 1.0e-3. The last pass
#   gated reads 8.4e-2, the norm left out 1.7e-2 and 3.5e-2. Four times the
#   worst shipped reading, four under the least faulty.
# * gradient norm after the exchange: off by 3.3e-5 to 1.1e-3. **A pass
#   dropped reads 1.9e-2 and 1.0e-2**, the cotangent for ``weights`` dropped
#   3.9e-2 and 3.5e-2 (the hidden states lose the gate's share), the norm
#   left out 8.0e-2 and 3.9e-2, a skipped exchange or a wrong divisor the
#   number of chips. 3.2 times the worst shipped reading, 2.9 under the
#   least faulty.
# * **the exit gate's gradient norm alone** (2049 of 509.7M parameters, the
#   one place the head rule's cotangent for its weights shows by itself):
#   off by 6.0e-6 to 8.3e-3 as shipped; **0.136 and 7.3e-2 with that
#   cotangent dropped** (the gate then learns from the entropy alone), 49
#   with the last pass gated, 7.7e-2 and 3.9e-2 with the norm left out,
#   3.5e-2 and 3.4e-2 with a pass dropped. Three times the worst shipped
#   reading, 2.9 under the fault it is for.
# * update norm: off by 2.5e-5 to 2.9e-5 (AdamW's first step is lr times the
#   gradient's sign); a state left unchanged reads 1, another learning rate
#   its factor less one, and **the state held in bfloat16, the nearest
#   precision below the float32 the configuration states, reads 10.5**
#   (``--variants params_bf16``, both seeds: at 3e-6 a step is far under a
#   parameter's last bfloat16 bit, and what the rounding moves is ten times
#   the step) while every other row reads as shipped. ``gpt_dp``'s bound,
#   between the readings and 1 with the room above them that fresh seeds
#   want.
LOSS_RTOL = 1.5e-4
PASS_LOSS_RTOL = 3e-4
ENTROPY_RTOL = 4e-3
GNORM_RTOL = 3.5e-3
GATE_RTOL = 2.5e-2
UPDATE_RTOL = 1e-3


class Job(gpt_dp.Job):
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.seed = config, seed
        c = config
        self.chips = hvd.size()
        self.batch, self.seq = traffic["global_batch"], traffic["seq_len"]
        if self.batch % self.chips:
            raise ValueError(f"global_batch {self.batch} does not divide "
                             f"over {self.chips} chips")
        if self.seq > c["max_position_embeddings"]:
            raise ValueError("the model's context is "
                             f"{c['max_position_embeddings']}")
        if c["hidden_act"] != "silu" or c["use_sliding_window"] \
                or c["sliding_window"] or c["tie_word_embeddings"] \
                or c["rope_scaling"] \
                or set(c["layer_types"]) != {"full_attention"} \
                or len(c["layer_types"]) < c["num_hidden_layers"]:
            raise ValueError("this job runs SiLU-gated feed-forwards, full "
                             "attention in every layer, an untied head, an "
                             "unscaled rotary embedding")
        # Data tokens: what a user's tokens-per-second means.
        self.samples_per_step = self.batch * self.seq
        self.passes = c["total_ut_steps"]
        # What the reference is told of the model, from the published keys
        # and the objective's one assumed number; the widths it reads off
        # the matrices.
        self.reference_model = dict(
            passes=self.passes, beta=c["exit_entropy_beta"],
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"], gated_mlp=True,
            norms="pre_post", norm_eps=c["rms_norm_eps"],
            rope_theta=float(c["rope_theta"]), loop_passes=self.passes,
            exit_entropy_coef=c["exit_entropy_beta"])
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        self.opt = hvd.DistributedOptimizer(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]))
        shape = dict(heads=self.cfg.num_heads, kv_heads=self.cfg.kv_heads,
                     head_dim=self.cfg.head_dim)
        self.flops_per_sample = flops_loop.loop_train_flops(
            self.seq, self.cfg.num_layers, self.passes, self.cfg.embed_dim,
            mlp=self.cfg.mlp_dim, vocab=self.cfg.vocab_size, **shape)
        # What one step asks of the flash kernels on one chip: a
        # checkpointed block keeps the kernel's output and log-sum-exp, so
        # one forward and one backward a block application, ``passes`` times
        # the layers of them (``gpt_dp``'s rule still reckons the forward
        # twice a layer, which no step has made since PR 26: PERF.md, Open
        # questions).
        per_chip = self.batch // self.chips
        calls = self.passes * self.cfg.num_layers
        parts = [cost(per_chip, self.seq, **shape)
                 for cost in (flops.flash_forward_cost,
                              flops.flash_backward_cost)]
        self.kernel_costs = {"flash": {
            "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
            **{key: calls * sum(p[key] for p in parts)
               for key in ("ops", "bytes")}}}
        self.step = _StepKeepingCounts(hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1)))
        self.first_call_s = None
        self.check_step = hvd.run_step(
            self._checked_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)

    def _loss(self, params, tokens, targets, positions):
        # Positional, all of it: ``tests/test_faults.py`` wraps this call.
        return gpt.loss_and_aux(params, tokens, targets, positions, self.cfg)

    # The step with the loss's parts beside it, as the expert jobs'.
    _step_with_aux = gpt_moe_dp.Job._step_with_aux

    def _train_step(self, params, opt_state, data):
        """The timed step; its last output is the step's mean exit
        distribution over all ranks (``_StepKeepingCounts`` keeps it off the
        loop, as the expert jobs' counts)."""
        out, aux = self._step_with_aux(params, opt_state, data)
        return (*out, hvd.allreduce(aux["exit_probs"], op=hvd.Average))

    def mean_exit_step(self):
        """``sum_t t p^t`` of the newest step's mean exit distribution, the
        passes counted from 1: where the gate would stop a token on average
        (1.875 at a gate of one half); None before a step has run."""
        if self.step.last_counts is None:
            return None
        probs = np.asarray(self.step.last_counts, np.float64)
        return float(np.sum(np.arange(1, len(probs) + 1) * probs))

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, each pass's mean cross-entropy, the mean entropy of the exit
        distribution, the norm of the gradient as the optimizer received it
        from the exchange (AdamW's first moment after its first step is
        ``1 - b1`` times that gradient), the exit gate's alone, and the norm
        of what the step added to the parameters."""
        (new_params, new_opt, loss), aux = self._step_with_aux(
            params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        mu, scale = new_opt[0].mu, 1 - self.adamw["b1"]
        return (loss, hvd.allreduce(aux["pass_losses"], op=hvd.Average),
                hvd.allreduce(aux["exit_entropy"], op=hvd.Average),
                optax.global_norm(mu) / scale,
                optax.global_norm(mu["exit_gate"]) / scale,
                optax.global_norm(moved))

    def check(self):
        """As ``gpt_dp``'s: one call of the timed step's own function at the
        published widths, against the float32 reference (T x L block calls,
        S x S attention, T whole log-softmaxes, a sequence at a time) on the
        same parameters and sample; the reference first, before the
        optimizer state exists. **The sample is large enough that the
        head's rule chains its blocks as the timed step's does** (T x the
        rows past ``gpt._HEAD_LOSS_FREE_BLOCKS`` blocks: 5 sequences of 1024
        are 10 blocks of 2048 rows)."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = gpt_dp._batch(np.random.default_rng(self.seed + 1), shape,
                             self.cfg.vocab_size)
        per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
        with jax.default_matmul_precision("highest"):
            ref_loss, ref, grad = reference.loss_and_grad(
                self._params, *(x.reshape(per_shard) for x in data),
                **self.reference_model)
        ref_gnorm = reference.shards.norm(grad)
        ref_gate = reference.shards.norm(grad["exit_gate"])
        ref_moved = reference.adamw_first_update_norm(
            self._params, grad, self.adamw["lr"],
            self.adamw["weight_decay"], self.adamw["eps"])
        del grad
        loss, pass_losses, entropy, gnorm, gate, moved = (
            np.asarray(x, np.float64) for x in self.check_step(
                self._params, self._opt_state, hvd.shard_batch(data)))
        beta = self.reference_model["beta"]
        rows = [("loss", float(loss), ref_loss, LOSS_RTOL)] + [
            (f"pass {t + 1}'s mean cross-entropy", float(pass_losses[t]),
             float(ref["pass_losses"][t]), PASS_LOSS_RTOL)
            for t in range(self.passes)] + [
            ("entropy term", beta * float(entropy),
             beta * float(ref["exit_entropy"]), ENTROPY_RTOL),
            ("gradient norm after the exchange", float(gnorm), ref_gnorm,
             GNORM_RTOL),
            ("exit gate's gradient norm", float(gate), ref_gate, GATE_RTOL),
            ("update norm", float(moved), ref_moved, UPDATE_RTOL)]
        return lambda: rows
