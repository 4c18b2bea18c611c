"""The user's side of a data-parallel training job on a grouped-query decoder
of expert blocks **trained by diffusion over blocks** (``model_type:
sdar_moe``, SDAR-30B-A3B-Chat; the objective is BD3-LM's, arXiv:2503.09573),
of which this rank holds its share of the experts, as ``gpt_moe_dp`` is for
the sparse decoder trained by next-token prediction and sharing what is the
same: AdamW with float32 moments, state donated to the step, the loss with
its load-balance term.

**What the method makes the user's:** the batch. A sample is ``L`` data
tokens (``seq_len``) from the rows of the vocabulary held here; from the
seed, one ``t`` a block of ``block_length`` tokens, uniform in ``[eps, 1)``,
and each token of the block masked with probability ``t`` (the mask id is
the slice's last row). The step is given ``2 L`` rows, the noised copy and
then the clean one, at positions ``[0..L-1 ; 0..L-1]``; targets (the data
token where it was masked, in place, no shift) and weights (``1 / t``) for
the first ``L`` rows; the loss is the weighted sum over the masked tokens
over **all** the sample's data tokens. The program is told the block length
(``GPTConfig.diffusion_block``) and nothing else.

**A sample is a data token**: ``samples_per_step``, the throughput and
``flops_per_sample`` count the ``L`` tokens a sequence trains on; the ``2 L``
rows are what the method costs, counted into them. The configuration file
uses the published ``config.json`` key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops_bd
from benchmarks.jobs import gpt_linear_moe_dp, gpt_moe_dp
from benchmarks.jobs.gpt_latent_moe_hybrid_dp import (Job as _KeepsCounts,
                                                      _StepKeepingCounts)
from benchmarks.jobs.gpt_window_moe_dp import _routers_off
from benchmarks.reference import gpt_bd_moe_dp as reference

# bfloat16 program (the flash kernels under the block-diffusion mask, their
# grids the mask's kept tiles; the sorted grouped expert layer over this
# rank's 16 experts, windowed; the head over the noised half alone; full
# recomputation) against the float32 reference (the mask from the three
# clauses, S x S logits 256 query rows at a time, every held expert on every
# row) on a seeded checkpoint, **at the timed step's own shapes: one sample
# of 8,192 data tokens, 16,384 rows, a chip** (the kernels' grids are the
# timed window's, 80 of 256 tiles at 1024-wide tiles: 8 noised-noised on the
# diagonal, 36 noised-clean above the square's diagonal, 36 clean-clean),
# noised from the seed as a timed batch is, through the timed step's own
# function. The reference runs first, before the optimizer state exists
# (``gpt_dp.Job._opt_state``): 2.4 GiB of parameters, as much of gradient
# and 5.6 GiB of temporaries (AOT, sandbox, PR 65). On the chip (my chip
# runs, PR 65: ``scripts/check_sweep.py --workload sdar-30b-a3b-chat_s8192``,
# 11 seeds of the shipped program, 2 or 3 seeds of each control,
# ``--variants``, and 7 more seeds through ``benchmarks/run.py``; PERF.md,
# Findings, PR 65, has every reading, and those of the first form of this
# check, on 1,024 data tokens):
#
# * loss: off by 1.9e-5 to 3.9e-4 (18 seeds). A sum of about 4,500 token
#   losses near ln(18992), each under a weight 1 / t between 1 and 10, over
#   8,192. What the row is for reads far off: half of the targets dropped
#   under the
#   unchanged divisor 0.48; the weights left out, the divisor the masked
#   tokens or a shift a factor of two (``tests/test_gpt_bd_moe.py`` holds
#   each to the reference at a tiny size). Five times the worst reading.
# * load-balance term: off by 3.7e-5 to 4.4e-4 (E sum_e f_e P_e over all
#   2 L rows, f_e counted from the choices: 1,600 to 1,800 of a sample's
#   786,432 row-expert choices differ from the reference's where a row's 8th
#   and 9th probabilities lie within bfloat16's rounding of the activations,
#   and the seeded router is far from even, the busiest expert at 5 to 8
#   times the mean, so a moved choice weighs). **Four experts a row where
#   the configuration says eight reads 0.48 and 0.49** (``--variant
#   top_k_4``, two seeds). 4.5 times the worst shipped reading.
# * gradient norm after the exchange: off by 2.0e-6 to **3.1e-2** (1.5e-2
#   and 1.1e-2 the next worst, eight of 18 seeds over 5e-3: the masked rows
#   again, the 1 / t weights, and held experts that few of the sample's rows
#   chose); half of the targets dropped reads 0.32, a skipped exchange or a
#   wrong divisor the number of chips. In the middle on a logarithmic scale
#   (three times either way).
# * update norm: off by 1.7e-4 to 2.1e-3 (AdamW's first step is lr times the
#   gradient's sign but for elements as small as its eps, a held expert's
#   that hardly a row chose); a state left unchanged reads 1, another
#   learning rate 0.99. Between the readings and 1, with the room above
#   them that fresh seeds want.
# * **the leak's row**: the gradient of every layer's key and value
#   projections, which reach the loss through attention alone, **along the
#   reference's** as a share of the reference's own length (1 where they
#   agree; a norm would not see a turn), **from a second call of the same
#   step with the targets of each sequence's first 8 blocks alone**
#   (``MASK_ROW_BLOCKS``), their weights scaled by what the sequence is
#   longer than they (256), so that the load-balance term's gradient, which
#   is all 16,384 rows' and no target's, does not drown 32 targets' of
#   8,192 (unscaled the leak read 2.0e-2 on two seeds). Why a second call:
#   the fault this row is for, a noised row let see its **own** clean block
#   (``bk <= bq`` in the second clause: the token it is to predict among its
#   keys), adds 4 keys to a noised row of block b's 4 + 4 b, so over a whole
#   sequence's 2,048 blocks it moves attention by ln(2048) / 2048 = 0.4%: on
#   the whole sample the same measure reads 2.0e-2 to 5.3e-2 with the leak
#   beside 1.1e-3 to 4.9e-2 as shipped (short on every seed: 4,500 masked
#   rows enter as one embedding row, and what bfloat16 rounds off them adds
#   up and does not average out; PERF.md, Findings, PR 65). Over the first 8
#   blocks the leak is a quarter
#   of a row's keys: the row reads 1.6e-6 to 3.8e-3 as shipped (18 seeds)
#   and **6.1e-2, 6.3e-2, 1.3e-1 and 1.9e-1 with the leak**
#   (``--variant bd_own_clean_block``, and the whole run with the fault
#   planted, where every other row passes). In the middle on a logarithmic
#   scale (four times either way).
# * **What no row sees: one tile of the 80 left out of the grids' tables**
#   (the last noised query tile's first clean key tile: 1,024 rows lose an
#   eighth of their keys; ``--variant bd_tile_dropped``). The key and value
#   gradients along the reference's on the whole sample read 7.5e-2, 8.3e-2
#   and 1.4e-1 with it beside 1.1e-3 to 4.9e-2 on 18 sound seeds (a row with
#   that measure stood here at 4e-2 and a sound seed, 2147487311, missed
#   it), the gradient norm 3.5e-3 to 2.3e-2, the load-balance term 1.6e-3
#   to 7.8e-3: each inside or beside its sound range. The compiled kernels
#   alone are held to dense attention at this grid by
#   ``scripts/flash_block_sweep.py --dense-long sdar`` (within 1.4e-4 along,
#   0.3% off): a change to the mask's tiling is held there first.
# * the routers' row (``gpt_window_moe_dp``'s): every expert block's float32
#   outputs as the step made them against the reference's product on the
#   operand the step's own product read (``GPTConfig.router_probe``): 0.0 to
#   1.5e-6 as shipped (the same float32 product); **8.6e-3 and 8.9e-3 with
#   the product in one bfloat16 pass**, where the configuration says float32
#   (``--variant router_bf16``), which reads as shipped on every other row.
LOSS_RTOL = 2e-3
LOAD_BALANCE_RTOL = 2e-3
GNORM_RTOL = 9e-2
UPDATE_RTOL = 3e-2
MASK_RTOL = 1.4e-2
ROUTER_RTOL = 1e-4
# The leak's row reads the targets of each sequence's first blocks alone.
MASK_ROW_BLOCKS = 8


def _kv_leaves(tree) -> list:
    """Every layer's key and value projections."""
    return [layer[name] for layer in tree["layers"] for name in ("wk", "wv")]


def _along(reference_leaves, leaves) -> float:
    """The program's gradient along the reference's, as a share of the
    reference's own length: 1 where they agree (a norm would not see a
    turn)."""
    return sum(float(np.vdot(r, g))
               for r, g in zip(reference_leaves, leaves, strict=True)) \
        / sum(float(np.vdot(r, r)) for r in reference_leaves)


def _noised(rng, batch: int, length: int, block: int, eps: float,
            mask_id: int) -> tuple:
    """One batch from ``rng``: ``(tokens [batch, 2 L], targets [batch, L],
    positions [batch, 2 L], weights [batch, L])``. Drawn in the order the
    reference's ``noised_batch`` states: the data ids, one ``t`` a block,
    one uniform number a token."""
    x = rng.integers(0, mask_id, (batch, length), dtype=np.int32)
    t = np.repeat(eps + (1.0 - eps) * rng.random((batch, length // block)),
                  block, axis=1)
    masked = rng.random((batch, length)) < t
    at = np.broadcast_to(np.arange(length, dtype=np.int32), x.shape)
    return (np.concatenate([np.where(masked, mask_id, x), x], axis=1),
            np.where(masked, x, np.int32(-1)),
            np.concatenate([at, at], axis=1),
            (1.0 / t).astype(np.float32))


class Job(gpt_moe_dp.Job):
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
        if c["mlp_only_layers"] or c["decoder_sparse_step"] != 1 \
                or c["hidden_act"] != "silu" or c["use_sliding_window"] \
                or c["sliding_window"] or c["attention_bias"] \
                or c["tie_word_embeddings"] or c["rope_scaling"]:
            raise ValueError("this job runs an expert block after every "
                             "mixer, SiLU, full attention, no bias, an "
                             "untied head, an unscaled rotary embedding")
        share = c["expert_parallel"]
        router = c["published"]["num_experts"]
        if c["num_experts"] * share["chips"] != router:
            raise ValueError(
                f"{share['chips']} chips of {c['num_experts']} experts are "
                f"not the published {router}")
        # What the noising is told: the block, the least t, the mask id (the
        # slice's last row; data ids are drawn below it).
        self.block, self.eps = c["block_length"], c["noise_eps"]
        self.mask_id = c["vocab_size"] - 1
        if self.seq % self.block or c["check"]["seq_len"] % self.block:
            raise ValueError(f"blocks of {self.block} do not divide the "
                             "sequence")
        # Data tokens: what a user's tokens-per-second means.
        self.samples_per_step = self.batch * self.seq
        first = share["rank"] * c["num_experts"]
        # What the reference is told of the model, from the published keys;
        # the widths it reads off the matrices.
        self.reference_model = dict(
            block=self.block, top_k=c["num_experts_per_tok"],
            first_expert=first, rope_theta=float(c["rope_theta"]),
            norm_eps=c["rms_norm_eps"],
            load_balance_coef=c["router_aux_loss_coef"])
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["moe_intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"], moe_every=1,
            num_experts=router, experts_per_token=c["num_experts_per_tok"],
            experts_held=c["num_experts"], first_expert=first,
            renormalize_experts=c["norm_topk_prob"],
            load_balance_coef=c["router_aux_loss_coef"], router_z_coef=0.0,
            qk_head_norm=True, norm_eps=c["rms_norm_eps"],
            rope_theta=float(c["rope_theta"]), router_probe=True,
            diffusion_block=self.block)
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        self.opt = hvd.DistributedOptimizer(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]))
        shape = dict(heads=self.cfg.num_heads, kv_heads=self.cfg.kv_heads,
                     head_dim=self.cfg.head_dim)
        self.flops_per_sample = flops_bd.bd_moe_train_flops(
            self.seq, self.block, self.cfg.num_layers, self.cfg.embed_dim,
            vocab=self.cfg.vocab_size, experts=dict(
                router=router, width=self.cfg.mlp_dim,
                top_k=self.cfg.experts_per_token, held=c["num_experts"]),
            **shape)
        # What one step asks of its kernels on one chip. A checkpointed
        # block keeps the flash kernel's output and log-sum-exp, so the
        # algorithm's share is one forward and one backward a layer, over
        # the mask's pairs; the grouped matmuls' a forward pass and two for
        # the backward over the held pairs of the 2 L rows.
        per_chip = self.batch // self.chips
        # What the expert layer routes: both copies' rows.
        self.per_chip_tokens = per_chip * 2 * self.seq
        parts = [cost(per_chip, self.seq, self.block, **shape)
                 for cost in (flops_bd.flash_forward_cost,
                              flops_bd.flash_backward_cost)]
        self.kernel_costs = {"flash": {
            "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
            **{key: self.cfg.num_layers * sum(p[key] for p in parts)
               for key in ("ops", "bytes")}}}
        # Until the check has counted the held experts' rows: an even
        # routing's share of the rows' pairs.
        self._grouped_matmul_cost(c["num_experts"] / router)
        self.step = _StepKeepingCounts(hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1)))
        self.first_call_s = None
        self.check_step = hvd.run_step(
            self._checked_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)
        # Rows per expert on the check's sample, [layers, router].
        self.expert_counts = None
        # The reference on a seed's sample, kept: a caller that checks
        # several programs on one seed's parameters (``scripts/
        # check_sweep.py --variants``) hands its jobs one dict.
        self.reference_cache: dict = {}

    # Three passes a layer over the pairs the held experts really multiply,
    # of the rows the expert layer sees (``per_chip_tokens``).
    _grouped_matmul_cost = gpt_linear_moe_dp.Job._grouped_matmul_cost

    def init_params(self, key):
        """The model's parameters from the seed as the autoregressive
        checkpoint this training starts from holds them: an embedding of
        the configuration's ``embedding_deviation`` where ``models/gpt.py``
        makes 0.02 (the other share cells', since PR 49: a trained model's
        stream is its tokens' own content), **but for the mask id's row**,
        which that checkpoint never trained on and which stays as
        ``gpt.init_params`` made it."""
        params = gpt.init_params(key, self.cfg)
        scale = jnp.full((self.cfg.vocab_size, 1),
                         self.config["embedding_deviation"] / 0.02,
                         jnp.float32).at[self.mask_id].set(1.0)
        return {**params, "embed": params["embed"] * scale}

    def _loss(self, params, tokens, targets, positions, weights):
        # Positional, all of it: ``tests/test_faults.py`` wraps this call.
        return gpt.loss_and_aux(params, tokens, targets, positions, self.cfg,
                                -1, weights, targets.size)

    def _train_step(self, params, opt_state, data):
        """The timed step; its last output is the step's rows per expert
        over all ranks (``_StepKeepingCounts`` keeps it off the loop)."""
        out, aux = self._step_with_aux(params, opt_state, data)
        return (*out, hvd.allreduce(aux["counts"], op=hvd.Sum))

    # ``moe_held_pairs_pct``: the newest step's pairs on the held experts.
    held_pairs_pct = _KeepsCounts.held_pairs_pct

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, the load-balance term in it, the norm of the gradient as the
        optimizer received it from the exchange (AdamW's first moment after
        its first step is ``1 - b1`` times that gradient), the norm of what
        the step added to the parameters, the rows each expert got, the
        gradient of the key and value projections itself (6 x 2 x 4 MB) and
        what each expert block's router read and gave (101 and 6 MB)."""
        (new_params, new_opt, loss), aux = self._step_with_aux(
            params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        mu = new_opt[0].mu
        scale = 1 - self.adamw["b1"]
        return (loss,
                hvd.allreduce(aux["load_balance"], op=hvd.Average),
                optax.global_norm(mu) / scale,
                optax.global_norm(moved),
                hvd.allreduce(aux["counts"], op=hvd.Sum),
                [leaf / scale for leaf in _kv_leaves(mu)],
                # every rank's rows, [T, layers, .]
                [hvd.allgather(jnp.swapaxes(aux[key], 0, 1))
                 for key in ("router_inputs", "router_logits")])

    def host_batches(self, n: int) -> list:
        """A fresh noise draw in each batch of the ring."""
        rng = np.random.default_rng(self.seed)
        return [_noised(rng, self.batch, self.seq, self.block, self.eps,
                        self.mask_id) for _ in range(n)]

    def _reference(self, data):
        """The reference's loss, parts and gradient on ``data``."""
        k = self.config["check"]
        with jax.default_matmul_precision("highest"):
            return reference.loss_and_grad(
                self._params, *(x.reshape(
                    self.chips, k["sequences_per_chip"], -1) for x in data),
                **self.reference_model)

    def _references(self, data, early):
        """What the check reads of the reference, once a seed: its loss and
        parts, its gradient's norm and its first update's norm on ``data``, and
        the key and value projections' gradients on ``early`` (the targets
        of the first blocks alone)."""
        if self.seed not in self.reference_cache:
            loss, parts, grad = self._reference(data)
            numbers = (loss, parts, reference.shards.norm(grad),
                       reference.adamw_first_update_norm(
                           self._params, grad, self.adamw["lr"],
                           self.adamw["weight_decay"], self.adamw["eps"]))
            del grad
            self.reference_cache.clear()
            self.reference_cache[self.seed] = (*numbers, jax.device_get(
                _kv_leaves(self._reference(early)[2])))
        return self.reference_cache[self.seed]

    def check(self):
        """As ``gpt_moe_dp``'s, **at the timed step's own shapes** (a
        sequence of ``check.seq_len`` = 8,192 data tokens, 16,384 rows: the
        kernels' grids are the timed window's, 80 of 256 tiles), the sample
        noised from the seed as a timed batch is, the reference given the
        same share of the experts; then both once more with the targets of
        each sequence's first ``MASK_ROW_BLOCKS`` blocks alone, for the
        leak's row. The experts' row counts are kept for the load metric
        and for the pairs the held experts multiply."""
        k = self.config["check"]
        data = _noised(np.random.default_rng(self.seed + 1),
                       self.chips * k["sequences_per_chip"], k["seq_len"],
                       self.block, self.eps, self.mask_id)
        tokens, targets, positions, weights = data
        # The early targets' weights scaled by what the sequence is longer
        # than they: the load-balance term's gradient is all 2 L rows' and
        # no target's, and would otherwise drown 32 targets' of 8,192.
        first = MASK_ROW_BLOCKS * self.block
        early = (tokens, np.where(np.arange(k["seq_len"]) < first, targets,
                                  np.int32(-1)),
                 positions, weights * np.float32(k["seq_len"] / first))
        ref_loss, ref, ref_gnorm, ref_moved, ref_kv_early = \
            self._references(data, early)
        *numbers, counts, _, routers = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data))
        loss, load_balance, gnorm, moved = map(float, numbers)
        routers_off = _routers_off(self._params, *routers)
        del routers
        kv_early = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(early))[-2]
        along_early = _along(ref_kv_early, jax.device_get(kv_early))
        self.expert_counts = np.asarray(counts)
        # A lower bound on the sample's row-expert choices that differ from
        # the reference's, as ``gpt_moe_dp`` reckons it.
        self.choices_moved = int(np.abs(
            self.expert_counts - np.asarray(ref["counts"])).sum() // 2)
        first, held = self.cfg.first_expert, self.cfg.experts_held
        self._grouped_matmul_cost(
            float(self.expert_counts[:, first:first + held].sum())
            / float(self.expert_counts.sum()))
        rows = [("loss", loss, ref_loss, LOSS_RTOL),
                ("load-balance term", load_balance, ref["load_balance"],
                 LOAD_BALANCE_RTOL),
                ("gradient norm after the exchange", gnorm, ref_gnorm,
                 GNORM_RTOL),
                ("update norm", moved, ref_moved, UPDATE_RTOL),
                ("key and value gradients along the reference's, the "
                 f"targets of the first {MASK_ROW_BLOCKS} blocks alone",
                 along_early, 1.0, MASK_RTOL),
                ("routers' outputs off the reference's on the same "
                 "activations", 1.0 + routers_off, 1.0, ROUTER_RTOL)]
        return lambda: rows
