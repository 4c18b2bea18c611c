"""The user's side of a data-parallel training job on a decoder whose every
block routes its experts from the block's own input, before attention runs
(a linear router on the un-normed stream), through ReLU-gated experts of
which this rank holds its share, under one full attention layer without a
position embedding and three sliding-window layers with the rotary one a
period (``smallthinker``: SmallThinker-21BA3B-Instruct), as ``gpt_moe_dp``
is for the sparse decoder and ``gpt_window_moe_dp`` for Trinity's, sharing
what is the same: AdamW with float32 moments, random tokens from the seed
(drawn from the rows of the vocabulary held here), the step and its loss
(``gpt_moe_dp.Job``), the band row's leaves, the routers' row and the
grouped matmuls' cost (``gpt_window_moe_dp``), state donated to the step.
The configuration file uses the published ``config.json`` key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops_window
from benchmarks.jobs import gpt_dp, gpt_moe_dp, gpt_window_moe_dp
from benchmarks.jobs.gpt_window_moe_dp import _routers_off, _window_kv_leaves
from benchmarks.reference import gpt_prerouted_moe_dp as reference

# bfloat16 program (the early routers' float32 products, the flash kernels
# under the causal mask and under the band at a group of 7, the sorted grouped
# ReLU-gated expert layer over this rank's 16 experts in windows of the sort's
# order, full recomputation) against the float32 reference (S x S logits in
# blocks of 256 query rows, every held expert on every token) on a seeded
# checkpoint (the embedding of deviation 1: ``Job.init_params``), through the
# timed step's own function **at the timed step's own shape: one
# 16,384-token sequence a chip**, so that what is held to the reference on
# the chip is what the window times: the flash kernels at 16,384 rows and
# 28:4 heads, the 4096 band at 70 of 136 tiles, the one backward kernel at
# its deepest shape, the head over 16,384 x 37,984, the barrier on the
# block's input in a step with AdamW. The reference runs first, before the
# optimizer state exists (``gpt_dp.Job._opt_state``): beside 2.45 GiB of
# parameters it takes 2.45 GiB of gradient and 6.8 GiB of temporaries, and
# the check's program then 7.34 GiB of state and 3.98 of temporaries (AOT,
# sandbox, PR 53; the chip has 15.75). The loss is over the targets past the
# first window alone, as ``gpt_window_moe_dp``'s: 12,288 of them. On the
# chip, over 28 seeds of the shipped program (my chip runs, PR 53: 8 of
# ``scripts/check_sweep.py --workload smallthinker-21b-a3b_s16384``, 8 runs of
# the cell, and the 12 runs of the two controls in the precision below on
# the rows they cannot move), and 6 seeds of each program with a mechanism
# left out or another in its place (``--variant``; 3 of the 6 under these
# very bounds, ``correct`` false on every one of the 21):
#
# * loss off by 1.0e-6 to 2.6e-5 (a mean of 12,288 token losses near
#   ln(37984) + 0.5; the 28 readings lie as a half-normal of deviation
#   1.3e-5). The precision hardly moves it and no control is over it on
#   every seed: the six weights not renormalised read 2.9e-5 to 2.8e-4 (over
#   the bound on 5 seeds of 6), SiLU for ReLU 3.0e-5 to 2.2e-4 (2 of 6), the
#   router fed elsewhere 5.6e-5 at most. The bound is 2.7 times the largest
#   sound reading, a ninth of the other share cells' 6e-4 that nothing read
#   here could fail; no control is claimed for this row.
# * gradient norm after the exchange 2.1e-6 to 5.9e-5; the rotary embedding
#   on the full layer 4.2e-4 to 5.5e-4, the router fed what the experts read
#   1.1e-3 to 2.0e-3, the band ignored 5.0e-3 to 8.3e-3, SiLU for ReLU 3.0e-2,
#   the six weights not renormalised 9.8e-2 to 1.0e-1. The bound lies between
#   the worst shipped reading and the least faulty one, in the middle on a
#   logarithmic scale: 2.6 times the one, 2.8 under the other.
# * update norm 1.8e-7 to 4.0e-6 (22 seeds) at the cell's 3e-6 (AdamW's first
#   step is lr times the gradient's sign; the tiny twin reads 1.4e-4 on the
#   CPU);
#   **3.58 with the parameters held in bfloat16** (an update of 3e-6 is lost
#   in a bfloat16 parameter's last bit and what is left is the rounding
#   itself), 1.0 for a state left unchanged; the mechanisms left out read
#   4.3e-3 at most. The precision below is caught here and by the routers'
#   row; the bound lies between the reading and 1 with the more room above
#   the reading.
# * choices shared with the reference: of the sample's 4 x 6 x 16384
#   token-expert choices, those the per-expert counts cannot tell from the
#   reference's (``choices_moved`` as ``gpt_moe_dp`` reckons it, a lower
#   bound): 336 to 413 moved, 8.5e-4 to 1.05e-3 (a token's 6th and 7th
#   outputs swap where they lie within what four bfloat16 layers move them);
#   the rotary embedding on the full layer 2.1e-3 to 2.4e-3, SiLU 2.7e-3 to
#   3.1e-3, not renormalised 4.1e-3 to 4.6e-3, the band ignored 4.3e-3 to
#   5.1e-3, **the router fed N2(a) where the experts read 1.9e-2 to 2.2e-2**
#   (the model's own placement against the usual one; the routers' row cannot
#   see it, the product being right for the operand it was given). The bound
#   lies between the worst shipped reading and the least faulty one, in the
#   middle on a logarithmic scale, 1.4 times the one and 1.4 under the other:
#   the 28 sound readings have a deviation of 7% of their mean, 9.4e-4, and
#   the bound lies nine of them above it.
# * the band row (``gpt_window_moe_dp``'s): the gradient of the window
#   layers' key and value projections along the reference's as a share of
#   the reference's own length: off by 2.9e-5 to 1.3e-3 as shipped, by
#   **1.4e-1 to 2.3e-1 with the band ignored** (three quarters of the
#   targets' queries lose keys to the band at 16,384 tokens; 3.7e-2 to 5.8e-2
#   when the check ran at 8192). The other mechanisms left out turn these
#   gradients too, by 6.8e-4 to 1.5e-2 (not renormalised is over the bound
#   on 2 seeds of 6). The bound lies between the worst
#   shipped reading and the band ignored, in the middle on a logarithmic
#   scale: it is the band's row and no other control is claimed for it.
# * the routers' row (``gpt_window_moe_dp``'s): every block's float32 router
#   outputs against the reference's product on the stream the block's router
#   read (``GPTConfig.router_probe``; the probe is the block's input in the
#   stream's own type): 1.42e-6 to 1.91e-6 on 46 seeds, the mechanisms left
#   out among them (another order of a float32 sum); **8.6e-3 to 1.2e-2 with
#   the product in one bfloat16 pass** (``--variant router_early_bf16``, the
#   control in the precision below, which reads as shipped on the five other
#   rows: unbiased noise, PERF.md, Open questions) and with bfloat16
#   parameters. **Before ``models/gpt.py::_block`` put a barrier on the
#   block's input the shipped program read 9.6e-3 to 2.0e-2 here on every one
#   of 11 seeds** (at 8192 tokens): XLA gave the forward router of two layers
#   another copy of the stream than the one the checkpoint keeps, and the
#   recomputed router, which reads the kept one, chose other experts for 106
#   and 164 of 8192 tokens (PERF.md, Findings, PR 53). The row is what found
#   it. In the 16,384-token step with AdamW, behind the barrier, a tap on
#   both passes' outputs reads no token of 16,384 x 4 whose six differ (2
#   seeds, my chip run, PR 53).
#
# What each row reads on a program with one mechanism left out, against the
# untouched reference (6 seeds each, the largest; in the rows' order: loss,
# gradient norm, update norm, choices, band, routers); in brackets the rows
# whose **least** reading of the six is over the bound:
#   router fed N2(a)           5.6e-5 [2.0e-3] 6.5e-6 [2.2e-2]  5.7e-3   1.9e-6
#   SiLU for ReLU              2.2e-4 [3.0e-2] 4.8e-4 [3.1e-3]  5.5e-3   1.9e-6
#   rotary on the full layer   4.0e-5 [5.5e-4] 5.9e-5 [2.4e-3]  4.3e-3   1.9e-6
#   band ignored               5.7e-5 [8.3e-3] 8.9e-4 [5.1e-3] [2.3e-1]  1.9e-6
#   weights not renormalised   2.8e-4 [1.0e-1] 4.3e-3 [4.6e-3]  1.5e-2   1.9e-6
#   router product in one pass 2.3e-5  5.0e-5  2.1e-6  1.1e-3   1.2e-3 [1.2e-2]
#   bfloat16 parameters        2.3e-5  4.2e-5  [3.58]  1.1e-3   1.3e-3 [1.2e-2]
LOSS_RTOL = 7e-5
GNORM_RTOL = 1.5e-4
UPDATE_RTOL = 5e-3
CHOICES_RTOL = 1.5e-3
BAND_RTOL = 1.3e-2
ROUTER_RTOL = 1e-4


class Job(gpt_moe_dp.Job):
    dense_layers = 0            # every block's feed-forward is experts
    _grouped_matmul_cost = gpt_window_moe_dp.Job._grouped_matmul_cost

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
        c = config
        share = c["expert_parallel"]
        held = c["moe_num_primary_experts"]
        router = c["published"]["moe_num_primary_experts"]
        if held * share["chips"] != router:
            raise ValueError(
                f"{share['chips']} chips of {held} experts are not the "
                f"published {router}")
        layers = c["num_hidden_layers"]
        if len(c["rope_layout"]) != layers \
                or len(c["sliding_window_layout"]) != layers \
                or set(c["rope_layout"] + c["sliding_window_layout"]) - {0, 1} \
                or not c["moe_primary_router_apply_softmax"] \
                or not c["norm_topk_prob"] or c["tie_word_embeddings"] \
                or c["rope_scaling"]:
            raise ValueError(
                "this job runs a layer's rotary embedding and window by two "
                "layouts of 0 and 1, a softmax router renormalised over the "
                "chosen, an untied head, an unscaled rotary embedding")
        self.samples_per_step = self.batch * self.seq
        self.windows = tuple(c["sliding_window_size"] if flag else None
                             for flag in c["sliding_window_layout"])
        ropes = tuple(bool(flag) for flag in c["rope_layout"])
        # What the reference is told of the model, from the published keys
        # and not from the program's own configuration below.
        self.reference_model = dict(
            windows=self.windows, ropes=ropes,
            top_k=c["moe_num_active_primary_experts"],
            first_expert=share["rank"] * held,
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=layers,
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["moe_ffn_hidden_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"],
            layers=tuple(gpt.LayerSpec(mixer="attention", window=window,
                                       rope=rope, ff="experts")
                         for window, rope in zip(self.windows, ropes)),
            num_experts=router,
            experts_per_token=c["moe_num_active_primary_experts"],
            experts_held=held, first_expert=share["rank"] * held,
            renormalize_experts=c["norm_topk_prob"],
            router_reads="block_input", expert_activation="relu",
            router_probe=True, norm_eps=c["rms_norm_eps"],
            rope_theta=float(c["rope_theta"]))
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        self.opt = hvd.DistributedOptimizer(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]))
        shape = dict(heads=self.cfg.num_heads, kv_heads=self.cfg.kv_heads,
                     head_dim=self.cfg.head_dim)
        # Forward and backward for one token, recomputation not counted:
        # each layer's projections and the pairs its band keeps, its router
        # and a token's six experts as far as an even routing sends them to
        # the experts held here, the head over the rows held.
        embed = self.cfg.embed_dim
        self.flops_per_sample = 3 * (
            sum(flops_window.layer_forward_flops(
                self.seq, embed, window=window, gate=False, **shape)
                for window in self.windows)
            + layers * (2 * embed * router
                        + 6 * embed * self.cfg.expert_width
                        * self.cfg.experts_per_token * held // router)
            + 2 * embed * self.cfg.vocab_size)
        # What one step asks of its kernels on one chip, as the step makes
        # them: a checkpointed block keeps the flash kernel's output and
        # log-sum-exp (``gpt.SAVED_NAMES``), so one forward and one (fused)
        # backward a layer, the band's pairs for a window layer, the
        # triangle's for a full one.
        per_chip = self.batch // self.chips
        self.per_chip_tokens = per_chip * self.seq

        def flash_cost(windows) -> dict:
            parts = [cost(per_chip, self.seq, window=w, **shape)
                     for w in windows
                     for cost in (flops_window.flash_forward_cost,
                                  flops_window.flash_backward_cost)]
            return {key: sum(p[key] for p in parts)
                    for key in ("ops", "bytes")}

        self.kernel_costs = {"flash": {
            "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
            **flash_cost(self.windows)}}
        # The window layers' alone, for ``flash_window_roofline_pct``.
        self.window_flash_cost = flash_cost(
            [w for w in self.windows if w is not None])
        # Until the check has counted the held experts' rows: an even
        # routing's share of the T k.
        self._grouped_matmul_cost(held / router)
        self.step = hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1))
        self.first_call_s = None
        self.check_step = hvd.run_step(
            self._checked_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)
        # Tokens per expert on the check's sample, [layers, router].
        self.expert_counts = None

    def init_params(self, key):
        """The model's parameters from the seed with **an embedding of
        deviation** ``embedding_deviation`` (the configuration's; under
        ``assumed`` there) where ``models/gpt.py`` makes 0.02: every router
        here reads the un-normed stream, so the embedding's scale is the
        scale of layer 0's router outputs (``r = x W_r`` has the deviation
        of ``x``'s elements under a matrix of variance ``1 / d``) and of
        every later layer's as far as the stream is still the token's own
        row."""
        params = gpt.init_params(key, self.cfg)
        return {**params, "embed": params["embed"]
                * (self.config["embedding_deviation"] / 0.02)}

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, the norm of the gradient as the optimizer received it from the
        exchange (AdamW's first moment after its first step is ``1 - b1``
        times that gradient), the norm of what the step added to the
        parameters, the tokens each expert got, the gradient of the window
        layers' key and value projections itself (6 x 5 MB), and what each
        block's router read and gave (84 and 4 MB a layer at 16,384 tokens)."""
        (new_params, new_opt, loss), aux = self._step_with_aux(
            params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        mu = new_opt[0].mu
        scale = 1 - self.adamw["b1"]
        return (loss,
                optax.global_norm(mu) / scale,
                optax.global_norm(moved),
                hvd.allreduce(aux["counts"], op=hvd.Sum),
                [leaf / scale for leaf in _window_kv_leaves(mu,
                                                            self.windows)],
                # every rank's tokens, [T, layers, .]
                [hvd.allgather(jnp.swapaxes(aux[key], 0, 1))
                 for key in ("router_inputs", "router_logits")])

    def check(self):
        """As ``gpt_window_moe_dp``'s without the biases: the reference
        given the same share of the experts; the experts' token counts are
        kept for the load metric and for the rows the held experts
        multiply."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = gpt_dp._batch(np.random.default_rng(self.seed + 1), shape,
                             self.cfg.vocab_size)
        # The loss is over the targets past the first window alone: there
        # every query's band is whole and shuts keys out, where the early
        # queries, whose few keys weigh most in a gradient's norm, see the
        # same keys under the band and without it.
        window = max((w for w in self.windows if w is not None), default=0)
        if window < k["seq_len"]:
            data[1][:, :window] = -1
        per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_counts, grad = reference.loss_and_grad(
                self._params, *(x.reshape(per_shard) for x in data),
                **self.reference_model)
        ref_gnorm = reference.shards.norm(grad)
        ref_band = _window_kv_leaves(grad, self.windows)
        ref_moved = reference.adamw_first_update_norm(
            self._params, grad, self.adamw["lr"],
            self.adamw["weight_decay"], self.adamw["eps"])
        del grad
        *numbers, counts, band, routers = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data))
        loss, gnorm, moved = map(float, numbers)
        routers_off = _routers_off(self._params, *routers)
        del routers
        # The program's gradient along the reference's, as a share of the
        # reference's own length: 1 where they agree (a norm would not see a
        # turn of the gradient at all).
        along = sum(float(jnp.vdot(r, g)) for r, g in zip(ref_band, band)) \
            / sum(float(jnp.vdot(r, r)) for r in ref_band)
        del ref_band, band
        self.expert_counts = np.asarray(counts)
        # A lower bound on the sample's token-expert choices that differ
        # from the reference's, as ``gpt_moe_dp`` reckons it.
        self.choices_moved = int(np.abs(
            self.expert_counts - np.asarray(ref_counts)).sum() // 2)
        choices = int(self.expert_counts.sum())
        first, held = self.cfg.first_expert, self.cfg.experts_held
        self._grouped_matmul_cost(
            float(self.expert_counts[:, first:first + held].sum()) / choices)
        rows = [("loss", loss, ref_loss, LOSS_RTOL),
                ("gradient norm after the exchange", gnorm, ref_gnorm,
                 GNORM_RTOL),
                ("update norm", moved, ref_moved, UPDATE_RTOL),
                ("token-expert choices shared with the reference",
                 float(choices - self.choices_moved), float(choices),
                 CHOICES_RTOL),
                ("window layers' key and value gradients along the "
                 "reference's", along, 1.0, BAND_RTOL),
                ("routers' outputs off the reference's on the same "
                 "activations", 1.0 + routers_off, 1.0, ROUTER_RTOL)]
        return lambda: rows
