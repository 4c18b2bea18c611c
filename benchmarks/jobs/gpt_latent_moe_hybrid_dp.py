"""The user's side of a data-parallel training job on a decoder whose blocks
are one sublayer each, by a pattern of ``M`` (a Mamba-2 mixer whose gated
norm runs a group at a time), ``*`` (grouped-query attention without a
position embedding) and ``E`` (LatentMoE: a sigmoid router on the stream
under a selection bias, un-gated squared-ReLU experts in a latent narrower
than the stream, of which this rank holds its share, beside a shared expert
on the stream) (``model_type: nemotron_h``,
NVIDIA-Nemotron-3-Super-120B-A12B), as ``gpt_mla_moe_dp`` is for
Moonlight's decoder and sharing what is the same: AdamW with float32
moments masked off the selection biases, the biases' update after the
optimizer's from the tokens each expert got over all ranks, the biases of a
checkpoint taken mid-training, random tokens from the seed (drawn from the
rows of the vocabulary held here), state donated to the step. The
configuration file uses the published ``config.json`` key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops, flops_latent_moe, flops_ssm
from benchmarks.jobs import gpt_dp, gpt_window_moe_dp
from benchmarks.reference import gpt_latent_moe_hybrid_dp as reference

# Relative tolerances of the check's four rows, each between the worst
# reading of the sound program and the least a wrong one read on the row
# that is for it (my chip runs, PR 55, ``scripts/check_sweep.py`` at the
# cell's own size, one 8,192-token sequence; PERF.md, Findings, PR 55, has
# every reading). Sound, nineteen seeds: loss 5.0e-5 at most, gradient norm
# 1.8e-4, update norm 4.5e-5, choices moved 2.9e-3 to 3.05e-3 of 901,120.
# Wrong, two seeds each: ReLU for its square (gradient norm 0.14, choices
# 4.2e-2), the scale 5 left out (update norm 1.05e-2, choices 9.8e-3), the
# weights not renormalised (loss 8.8e-4, gradient norm 3.6e-2, choices
# 9.1e-2), the experts fed the stream's first columns for the
# down-projection (gradient norm 1.8e-3, update norm 1.6e-2, choices
# 9.4e-3), parameters held in bfloat16 (update norm 1.95). The loss has the
# share cells' limit, twelve times its worst reading.
LOSS_RTOL = 6e-4
GNORM_RTOL = 7e-4
UPDATE_RTOL = 5e-4
CHOICES_RTOL = 5.5e-3

BLOCKS = {"M": gpt.LayerSpec(mixer="ssm", ff=None),
          "*": gpt.LayerSpec(mixer="attention", rope=False, ff=None),
          "E": gpt.LayerSpec(mixer=None, ff="experts")}


class _StepKeepingCounts:
    """The jitted step as the loop calls it, ``step(*state, batch) ->
    (*state, loss)``, with the last thing the program's step returns, the
    tokens each expert got in that step ``[expert blocks, router]``, kept
    on the device and not handed to the loop: ``moe_held_pairs_pct`` reads
    the newest after the run. Everything else (``lower``, ``_cache_size``,
    ``__name__``) is the jitted function's own."""

    def __init__(self, jitted):
        self._jitted = jitted
        self.last_counts = None

    def __call__(self, *args):
        *out, self.last_counts = self._jitted(*args)
        return tuple(out)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


class Job(gpt_window_moe_dp.Job):
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
        pattern = c["hybrid_override_pattern"]
        if len(pattern) != c["num_hidden_layers"] or set(pattern) - set(BLOCKS):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} must name one of "
                f"{tuple(BLOCKS)} for each of the {c['num_hidden_layers']} "
                "blocks")
        for key in ("n_group", "topk_group"):
            if c[key] > 1:
                raise ValueError(
                    f"{key}={c[key]}: this job's router chooses over one "
                    "group, a plain top-k over all its scores; a choice "
                    "limited to groups is not implemented")
        if c["mlp_hidden_act"] != "relu2" or c["mamba_hidden_act"] != "silu" \
                or c["attention_bias"] or c["mlp_bias"] or c["use_bias"] \
                or c["mamba_proj_bias"] or not c["use_conv_bias"] \
                or c["tie_word_embeddings"] or c["n_shared_experts"] != 1 \
                or not c["norm_topk_prob"] or c["sliding_window"] \
                or c["mamba_num_heads"] % c["n_groups"]:
            raise ValueError(
                "this job runs un-gated squared-ReLU experts and one shared "
                "expert, SiLU in the Mamba mixer, a convolution bias and no "
                "other, an untied head, weights renormalised over the "
                "chosen, full attention, whole groups of Mamba heads")
        share = c["expert_parallel"]
        held, router = c["n_routed_experts"], c["published"]["n_routed_experts"]
        if held * share["chips"] != router:
            raise ValueError(
                f"{share['chips']} chips of {held} experts are not the "
                f"published {router}")
        self.samples_per_step = self.batch * self.seq
        self.pattern = pattern
        self.dense_layers = 0
        self.bias_rate = c["optimizer"]["router_bias_update_rate"]
        first = share["rank"] * held
        # What the reference is told of the model, from the published keys
        # and not from the program's own configuration below; the widths it
        # reads off the matrices.
        self.reference_model = dict(
            top_k=c["num_experts_per_tok"],
            route_scale=float(c["routed_scaling_factor"]),
            first_expert=first, ssm_state=c["ssm_state_size"],
            norm_eps=c["layer_norm_epsilon"])
        self.ssm = dict(heads=c["mamba_num_heads"],
                        head_dim=c["mamba_head_dim"],
                        state=c["ssm_state_size"], groups=c["n_groups"],
                        chunk=c["chunk_size"])
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=len(pattern),
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["intermediate_size"],
            expert_dim=c["moe_intermediate_size"],
            moe_latent_dim=c["moe_latent_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"],
            layers=tuple(BLOCKS[kind] for kind in pattern),
            ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
            ssm_state=c["ssm_state_size"], ssm_groups=c["n_groups"],
            ssm_conv=c["conv_kernel"], ssm_chunk=c["chunk_size"],
            num_experts=router, experts_per_token=c["num_experts_per_tok"],
            experts_held=held, first_expert=first,
            renormalize_experts=c["norm_topk_prob"],
            shared_expert_dim=c["n_shared_experts"]
            * c["moe_shared_expert_intermediate_size"],
            shared_expert_gate=False, expert_activation=c["mlp_hidden_act"],
            router_score="sigmoid", router_bias=True,
            route_scale=float(c["routed_scaling_factor"]),
            norm_eps=c["layer_norm_epsilon"])
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        # AdamW over the parameters; the selection biases are state and the
        # optimizer is masked off them (its decay would move them).
        self.opt = hvd.DistributedOptimizer(optax.masked(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]),
            gpt.trainable))
        shape = dict(heads=self.cfg.num_heads, kv_heads=self.cfg.kv_heads,
                     head_dim=self.cfg.head_dim)
        # Forward and backward for one token, recomputation not counted; of
        # a token's 22 experts the share an even routing sends to the 8
        # held here.
        self.flops_per_sample = flops_latent_moe.latent_moe_hybrid_train_flops(
            self.seq, pattern, self.cfg.embed_dim, vocab=self.cfg.vocab_size,
            ssm=self.ssm, experts=dict(
                latent=self.cfg.moe_latent_dim, router=router,
                width=self.cfg.expert_width,
                top_k=self.cfg.experts_per_token, held=held,
                shared_width=self.cfg.shared_expert_dim), **shape)
        # What one step asks of its kernels on one chip. A checkpointed
        # block keeps the flash kernel's output and log-sum-exp and the
        # scan's output (``gpt.SAVED_NAMES``), so the algorithm's share is
        # one forward and one backward an attention block and, a Mamba
        # block, a forward pass of the scan and two for the backward.
        per_chip = self.batch // self.chips
        self.per_chip_tokens = per_chip * self.seq
        attention = pattern.count("*")
        fwd = flops.flash_forward_cost(per_chip, self.seq, **shape)
        bwd = flops.flash_backward_cost(per_chip, self.seq, **shape)
        scan = flops_ssm.scan_pass_cost(self.per_chip_tokens, **self.ssm)
        passes = 3 * pattern.count("M")
        self.kernel_costs = {
            "flash": {
                "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
                "ops": attention * (fwd["ops"] + bwd["ops"]),
                "bytes": attention * (fwd["bytes"] + bwd["bytes"])},
            "ssm_scan": {
                "match": r"^hvd_ssd_",
                "ops": passes * scan["ops"],
                "bytes": passes * scan["bytes"]}}
        # Until the check has counted the held experts' rows: an even
        # routing's share of the T k.
        self._grouped_matmul_cost(held / router)
        self.step = _StepKeepingCounts(hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1)))
        self.first_call_s = None
        self.check_step = hvd.run_step(
            self._checked_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)
        # Tokens per expert on the check's sample, [expert blocks, router].
        self.expert_counts = None

    def _grouped_matmul_cost(self, held_share: float) -> None:
        """The grouped matmuls' least cost a step: three passes an expert
        block (forward, and two for the backward) over the rows the held
        experts really multiply, ``held_share`` of the ``T k`` token-expert
        pairs, through two matrices at the latent's width."""
        rows = int(self.per_chip_tokens * self.cfg.experts_per_token
                   * held_share)
        one = flops_latent_moe.grouped_matmul_pass_cost(
            rows, latent=self.cfg.moe_latent_dim,
            width=self.cfg.expert_width, experts=self.cfg.experts_held)
        passes = 3 * self.pattern.count("E")
        self.kernel_costs["grouped_matmul"] = {
            "match": r"^ragged-dot-",
            "ops": passes * one["ops"], "bytes": passes * one["bytes"]}

    def init_params(self, key):
        """The model's parameters from the seed as a checkpoint taken
        mid-training holds them: selection biases that are not all alike
        (``gpt_window_moe_dp``'s: normal with deviation 0.01, so that the
        check sees them) and an embedding of deviation
        ``embedding_deviation`` (the configuration's, under ``assumed``
        there) where ``models/gpt.py`` makes 0.02, as the other share cells
        have it since PR 49: a trained model's stream is its tokens' own
        content, and a router fed a stream that is mostly what the mixers
        add to every token of a sequence alike sends a batch's tokens where
        the sequence leans."""
        params = super().init_params(key)
        return {**params, "embed": params["embed"]
                * (self.config["embedding_deviation"] / 0.02)}

    def _train_step(self, params, opt_state, data):
        """The timed step; its last output is the step's tokens per expert
        over all ranks (``_StepKeepingCounts`` keeps it off the loop)."""
        out, aux = self._step_with_aux(params, opt_state, data)
        return (*out, aux["counts"])

    def held_pairs_pct(self):
        """100 x the token-expert pairs of the newest step that fell on the
        experts held here over all its pairs, the mean over the expert
        blocks (``100 held / router`` under an even router); None before a
        step has run."""
        if self.step.last_counts is None:
            return None
        counts = np.asarray(self.step.last_counts, np.float64)
        first, held = self.cfg.first_expert, self.cfg.experts_held
        return float(np.mean(100.0 * counts[:, first:first + held].sum(-1)
                             / counts.sum(-1)))

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, the norm of the gradient as the optimizer received it from the
        exchange (AdamW's first moment after its first step is ``1 - b1``
        times that gradient), the norm of what the step added to the
        parameters (the biases' update apart) and the tokens each expert
        got."""
        (new_params, new_opt, loss), aux = self._step_with_aux(
            params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        for layer in moved["layers"]:
            if "moe" in layer:
                layer["moe"].pop("router_bias")
        return (loss,
                optax.global_norm(new_opt.inner_state[0].mu)
                / (1 - self.adamw["b1"]),
                optax.global_norm(moved), aux["counts"])

    def check(self):
        """As ``gpt_mla_moe_dp``'s without the rows of its attention and its
        probe: the reference given the same share of the experts, heads and
        vocabulary and the same biases; the experts' token counts are kept
        for the load metric and for the rows the held experts multiply."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = gpt_dp._batch(np.random.default_rng(self.seed + 1), shape,
                             self.cfg.vocab_size)
        per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_counts, grad = reference.loss_and_grad(
                self._params, *(x.reshape(per_shard) for x in data[:2]),
                **self.reference_model)
        ref_gnorm = reference.shards.norm(grad)
        ref_moved = reference.adamw_first_update_norm(
            self._params, grad, self.adamw["lr"],
            self.adamw["weight_decay"], self.adamw["eps"])
        del grad
        *numbers, counts = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data))
        loss, gnorm, moved = map(float, numbers)
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
                 CHOICES_RTOL)]
        return lambda: rows
