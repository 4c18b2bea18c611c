"""The user's side of a data-parallel training job on a decoder that mixes
Kimi-delta-attention layers (KDA: a delta-rule state that decays a key
channel, from a gate bounded below) with gated latent-attention layers
(MLA), one in ``layer_group_size``, over leading dense feed-forwards and then
expert blocks whose sigmoid router, under a selection bias, chooses inside
the best ``topk_group`` of ``n_group`` groups of experts, of which this rank
holds its share (``model_type: bailing_hybrid``, Ling-3.0-flash), as
``gpt_mla_moe_dp`` is for Moonlight's decoder and sharing what is the same:
AdamW with float32 moments masked off the selection biases, the biases'
update after the optimizer's, the biases of a checkpoint taken mid-training,
random tokens from the rows of the vocabulary held here, state donated to
the step. The configuration file uses the published ``config.json`` key
names; ``held_layers`` says which published layers the stack holds, and a
layer's kind and swiglu limit are read at its published index.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops_kda, flops_mla
from benchmarks.jobs import gpt_dp, gpt_latent_moe_hybrid_dp, \
    gpt_mla_moe_dp, gpt_window_moe_dp
from benchmarks.jobs.gpt_latent_moe_hybrid_dp import _StepKeepingCounts
from benchmarks.jobs.gpt_window_moe_dp import _bias_step_on_load, _routers_off
from benchmarks.reference import gpt_kda_mla_moe_dp as reference

# bfloat16 program (the KDA layers' projections, convolution kernels, the
# four ``hvd_kda_*`` kernels in chunks of 64 by sub-blocks of 16, the gated
# norm; the MLA layer's projections, rotary embedding, flash kernels at 192
# beside 128 and head-wise gate; the grouped choice and the sorted grouped
# expert layer over this rank's 8 experts; full recomputation) against the
# float32 reference (KDA one token a step, S x S logits, the grouped choice
# written plainly, every held expert on every token) on a seeded checkpoint
# (the biases normal with deviation 0.01, the embedding of deviation 1),
# **the timed step's own 8,192-token sequence** (128 chunks a scan, 8,192
# keys, 8,192 x 512 grouped choices a layer), through the timed step's own
# function. On the chip (my chip runs, PR 63, ``scripts/check_sweep.py
# --workload ling-3.0-flash_s8192``: nine seeds of the shipped program and
# two seeds of each control, ``--variants``, all at 8,192 tokens; the first
# sweeps ran a 2,048-token sequence, 21 seeds, and read the gradient norm
# 3.5e-5 to 5.8e-4 and the choices 1.09e-2 to 1.20e-2: PERF.md, Findings,
# PR 63, has both sizes):
#
# * loss off by 9.2e-8 to 2.2e-5 (a mean of 8191 token losses near
#   ln(19648) + 0.5). The precision hardly moves it: its bound is the other
#   share cells', 27 times the largest seen; the head-wise gates left out
#   read 2.1e-4 to 1.5e-3.
# * gradient norm after the exchange 1.01e-3 to 1.42e-3, the program's the
#   longer on every seed (rounding that is not biased lengthens a gradient:
#   at 8,192 tokens the mean gradient is half as long as at 2,048 and the
#   rounding's part of it as long); **the scan's decays, running sums and
#   state in bfloat16 1.38e-2 to 1.62e-2**, the unbounded gate 7.4e-2 to
#   7.5e-2, one decay a head 8.1e-2 to 8.4e-2, the gates left out 0.47 to
#   0.48. The bound is 3.1 times the worst shipped-like reading (1.46e-3,
#   the router's control, whose gradient is the shipped one) and 3.1 times
#   under the least control.
# * update norm 2.3e-5 to 4.4e-5 at the cell's 3e-6 (AdamW's first step is
#   lr times the gradient's sign; 2.4e-4 to 3.1e-4 at 3e-7, where float32
#   parameters near one round the step to a multiple of 6e-8); one decay a
#   head 5e-3. The bound is Moonlight's, between the readings and 1 with
#   the room above them that fresh seeds want.
# * choices shared with the reference: of the sample's 5 x 8 x 8192 token-
#   expert choices, those the per-expert counts cannot tell from the
#   reference's (a lower bound, as ``gpt_moe_dp`` reckons it): **6.24e-3 to
#   6.51e-3 moved** (6.59e-3 on a control that leaves the routing alone),
#   twice Moonlight's share: a token's fourth and fifth group swap where
#   their scores lie within what bfloat16 activations move them, and a
#   swapped group takes up to all eight of the token's experts with it.
#   **The group limit left out (a plain top-8 over all 512) reads 2.15e-2
#   to 2.17e-2** and inside every other row's bound on one seed of two (the
#   bias row 2.7e-2 to 3.8e-2): this row is what holds the groups. The
#   scan in bfloat16 1.27e-2, one decay a head 2.7e-2, the unbounded gate
#   3.6e-2 to 4.0e-2, the gates left out 6.0e-2. The bound lies between the
#   worst shipped-like reading and the plain top-8, in the middle on a
#   logarithmic scale (1.8 times either way; thirteen programs that leave
#   the routing alone read within 6% of one another).
# * the latent row (``gpt_mla_moe_dp``'s, of the one MLA layer): off by
#   6.9e-4 to 3.4e-3 as shipped; 0.115 to 0.19 under the three controls
#   that change what the MLA layer reads (a decay a head, the unbounded
#   gate, the gates left out), 4.0e-3 to 8.8e-3 with the plain top-8.
# * **the KDA row**: the gradient of every KDA layer's ``W_f``, ``A_log``,
#   ``dt_bias`` and ``W_beta``, which reach the loss through the scan alone,
#   **along the reference's** as a share of the reference's own length: off
#   by 8.1e-4 to 2.6e-3 as shipped; **0.94 with one decay a head** (the
#   channels' mean: ``W_f``'s gradient is then the same for every channel of
#   a head), **0.92 to 0.93 with the unbounded gate**, 0.49 to 0.51 with the
#   gates left out, 1.5e-2 to 1.6e-2 with the plain top-8. The bound is 7.7
#   times the worst shipped reading and 24 times under the least of the
#   three.
# * the bias row (``gpt_window_moe_dp``'s) 7.2e-5 to 2.9e-3; 0.19 to 0.21
#   with the gates left out, 2.7e-2 to 3.8e-2 with the plain top-8 (other
#   counts). The bound is Moonlight's, twelve times the worst shipped.
# * the routers' row (``gpt_window_moe_dp``'s): 1.4e-6 to 1.9e-6 as shipped
#   (the same float32 product, another order of its sum in the reference's
#   program); **8.9e-3 to 9.5e-3 with the product in one bfloat16 pass**
#   (``--variant router_bf16``), which reads as shipped on the seven other
#   rows.
# * **What ``correct`` holds of the scan's float32** (the configuration's
#   ``departures`` say the same). With the decays, their running sums and
#   the carried state in bfloat16 (``--variant kda_state_bf16``) the
#   gradient norm reads three times its bound and the choices just over
#   theirs. With the decays and the state alone in bfloat16 and the running
#   sums left in float32 (``--variant kda_decays_state_bf16``, listed as a
#   control that **passes**) every row reads as shipped at 128 chunks as at
#   32 (gradient norm 1.19e-3 to 1.46e-3, KDA row 1.8e-3, choices 6.2e-3 to
#   6.6e-3, two seeds): the products round their operands to bfloat16
#   anyway and the state's rounding is not biased, so no norm, count or
#   projection sees it. ``correct`` holds the running sums, up to 320 in
#   size, to float32, and not ``ops/kda.py::_STATE_DTYPE``:
#   ``tests/test_kda.py`` holds the float32 state to the recurrence at 2e-5
#   over 128 chunks.
LOSS_RTOL = 6e-4
GNORM_RTOL = 4.5e-3
UPDATE_RTOL = 1.2e-2
CHOICES_RTOL = 1.2e-2
LATENT_RTOL = 2e-2
KDA_RTOL = 2e-2
BIAS_RTOL = 3.5e-2
ROUTER_RTOL = 1e-4


def _latent_leaves(tree) -> list:
    """The parameters of the MLA layers that reach the loss through the
    keys and values alone (``gpt_mla_moe_dp``'s row)."""
    return [layer["mla"][name] for layer in tree["layers"] if "mla" in layer
            for name in ("wkv_a", "kv_norm", "wkv_b")]


def _kda_leaves(tree) -> list:
    """The parameters of every KDA layer that reach the loss through the
    scan alone: the gate's projection, ``A_log``, ``dt_bias`` and the
    writing strength's projection."""
    return [layer["kda"][name] for layer in tree["layers"] if "kda" in layer
            for name in ("w_f", "A_log", "dt_bias", "w_beta")]


def _along(reference_leaves, leaves) -> float:
    """The program's gradient along the reference's, as a share of the
    reference's own length: 1 where they agree."""
    return sum(float(np.vdot(r, g))
               for r, g in zip(reference_leaves, leaves, strict=True)) \
        / sum(float(np.vdot(r, r)) for r in reference_leaves)


class Job(gpt_mla_moe_dp.Job):
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
        held = c["held_layers"]
        if len(held) != c["num_hidden_layers"]:
            raise ValueError(f"held_layers {held} names no "
                             f"{c['num_hidden_layers']} layers")
        for key in ("q_lora_rank", "rope_scaling"):
            if c.get(key) is not None:
                raise ValueError(
                    f"{key}={c[key]!r}: this job projects the query "
                    "straight from the stream and rotates at the base alone")
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            limits = {i: c[key][i] for i in held if c[key][i]}
            if limits:
                raise ValueError(
                    f"{key} is {limits} at held layers: a clamp on the "
                    "experts' gated products is not implemented")
        if c["hidden_act"] != "silu" or c["score_function"] != "sigmoid" \
                or c["topk_method"] != "noaux_tc" or c["use_bias"] \
                or c["use_qkv_bias"] or c["tie_word_embeddings"] \
                or c["num_key_value_heads"] != c["num_attention_heads"] \
                or c["num_kv_heads_for_linear_attn"] or c["use_kda_lora"] \
                or not c["no_kda_lora"] or not c["kda_safe_gate"] \
                or not c["linear_silu"] or not c["use_qk_norm"] \
                or c["use_mla_nope"] or c["use_nGPT"] or c["value_norm"] \
                or c["up_proj_norm"] or c["scale_router_input"] \
                or c["group_norm_size"] != 1 \
                or not c["moe_router_enable_expert_bias"] \
                or c["gated_attention_proj_granularity_type"] != "head_wise" \
                or c["rotary_dim"] != c["qk_rope_head_dim"]:
            raise ValueError(
                "this job runs SiLU, a sigmoid router under a selection "
                "bias (noaux_tc), no bias, an untied head, as many KDA "
                "key heads as query heads, the gate's projection at full "
                "rank under the safe (bounded) gate, a SiLU after the "
                "convolution, the q/k L2 norm, the rotary part applied, one "
                "norm group a head and head-wise output gates")
        share = c["expert_parallel"]
        router = c["published"]["num_experts"]
        if c["num_experts"] * share["chips"] != router:
            raise ValueError(
                f"{share['chips']} chips of {c['num_experts']} experts are "
                f"not the published {router}")
        self.samples_per_step = self.batch * self.seq
        self.bias_rate = c["optimizer"]["router_bias_update_rate"]
        first = share["rank"] * c["num_experts"]
        # A layer's kind and feed-forward, read at its published index.
        group, dense = c["layer_group_size"], \
            c["published"]["first_k_dense_replace"]
        kinds = ["mla" if (i + 1) % group == 0 else "kda" for i in held]
        plan = tuple(gpt.LayerSpec(
            mixer=kind, ff="gated" if i < dense else "experts", depth=i)
            for i, kind in zip(held, kinds))
        self.dense_layers = sum(spec.ff == "gated" for spec in plan)
        if self.dense_layers != c["first_k_dense_replace"]:
            raise ValueError(
                f"held_layers {held} hold {self.dense_layers} dense layers, "
                f"first_k_dense_replace says {c['first_k_dense_replace']}")
        # What the reference is told of the model, from the published keys
        # and not from the program's own configuration below; the widths it
        # reads off the matrices, a layer's kind off what the layer holds.
        self.reference_model = dict(
            top_k=c["num_experts_per_tok"],
            route_scale=c["routed_scaling_factor"], first_expert=first,
            groups=c["n_group"], kept=c["topk_group"],
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
            lower_bound=float(c["kda_lower_bound"]))
        heads = c["num_attention_heads"]
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=len(plan),
            num_heads=heads, head_dim=c["qk_nope_head_dim"],
            mla_rope_dim=c["qk_rope_head_dim"],
            mla_value_dim=c["v_head_dim"], mla_kv_rank=c["kv_lora_rank"],
            mla_head_gate=True, kda_heads=heads, kda_key_dim=c["head_dim"],
            kda_value_dim=c["head_dim"],
            kda_conv=c["short_conv_kernel_size"], kda_chunk=c["kda_chunk"],
            kda_lower_bound=float(c["kda_lower_bound"]),
            embed_dim=c["hidden_size"], mlp_dim=c["intermediate_size"],
            expert_dim=c["moe_intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"], layers=plan,
            num_experts=router, experts_per_token=c["num_experts_per_tok"],
            experts_held=c["num_experts"], first_expert=first,
            renormalize_experts=c["norm_topk_prob"],
            shared_expert_dim=c["num_shared_experts"]
            * c["moe_shared_expert_intermediate_size"],
            shared_expert_gate=False, router_score=c["score_function"],
            router_bias=True, route_scale=c["routed_scaling_factor"],
            router_groups=c["n_group"], router_groups_kept=c["topk_group"],
            router_probe=True, norm_eps=c["rms_norm_eps"],
            rope_theta=float(c["rope_theta"]))
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        # AdamW over the parameters; the selection biases are state and the
        # optimizer is masked off them (its decay would move them).
        self.opt = hvd.DistributedOptimizer(optax.masked(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]),
            gpt.trainable))
        kda = dict(heads=heads, key_dim=self.cfg.kda_key_dim,
                   value_dim=self.cfg.kda_value_dim, chunk=self.cfg.kda_chunk)
        key_dim = self.cfg.head_dim + self.cfg.mla_rope_dim
        self.flops_per_sample = flops_kda.kda_mla_moe_train_flops(
            self.seq, kinds, self.dense_layers, self.cfg.embed_dim, kda=kda,
            mla=dict(heads=heads, nope_dim=self.cfg.head_dim,
                     rope_dim=self.cfg.mla_rope_dim,
                     value_dim=self.cfg.mla_value_dim,
                     kv_rank=self.cfg.mla_kv_rank),
            mlp=self.cfg.mlp_dim, vocab=self.cfg.vocab_size, experts=dict(
                router=router, width=self.cfg.expert_width,
                top_k=self.cfg.experts_per_token, held=c["num_experts"],
                shared_width=self.cfg.shared_expert_dim))
        # What one step asks of its kernels on one chip: a forward and a
        # backward of the flash kernels an MLA layer (a checkpointed block
        # keeps their output and log-sum-exp), and of the scan a KDA layer a
        # forward pass and, for the backward, two; what recomputation runs
        # again is not the algorithm's.
        per_chip = self.batch // self.chips
        self.per_chip_tokens = per_chip * self.seq
        parts = [cost(per_chip, self.seq, heads, heads, key_dim,
                      self.cfg.mla_value_dim)
                 for cost in (flops_mla.flash_forward_cost,
                              flops_mla.flash_backward_cost)]
        scan = flops_kda.scan_pass_cost(self.per_chip_tokens, **kda)
        passes = 3 * kinds.count("kda")
        self.kernel_costs = {
            "flash": {
                "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
                **{key: kinds.count("mla") * sum(p[key] for p in parts)
                   for key in ("ops", "bytes")}},
            "kda_scan": {
                # ``hvd_kda_fwd``, ``hvd_kda_bwd``, ``hvd_kda_rec_fwd``,
                # ``hvd_kda_rec_bwd``; what XLA lowers of the scan lies
                # under the scope kda/kda_scan
                # (``layer_metrics/kda_scan_ms.py`` reads both).
                "match": r"^hvd_kda_",
                "ops": passes * scan["ops"],
                "bytes": passes * scan["bytes"]}}
        # Until the check has counted the held experts' rows: an even
        # routing's share of the T k.
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
        # Tokens per expert on the check's sample, [expert layers, router].
        self.expert_counts = None
        # seed -> the reference's numbers: a process that checks several
        # programs on one seed's parameters (``scripts/check_sweep.py
        # --variants``) hands its jobs one dict and the reference is
        # computed once.
        self.reference_cache: dict = {}

    def init_params(self, key):
        """The model's parameters from the seed as a checkpoint taken
        mid-training holds them: selection biases of deviation 0.01
        (``gpt_window_moe_dp``'s) and an embedding of the configuration's
        ``embedding_deviation`` (``gpt_mla_moe_dp`` says why: a trained
        model's stream is its tokens' own content)."""
        params = gpt_window_moe_dp.Job.init_params(self, key)
        return {**params, "embed": params["embed"]
                * (self.config["embedding_deviation"] / 0.02)}

    def _train_step(self, params, opt_state, data):
        """The timed step; its last output is the step's tokens per expert
        over all ranks (``_StepKeepingCounts`` keeps it off the loop)."""
        out, aux = self._step_with_aux(params, opt_state, data)
        return (*out, aux["counts"])

    # ``moe_held_pairs_pct``: the newest step's pairs on the held experts.
    held_pairs_pct = gpt_latent_moe_hybrid_dp.Job.held_pairs_pct

    def _checked_step(self, params, opt_state, data):
        """``gpt_mla_moe_dp``'s numbers (the latent's leaves of the MLA
        layers alone) and, after them, the gradient of the KDA layers' gate
        and writing-strength leaves itself (5 x 42 MB)."""
        (new_params, new_opt, loss), aux = self._step_with_aux(
            params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        for layer in moved["layers"]:
            if "moe" in layer:
                layer["moe"].pop("router_bias")
        mu = new_opt.inner_state[0].mu
        scale = 1 - self.adamw["b1"]
        return (loss,
                optax.global_norm(mu) / scale,
                optax.global_norm(moved),
                _bias_step_on_load(reference.biases(params),
                                   reference.biases(new_params),
                                   aux["counts"]),
                aux["counts"],
                [leaf / scale for leaf in _latent_leaves(mu)],
                [leaf / scale for leaf in _kda_leaves(mu)],
                # every rank's tokens, [T, expert layers, .]
                [hvd.allgather(jnp.swapaxes(aux[key], 0, 1))
                 for key in ("router_inputs", "router_logits")])

    def _reference(self, data):
        """The reference's loss, counts, gradient norm, update norm, bias
        row and the two rows' leaves on the check's sample, once a seed."""
        if self.seed not in self.reference_cache:
            k = self.config["check"]
            per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
            with jax.default_matmul_precision("highest"):
                loss, counts, grad = reference.loss_and_grad(
                    self._params, *(x.reshape(per_shard) for x in data),
                    **self.reference_model)
            self.reference_cache.clear()
            self.reference_cache[self.seed] = (
                loss, counts, reference.shards.norm(grad),
                reference.adamw_first_update_norm(
                    self._params, grad, self.adamw["lr"],
                    self.adamw["weight_decay"], self.adamw["eps"]),
                reference.bias_step_on_load(
                    reference.biases(self._params),
                    reference.updated_biases(self._params, counts,
                                             self.bias_rate), counts),
                jax.device_get(_latent_leaves(grad)),
                jax.device_get(_kda_leaves(grad)))
        return self.reference_cache[self.seed]

    def check(self):
        """As ``gpt_mla_moe_dp``'s, the reference given the same share of
        the experts, the same biases and the groups, with a row for the KDA
        layers' gradients beside the latent's."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = gpt_dp._batch(np.random.default_rng(self.seed + 1), shape,
                             self.cfg.vocab_size)
        (ref_loss, ref_counts, ref_gnorm, ref_moved, ref_bias, ref_latent,
         ref_kda) = self._reference(data)
        *numbers, counts, latent, kda, routers = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data))
        loss, gnorm, moved, bias = map(float, numbers)
        routers_off = _routers_off(self._params, *routers)
        del routers
        along_latent = _along(ref_latent, jax.device_get(latent))
        along_kda = _along(ref_kda, jax.device_get(kda))
        del latent, kda
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
                ("latent attention's key and value gradients along the "
                 "reference's", along_latent, 1.0, LATENT_RTOL),
                ("Kimi delta attention's gate and writing-strength "
                 "gradients along the reference's", along_kda, 1.0,
                 KDA_RTOL),
                ("selection biases' update weighed by the experts' load",
                 bias, ref_bias, BIAS_RTOL),
                ("routers' outputs off the reference's on the same "
                 "activations", 1.0 + routers_off, 1.0, ROUTER_RTOL)]
        return lambda: rows
