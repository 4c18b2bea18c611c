"""The user's side of a data-parallel training job on a decoder whose every
layer is a CCA attention sublayer (attention in a compressed latent, q and k
mixed by two stacked causal convolutions) and an expert sublayer with one
expert a token chosen by an MLP router that carries a state from layer to
layer under a selection bias, each sublayer joined to the stream under a
learned scaling, of which this rank holds its share of the experts
(``model_type: zaya``, ZAYA1-8B), as ``gpt_window_moe_dp`` is for Trinity's
decoder and sharing what is the same: AdamW with float32 moments masked off
the selection biases, the biases' update after the optimizer's from the
tokens each expert got over all ranks, random tokens from the seed (drawn
from the rows of the vocabulary held here), state donated to the step. The
configuration file uses the published ``config.json`` key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops, flops_cca
from benchmarks.jobs import gpt_dp, gpt_window_moe_dp
from benchmarks.reference import gpt_cca_moe_dp as reference

# bfloat16 program (the depthwise stage through ``hvd_conv_*``, the grouped
# stage, means, L2 norms and residual scaling in float32 from bfloat16
# inputs, the three flash kernels at 8:2 heads of 128, the MLP router in
# float32 at the highest precision, the sorted grouped expert layer over
# this rank's 8 experts un-windowed, full recomputation with the router's
# state crossing the blocks) against the float32 reference (the convolutions
# as shifted sums, S x S logits, every held expert on every token) on a
# seeded checkpoint (what starts at one or zero moved off it, the selection
# biases settled: ``Job.init_params``), one 1024-token sequence a chip,
# through the timed step's own function. On the chip (my chip runs, PR 46;
# ``scripts/check_sweep.py --workload zaya1-8b_s4096`` and the cell's own
# runs; 15 seeds of the shipped program, and two seeds of each program with
# a mechanism left out or computed in bfloat16, ``--variant``; the seeds
# run after this was written are in PERF.md, Findings, PR 46):
#
# * loss off by 1.0e-4 at most (a mean of 1023 token losses near ln(32784) +
#   0.4); the residual scaling left out reads 3.4e-4 and 2.4e-3. The
#   precision hardly moves it: its bound is the other share cells', six
#   times the largest seen.
# * gradient norm after the exchange 6.9e-4 at most; the rotary embedding on
#   the whole head 4.3e-3, the residual scaling left out 2.7e-2. A wrong
#   scale or a dropped term; the bound lies between.
# * update norm 1.8e-4 to 2.1e-4 at the cell's 3e-7 (7.3e-5 at most at
#   3e-6, 1.8e-3 to 2.0e-3 at 1e-7: AdamW's first step is lr times the
#   gradient's sign, and float32 parameters near one round such a step to
#   a multiple of 6e-8, which is why the cell's rate is not lower; the
#   rehearsal's tiny twin, whose vectors near one are a larger share of
#   its parameters, reads 1.8e-3 at 3e-7 on the CPU). The bound is three
#   times the twin's reading and thirty times the chip's; what it is for, a
#   wrong learning rate, misses it by the factor (1.0 at twice the rate,
#   ``benchmarks/tests/test_faults.py zaya1-8b_s4096 other_rate``).
# * choices shared with the reference: of the sample's 6 x 1024 choices of
#   one expert, those the per-expert counts cannot tell from the
#   reference's (``choices_moved`` as ``gpt_moe_dp`` reckons it, a lower
#   bound): 6.5e-3 moved at most. One expert a token: where the two largest
#   scores under the bias lie within what bfloat16 activations move them the
#   token's whole expert changes. The rotary embedding on the whole head
#   moves 2.6e-2 and 3.1e-2, the residual scaling left out 7.0e-2 and
#   8.4e-2, routers that take no state 0.10 and 0.13. The bound lies between.
# * the bias row (``gpt_window_moe_dp``'s: the update weighed by the load it
#   answers) 1.2e-2 at most, with a long tail (a third of the seeds over
#   3e-3); 0.16 to 0.27 with the residual scaling or the routers' state
#   left out (other counts), 1.0 with the update left out, 1.4 with the
#   bias left out of the choice. The bound lies between, in the middle of
#   the two on a logarithmic scale.
# * the routers' row: every layer's router outputs as the step made them
#   against the reference's router **on the operand the step's own product
#   read and the state the reference's own chain made of the layers
#   before** (``GPTConfig.router_probe``), element by element: the largest
#   difference over the root mean square of the reference's. 1.6e-6 at
#   most as shipped; **2.2e-2 and 2.5e-2 with the MLP's products in one
#   bfloat16 pass** (``--variant router_mlp_bf16``: the control in the precision
#   below the one the configuration states, which no other row sees: it
#   reads as shipped on the six others), 4.7 and 5.2 with no state handed
#   on. The bound lies between, above what another order of a float32 sum
#   reads.
# * the mix row: the norm of the gradient of the convolutions' taps and
#   biases and of the key temperatures, which reach the loss through the mix
#   alone: 3.3e-3 at most; the rotary embedding on the whole head 3.6e-2, the
#   residual scaling left out 0.10; the bound lies between, in the middle
#   on a logarithmic scale. **The mix's means, norms and sums in
#   bfloat16 (``--variant cca_mix_bf16``) read as shipped on every row**
#   (1.3e-3 here): its rounding is not biased, and a norm, a count or a
#   projection cannot see unbiased noise (PERF.md, Open questions).
LOSS_RTOL = 6e-4
GNORM_RTOL = 2e-3
UPDATE_RTOL = 6e-3
CHOICES_RTOL = 1.4e-2
BIAS_RTOL = 4e-2
ROUTER_RTOL = 1e-4
MIX_RTOL = 1e-2


# The rates of the selection biases' settling before the first step
# (``Job._balanced``), one a forward pass: a run's own rule at rates falling
# by 3% a pass from thirty times the cell's to the cell's, and 48 passes
# there.
BALANCE = tuple(max(0.03 * 0.97 ** k, 0.001) for k in range(160))


def _mix_leaves(tree) -> list:
    """The parameters that reach the loss through a CCA mixer's mix alone:
    both convolutions' taps and biases and the key temperatures."""
    return [layer["cca"][name] for layer in tree["layers"]
            for name in ("conv0_w", "conv0_b", "conv1_w", "conv1_b", "temp")]


def _routers_off(params, inputs, logits, eps: float) -> float:
    """How far each layer's router outputs ``logits`` ``[T, layers, E]``
    (the program's own, float32) lie from the reference's router on the
    activations the program's router read (``inputs`` ``[T, layers, d]``)
    and the state the reference made of the layers before, element by
    element: the largest difference over the root mean square of the
    reference's, the worst layer's."""
    worst, state = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i, layer in enumerate(params["layers"]):
            want, state = reference.router(inputs[:, i],
                                           layer["moe"]["router"], state,
                                           eps)
            worst = max(worst, float(
                jnp.max(jnp.abs(logits[:, i] - want))
                / jnp.sqrt(jnp.mean(jnp.square(want)))))
    return worst


class Job(gpt_window_moe_dp.Job):
    dense_layers = 0

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
        router = c["published"]["num_experts"]
        if c["num_experts"] * share["chips"] != router:
            raise ValueError(
                f"{share['chips']} chips of {c['num_experts']} experts are "
                f"not the published {router}")
        layers = c["num_hidden_layers"]
        rope = c["rope_parameters"]["hybrid"]
        if set(c["layer_types"]) != {"hybrid"} \
                or len(c["layer_types"]) < layers \
                or c["hidden_act"] != "silu" or c["attention_bias"] \
                or c["lm_head_bias"] or not c["tie_word_embeddings"] \
                or c["sliding_window"] or rope["rope_type"] != "default":
            raise ValueError(
                "this job runs hybrid layers alone (a CCA sublayer and an "
                "expert sublayer), SiLU, no bias on a projection or on the "
                "head, a tied head, full attention, an unscaled rotary "
                "embedding")
        self.samples_per_step = self.batch * self.seq
        self.bias_rate = c["router_bias_update_rate"]
        taps = (c["cca_time0"], c["cca_time1"])
        rotary_dim = int(c["head_dim"] * rope["partial_rotary_factor"])
        first = share["rank"] * c["num_experts"]
        # What the reference is told of the model, from the published keys
        # and not from the program's own configuration below.
        self.reference_model = dict(
            heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            rope_theta=float(rope["rope_theta"]), rotary_dim=rotary_dim,
            first_expert=first, norm_eps=c["rms_norm_eps"])
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=layers,
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["moe_intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"],
            layers=tuple(gpt.LayerSpec(mixer="cca", ff="experts")
                         for _ in range(layers)),
            cca_taps=taps, num_experts=router,
            experts_per_token=c["num_experts_per_tok"],
            experts_held=c["num_experts"], first_expert=first,
            router_kind="mlp", router_dim=c["router_hidden_size"],
            router_bias=True, router_probe=True, residual_scaling=True,
            norm_eps=c["rms_norm_eps"], rope_theta=float(rope["rope_theta"]),
            rotary_dim=rotary_dim, tie_embeddings=True)
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
        self.flops_per_sample = flops_cca.cca_moe_train_flops(
            self.seq, layers, self.cfg.embed_dim, taps=taps,
            router_dim=self.cfg.router_dim, vocab=self.cfg.vocab_size,
            experts=dict(router=router, width=self.cfg.expert_width,
                         top_k=self.cfg.experts_per_token,
                         held=c["num_experts"]), **shape)
        # What one step asks of its kernels on one chip. A checkpointed
        # block keeps the flash kernel's output and log-sum-exp
        # (``gpt.SAVED_NAMES``), so the algorithm's share is one forward and
        # one backward a layer.
        per_chip = self.batch // self.chips
        self.per_chip_tokens = per_chip * self.seq
        fwd = flops.flash_forward_cost(per_chip, self.seq, **shape)
        bwd = flops.flash_backward_cost(per_chip, self.seq, **shape)
        self.kernel_costs = {"flash": {
            "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
            "ops": layers * (fwd["ops"] + bwd["ops"]),
            "bytes": layers * (fwd["bytes"] + bwd["bytes"])}}
        # The mix's least cost a step, for ``cca_mix_roofline_pct``: three
        # passes a layer under full recomputation (forward, again,
        # backward). Read by scope (``attn/cca_mix``), not by a kernel's
        # name: no ``match``, so it is no entry of ``kernel_costs``.
        one = flops_cca.mix_pass_cost(self.per_chip_tokens, taps=taps,
                                      **shape)
        passes = layers * (3 if c["remat"] == "full" else 2)
        self.cca_mix_cost = {key: passes * one[key]
                             for key in ("ops", "bytes")}
        # Until the check has counted the held experts' rows: an even
        # routing's share of the T k.
        self._grouped_matmul_cost(c["num_experts"] / router)
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
        """The model's parameters from the seed and, as a checkpoint taken
        mid-training holds them, every vector that starts at one or zero
        moved off it, so that the check sees each: the selection biases
        (deviation 0.01), the key temperatures (0.1), the residual
        scaling's scales and the routers' weights on the carried state
        (0.05 about one), the residual scaling's and the routers' biases
        (0.02)."""
        params = gpt.init_params(key, self.cfg)
        keys = iter(jax.random.split(jax.random.fold_in(key, 1000), 64
                                     * len(params["layers"])))

        def off(leaf, by):
            return leaf + by * jax.random.normal(next(keys), leaf.shape,
                                                 jnp.float32)

        for layer in params["layers"]:
            moe, cca = layer["moe"], layer["cca"]
            moe["router_bias"] = off(moe["router_bias"], 0.01)
            cca["temp"] = off(cca["temp"], 0.1)
            for name in ("mixer_res", "mlp_res"):
                layer[name] = {
                    part: off(leaf, 0.05 if part.endswith("scale") else 0.02)
                    for part, leaf in layer[name].items()}
            for name in ("down_b", "b1", "b2"):
                moe["router"][name] = off(moe["router"][name], 0.02)
            if "carry" in moe["router"]:
                moe["router"]["carry"] = off(moe["router"]["carry"], 0.05)
        return self._balanced(params, jax.random.fold_in(key, 2000))

    def _balanced(self, params, key):
        """``params`` with the selection biases a run's controller would
        have reached: ``gpt.update_router_bias`` applied at ``BALANCE``'s
        falling rates to the counts of as many forward passes over one
        sequence of seeded tokens, nothing else moved. A router MLP fresh
        from its initialisation leans every token the same way (its GELUs'
        outputs have a mean, which the next matrix turns into an offset an
        expert): the busiest expert of 16 got 5.7 to 9.8 times the mean on
        six seeds (my chip run, PR 46), the held experts' share of the rows
        hung on the seed and moved through a window as the step's own
        updates at ``router_bias_update_rate`` evened it out. A checkpoint
        taken mid-training has that behind it."""
        seq = min(self.seq, 4096)
        tokens = jax.random.randint(key, (1, seq), 0, self.cfg.vocab_size)
        positions = jnp.arange(seq)[None]

        def with_biases(biases):
            return {**params, "layers": [
                {**layer, "moe": {**layer["moe"], "router_bias": bias}}
                for layer, bias in zip(params["layers"], biases,
                                       strict=True)]}

        def step(biases, rate):
            held = with_biases(biases)
            counts = gpt.loss_and_aux(held, tokens, tokens, positions,
                                      self.cfg)[1]["counts"]
            return reference.biases(
                gpt.update_router_bias(held, counts, rate)), None

        return with_biases(jax.lax.scan(
            step, reference.biases(params),
            jnp.asarray(BALANCE, jnp.float32))[0])

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, the norm of the gradient as the optimizer received it from the
        exchange (AdamW's first moment after its first step is ``1 - b1``
        times that gradient), the norm of what the step added to the
        parameters (the biases' update apart), the biases' update weighed
        by the load, the norm of the gradient of the mix's own parameters,
        the tokens each expert got, and what each layer's router read and
        gave (42 and 0.3 MB)."""
        (new_params, new_opt, loss), aux = self._step_with_aux(
            params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        for layer in moved["layers"]:
            layer["moe"].pop("router_bias")
        mu = new_opt.inner_state[0].mu
        scale = 1 - self.adamw["b1"]
        return (loss,
                optax.global_norm(mu) / scale,
                optax.global_norm(moved),
                gpt_window_moe_dp._bias_step_on_load(
                    reference.biases(params), reference.biases(new_params),
                    aux["counts"]),
                optax.global_norm(_mix_leaves(mu)) / scale,
                aux["counts"],
                # every rank's tokens, [T, layers, .]
                [hvd.allgather(jnp.swapaxes(aux[key], 0, 1))
                 for key in ("router_inputs", "router_logits")])

    def check(self):
        """As ``gpt_window_moe_dp``'s, the reference given the same share of
        the experts and the same biases; the experts' token counts are kept
        for the load metric and for the rows the held experts multiply."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = gpt_dp._batch(np.random.default_rng(self.seed + 1), shape,
                             self.cfg.vocab_size)
        per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
        # Made before the reference's precision is in force: settling the
        # biases runs the program's own forward pass, flash kernels and all,
        # and those take their bfloat16 operands at the default precision.
        params = self._params
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_counts, grad = reference.loss_and_grad(
                params, *(x.reshape(per_shard) for x in data),
                **self.reference_model)
        ref_gnorm = reference.shards.norm(grad)
        ref_mix = reference.shards.norm(_mix_leaves(grad))
        ref_moved = reference.adamw_first_update_norm(
            params, grad, self.adamw["lr"],
            self.adamw["weight_decay"], self.adamw["eps"])
        del grad
        ref_bias = reference.bias_step_on_load(
            reference.biases(params), reference.updated_biases(
                params, ref_counts,
                self.config["router_bias_update_rate"]), ref_counts)
        *numbers, counts, routers = self.check_step(
            params, self._opt_state, hvd.shard_batch(data))
        loss, gnorm, moved, bias, mix = map(float, numbers)
        routers_off = _routers_off(params, *routers, self.cfg.norm_eps)
        del routers
        self.expert_counts = np.asarray(counts)
        # A lower bound on the sample's choices that differ from the
        # reference's, as ``gpt_moe_dp`` reckons it.
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
                ("selection biases' update weighed by the experts' load",
                 bias, ref_bias, BIAS_RTOL),
                ("routers' outputs off the reference's on the same "
                 "activations", 1.0 + routers_off, 1.0, ROUTER_RTOL),
                ("gradient norm of the convolutions' and temperatures' "
                 "parameters", mix, ref_mix, MIX_RTOL)]
        return lambda: rows
