"""The user's side of a data-parallel training job on a decoder that mixes
sliding-window and full attention layers and puts dense feed-forwards before
expert blocks whose router scores with a sigmoid under a selection bias, of
which this rank holds its share of the experts (``model_type: afmoe``,
Trinity-Mini), as ``gpt_moe_dp`` is for the sparse decoder and sharing what
is the same: AdamW with float32 moments, random tokens from the seed (drawn
from the rows of the vocabulary held here), state donated to the step. The
selection bias is state beside the parameters: AdamW is masked off it
(``gpt.trainable``) and the step moves it from the tokens each expert got,
summed over the ranks (``hvd.allreduce``), after the optimizer's update
(``gpt.update_router_bias``). The configuration file uses the published
``config.json`` key names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops_moe, flops_window
from benchmarks.jobs import gpt_dp, gpt_moe_dp
from benchmarks.reference import gpt_window_moe_dp as reference

# bfloat16 program (the three flash kernels under the band and under the
# causal mask, the sorted grouped expert layer over this rank's 16 experts,
# full recomputation) against the float32 reference (S x S logits under the
# band mask, every held expert on every token) on a seeded checkpoint (the
# biases normal with deviation 0.01), one 4096-token sequence a chip (twice
# the window), through the timed step's own function. The loss is over the
# targets past the first window alone: there every query's band is whole and
# shuts keys out. On the chip, over 76 seeds of the shipped program (my chip
# runs, PR 35; 64 of ``scripts/check_sweep.py --workload trinity-mini_s8192``
# and 12 runs of the cell; the band row over the last 52, the routers' row
# over the last 36, the others over all; at a learning rate of 1e-4, which
# both sides carry: 14 more seeds at the 1e-5 the cell ships with read
# inside every range below):
#
# * loss off by 1e-6 to 1.8e-4 (a mean of 2048 token losses near ln(25024) +
#   0.5), gradient norm after the exchange by 3.5e-6 to 2.3e-3: a wrong
#   scale or a dropped term. Their bounds are at three times the worst seen.
# * update norm by 5e-7 to 1.5e-3 on 73 seeds and by 2.7e-3, 3.2e-3 and
#   3.8e-3 on three: AdamW's first step is lr times the gradient's sign but
#   for elements as small as its eps, and a held expert that few of the
#   sample's tokens chose (the seeded router's busiest expert gets 6 to 11
#   times the mean, its idlest next to nothing) has 6.3M such elements, 1%
#   of the tree's: a token it gains or loses to bfloat16's rounding moves
#   them all. The bound is at three times the worst seen (the first 32
#   seeds read 9.1e-4 at most, and it was 3e-3 until the others were run);
#   what it is for, a wrong learning rate, misses it by the factor.
# * choices shared with the reference: of the sample's 4 x 32,768
#   token-expert choices, those the per-expert counts cannot tell from the
#   reference's (``choices_moved`` as ``gpt_moe_dp`` reckons it, a lower
#   bound): 471 to 592 moved, 3.6e-3 to 4.5e-3. The program's routers read
#   activations that four bfloat16 layers have rounded, so a token's 8th and
#   9th experts swap where their leaning scores lie within that. The bound
#   lies between the worst shipped reading and the least faulty one (1.25e-2,
#   the weights' constant dropped).
# * the band row: the gradient of the window layers' key and value
#   projections, which reach the loss through attention alone, **along the
#   reference's** as a share of the reference's own length: 1 where they
#   agree. Off by 2.5e-5 to 4.9e-3 as shipped, by 0.22 to 0.27 with the band
#   ignored: a turn of the gradient, which its norm does not see (the norm
#   of the same leaves read 1.3e-3 to 2.9e-2 with the band ignored, on one
#   seed in three as shipped).
# * the bias row: ``sum_e (b'_e - b_e) c_e / sum_e c_e`` summed over the
#   expert layers, ``b'`` the biases after the step's update and ``c`` the
#   step's counts (``reference.bias_step_on_load``): what the update did,
#   weighed by the load it answers. Off by 0 to 1.7e-3 as shipped; 1.0 with
#   the update left out, 2.3e-3 to 1.5e-2 with the bias out of the choice
#   (other counts), 6e-2 to 1e-1 with the band ignored.
# * the routers' row: every expert layer's float32 outputs as the step made
#   them against the reference's product **on the operand the step's own
#   product read** (``GPTConfig.router_probe``), element by element: the
#   largest difference over the root mean square of the reference's. Exactly
#   0 on 36 seeds as shipped (the same float32 product at the highest
#   precision on the same chip); 8.2e-3 to 8.4e-3 on 3 seeds with the product
#   in one bfloat16 pass, which no other row sees. The bound lies between,
#   above what another order of a float32 sum would read (1e-6). The operand
#   and not the layer's bfloat16 input: the compiler feeds the product the
#   normed activations before their rounding (a first probe of the rounded
#   input read 2.3e-2 as shipped and with one pass alike).
#
# What each row reads on a program with one mechanism left out, against the
# untouched reference (``--variant``, 3 seeds each and two more on the last
# trees, in the rows' order: loss, gradient norm, update norm, choices, band,
# bias; the routers' row reads 0 under all but the last), the misses in
# brackets:
#   band ignored (full causal)  1.5e-3 [1.2e-2]  6.2e-3  [8.9e-2] [2.7e-1] [9.8e-2]
#   bias out of the choice      2.3e-4  2.4e-3   4.5e-3  [5.4e-2]  9.7e-3  [1.4e-2]
#   bias never updated          1.1e-4  8.8e-4   8.1e-4   4.4e-3   2.4e-3  [1.0]
#   route_scale dropped         5.5e-4  5.3e-3  [1.3e-2] [1.8e-2] [3.4e-2]  5.5e-3
#   norms after the branches   [3.6e-3] [2.9e-1] [1.3e-2] [4.2e-1] [9.9e-1] [8.5e-1]
#   shared expert dropped      [2.4e-3] [1.3e-1] [2.3e-2] [1.2e-1] [5.1e-1] [3.9e-2]
#   router product in one pass  1.5e-4  1.9e-3   1.3e-3   4.5e-3   4.6e-3   1.2e-3  [8.4e-3]
# (largest of the seeds; a bracket is over its bound on the largest, and on
# every seed for the band ignored: choices, band, bias; the bias out of the
# choice: choices; route_scale dropped: choices; the next two: every row but
# the loss and the update norm once; the last: the routers' row.) The
# router's product in one bfloat16 pass (the backend's default precision in
# place of the highest) reads as shipped on the first six rows: its inputs
# are activations that bfloat16 has already moved by far more than the pass
# adds, its rounding is not biased, and a norm, a count or a projection
# cannot see unbiased noise (PERF.md, Open questions); the seventh row holds
# the product itself.
LOSS_RTOL = 6e-4
GNORM_RTOL = 7e-3
UPDATE_RTOL = 1.2e-2
CHOICES_RTOL = 8e-3
BAND_RTOL = 2e-2
BIAS_RTOL = 6e-3
ROUTER_RTOL = 1e-4


def _window_kv_leaves(tree, windows) -> list:
    """The key and value projections of the window attention layers
    (``windows``: each layer's window, None for a full layer)."""
    return [layer[name] for window, layer in zip(windows, tree["layers"])
            if window is not None for name in ("wk", "wv")]


def _bias_step_on_load(before, after, counts):
    """``reference.bias_step_on_load`` on the device, from the program's own
    biases and counts ``[expert layers, E]``."""
    c = counts.astype(jnp.float32)
    step = jnp.stack(after) - jnp.stack(before)
    return jnp.sum(jnp.sum(step * c, axis=-1) / jnp.sum(c, axis=-1))


def _routers_off(params, inputs, logits) -> float:
    """How far each expert block's router outputs ``logits`` ``[T, blocks,
    E]`` (the program's own, float32) lie from the reference's on the
    activations the program's router read (``inputs`` ``[T, blocks, d]``),
    element by element: the largest difference over the root mean square of
    the reference's, the worst block's."""
    routers = [layer["moe"]["router"] for layer in params["layers"]
               if "moe" in layer]
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for block, router in enumerate(routers):
            got = logits[:, block]
            want = reference.router_logits(inputs[:, block], router)
            worst = max(worst, float(
                jnp.max(jnp.abs(got - want))
                / jnp.sqrt(jnp.mean(jnp.square(want)))))
    return worst


class Job(gpt_moe_dp.Job):
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
        kinds = c["layer_types"]
        if len(kinds) != layers or set(kinds) - {"sliding_attention",
                                                 "full_attention"} \
                or c["hidden_act"] != "silu" or c["score_func"] != "sigmoid" \
                or c["tie_word_embeddings"] or c["rope_scaling"] \
                or c["num_shared_experts"] != 1 or c["n_group"] != 1 \
                or c["topk_group"] != 1 or not c["mup_enabled"]:
            raise ValueError(
                "this job runs sliding and full attention layers, SiLU, a "
                "sigmoid router over one group, one shared expert, an untied "
                "head, an unscaled rotary embedding, the embedding's "
                "multiplier")
        self.samples_per_step = self.batch * self.seq
        self.windows = tuple(c["sliding_window"] if kind ==
                             "sliding_attention" else None for kind in kinds)
        self.dense_layers = c["num_dense_layers"]
        self.bias_rate = c["load_balance_coeff"]
        # What the reference is told of the model, from the published keys
        # and not from the program's own configuration below.
        self.reference_model = dict(
            windows=self.windows, dense_layers=self.dense_layers,
            top_k=c["num_experts_per_tok"], route_scale=c["route_scale"],
            first_expert=share["rank"] * c["num_experts"],
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])
        # Each layer said once: a window layer has the window and the rotary
        # embedding, a full one neither; dense feed-forwards first.
        plan = tuple(gpt.LayerSpec(
            mixer="attention", window=window, rope=window is not None,
            ff="gated" if i < self.dense_layers else "experts")
            for i, window in enumerate(self.windows))
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=layers,
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            embed_dim=c["hidden_size"], mlp_dim=c["intermediate_size"],
            expert_dim=c["moe_intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"], layers=plan,
            num_experts=router, experts_per_token=c["num_experts_per_tok"],
            experts_held=c["num_experts"],
            first_expert=share["rank"] * c["num_experts"],
            renormalize_experts=c["route_norm"],
            shared_expert_dim=c["num_shared_experts"]
            * c["moe_intermediate_size"], shared_expert_gate=False,
            router_score=c["score_func"], router_bias=True,
            route_scale=c["route_scale"], router_probe=True,
            qk_head_norm=True,
            norm_eps=c["rms_norm_eps"], post_norm=True, attention_gate=True,
            rope_theta=float(c["rope_theta"]),
            embedding_multiplier=math.sqrt(c["hidden_size"]))
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
        self.flops_per_sample = flops_window.window_moe_train_flops(
            self.seq, self.windows, self.dense_layers, self.cfg.embed_dim,
            mlp=self.cfg.mlp_dim, vocab=self.cfg.vocab_size, experts=dict(
                router=router, width=self.cfg.expert_width,
                top_k=self.cfg.experts_per_token, held=c["num_experts"],
                shared_width=self.cfg.shared_expert_dim), **shape)
        # What one step asks of its kernels on one chip. A checkpointed
        # block keeps the flash kernel's output and log-sum-exp
        # (``gpt.SAVED_NAMES``), so the algorithm's share is one forward and
        # one backward an attention layer: the band's pairs for a window
        # layer, the triangle's for a full one.
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
        # The window layers' alone, for ``flash_window_roofline_pct``; their
        # kernels are told from the full layers' by the scope they run under
        # (``attn_window``), not by a name: no ``match``.
        self.window_flash_cost = flash_cost(
            [w for w in self.windows if w is not None])
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
        # Tokens per expert on the check's sample, [expert layers, router].
        self.expert_counts = None

    def _grouped_matmul_cost(self, held_share: float) -> None:
        """The grouped matmuls' least cost a step: three passes an expert
        layer over the rows the held experts really multiply, ``held_share``
        of the ``T k`` token-expert pairs (from the counts the layer
        returns)."""
        rows = int(self.per_chip_tokens * self.cfg.experts_per_token
                   * held_share)
        one = flops_moe.grouped_matmul_pass_cost(
            rows, embed=self.cfg.embed_dim, width=self.cfg.expert_width,
            experts=self.cfg.experts_held)
        passes = 3 * (self.cfg.num_layers - self.dense_layers)
        self.kernel_costs["grouped_matmul"] = {
            "match": r"^ragged-dot-",
            "ops": passes * one["ops"], "bytes": passes * one["bytes"]}

    def init_params(self, key):
        """The model's parameters from the seed and, as a checkpoint taken
        mid-training holds them, selection biases that are not all alike:
        normal with deviation 0.01, so that the check sees them."""
        params = gpt.init_params(key, self.cfg)
        for i, layer in enumerate(params["layers"]):
            if "moe" in layer:
                layer["moe"]["router_bias"] = 0.01 * jax.random.normal(
                    jax.random.fold_in(key, 1000 + i),
                    layer["moe"]["router_bias"].shape, jnp.float32)
        return params

    def _step_with_aux(self, params, opt_state, data):
        (loss, aux), grads = jax.value_and_grad(self._loss, has_aux=True)(
            params, *data)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        # The biases move outside the loss, from what every rank's tokens
        # chose in this step.
        counts = hvd.allreduce(aux["counts"], op=hvd.Sum)
        params = gpt.update_router_bias(
            optax.apply_updates(params, updates), counts, self.bias_rate)
        return (params, opt_state,
                hvd.allreduce(loss, op=hvd.Average)), dict(aux, counts=counts)

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, the norm of the gradient as the optimizer received it from the
        exchange (AdamW's first moment after its first step is ``1 - b1``
        times that gradient), the norm of what the step added to the
        parameters (the biases' update apart), the biases' update weighed
        by the load, the tokens each expert got, and the gradient of the
        window layers' key and value projections itself (33 MB), and what
        each expert block's router read and gave (134 and 8 MB)."""
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
                [leaf / scale for leaf in _window_kv_leaves(mu,
                                                            self.windows)],
                # every rank's tokens, [T, expert layers, .]
                [hvd.allgather(jnp.swapaxes(aux[key], 0, 1))
                 for key in ("router_inputs", "router_logits")])

    def check(self):
        """As ``gpt_moe_dp``'s, the reference given the same share of the
        experts and the same biases; the experts' token counts are kept for
        the load metric and for the rows the held experts multiply."""
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
        ref_bias = reference.bias_step_on_load(
            reference.biases(self._params), reference.updated_biases(
                self._params, ref_counts, self.config["load_balance_coeff"]),
            ref_counts)
        *numbers, counts, band, routers = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data))
        loss, gnorm, moved, bias = map(float, numbers)
        routers_off = _routers_off(self._params, *routers)
        del routers
        # The program's gradient along the reference's, as a share of the
        # reference's own length: 1 where they agree; rounding that is not
        # biased turns the gradient a little and hardly moves this, a band
        # ignored turns it far (a norm would not see a turn at all).
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
                ("selection biases' update weighed by the experts' load",
                 bias, ref_bias, BIAS_RTOL),
                ("routers' outputs off the reference's on the same "
                 "activations", 1.0 + routers_off, 1.0, ROUTER_RTOL)]
        return lambda: rows
