"""The user's side of a data-parallel training job on a decoder-hybrid-decoder
stack (``phi-4-mini-flash-reasoning``: SambaY, arXiv:2507.06607): Mamba-1
selective scans and differential attention under a 512-key window, one
Mamba-1 and one full differential attention layer that publish their scan
output and their keys and values, and Gated Memory Units and differential
cross-attention layers that read them; LayerNorms, a tied head, no position
embedding. As ``gpt_dp`` is for the dense decoder, sharing what is the same:
AdamW with float32 moments, random tokens from the seed (drawn from the rows
of the vocabulary held here), the step and its loss (``gpt_dp.Job``), state
donated to the step. The configuration file uses the published
``config.json`` key names; which layer is of which kind follows from the
published rule (``reference.published_layers``) at the kept layers'
published indices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt

from benchmarks import flops_s6
from benchmarks.jobs import gpt_dp
from benchmarks.reference import gpt_sambay_dp as reference

# bfloat16 program (the selective scan's two kernels with float32 decays and
# states, the flash kernels at keys of 64 beside values of 128 under the
# causal mask and under the 512 band, twice a differential layer, the
# published values through the one carry across checkpointed blocks) against
# the float32 reference (the scan one token a step, S x S logits in blocks of
# 256 query rows, the published values passed by hand) on a seeded
# checkpoint (LayerNorm biases of deviation 0.1: ``Job.init_params``),
# through the timed step's own function **at the timed step's own shape: one
# 16,384-token sequence a chip**. The reference runs first, before the
# optimizer state exists (``gpt_dp.Job._opt_state``). On the chip, over 12
# seeds of the shipped program (my chip runs, PR 60: 2 of
# ``scripts/check_sweep.py --workload phi-4-mini-flash-reasoning_s16384``
# and 10 runs of the cell, 3 of them of the committed files alone under
# these very bounds), and 2 seeds of
# each program with a mechanism left out or another in its place
# (``--variants``, against the untouched reference):
#
# * loss off by 1.2e-6 to 2.2e-5 (a mean of 16,383 token losses near
#   ln(25008) + 0.5). The LayerNorms' biases dropped read 2.6e-4 to 8.7e-4,
#   ``lambda_init`` at nought 2.3e-4 to 6.2e-4; the others 7e-6 to 1.4e-4.
#   The bound is 4.6 times the worst sound reading and 2.3 under those two.
# * gradient norm after the exchange 1.8e-5 to 1.9e-4; the decays in
#   bfloat16 1.8e-3 to 4.7e-3, the cross layer fed the window layer's keys
#   4.1e-3 to 4.3e-3, ``lambda_init`` from the index in this stack 4.7e-2 to
#   5.0e-2, the biases dropped 0.14 to 0.15, ``lambda_init`` at nought 0.22;
#   the memory taken after the gate 2.9e-4 to 1.8e-3 (not over the bound on
#   every seed: the producers' rows are what catches it). The bound is 3.2
#   times the worst sound reading and 3.1 under the decays'.
# * update norm 5.7e-6 to 7.6e-6 (AdamW's first step is lr times the
#   gradient's sign); **5.05e-3 and 5.06e-3 with the parameters held in
#   bfloat16** (at 1e-4 most of an update survives a bfloat16 parameter's last
#   bit; what is lost is the row's 0.5%), 1.0 for a state left unchanged, the
#   mechanisms left out 9.3e-4 at most. The precision below is caught here
#   and nowhere else; the bound lies between the reading and that control
#   with the more room above the reading: 66 times it, 10 under the control.
# * the producers' own gradients: of layer 16's ``A_log``, ``dt_proj`` and
#   ``x_proj`` (which the scan's backward kernel and the Gated Memory Unit's
#   cotangent through the carry alone can move) and of layer 17's key and
#   value projections (which receive the sum of the full layer's own and the
#   cross layer's cotangents), each leaf as the length of its difference
#   from the reference's over the reference's own length, the largest of the
#   leaves. bfloat16 operands put a floor under both: **the scan's row reads
#   3.3e-2 to 4.1e-2 on ten seeds, 5.6e-2 and 5.8e-2 on two, the attention's
#   3.8e-2 to 4.5e-2.** The memory taken after the gate reads 0.68 to 0.77 and
#   0.21, the cross layer fed the window layer's keys 0.18 to 0.22 and 0.68
#   to 0.76, ``lambda_init`` from the index in this stack 0.24 to 0.26 and
#   1.0 to 1.1, at nought 0.67 to 0.68 and 2.6, the biases dropped 1.2 and
#   1.3, **the decays in bfloat16 5.7 to 36 and 0.14 to 0.29** (the control
#   in the precision below for the scan: a state decayed 16,384 times by
#   factors off by 2**-9). The bounds: the scan's 1.9 times the worst sound
#   reading and 1.6 under the least faulty one it is for (0.18), the
#   attention's 2.0 times and 1.5 under (0.14).
# * **not told apart at the cell's size: a window of 513 keys for 512.** It
#   reads 5.4e-2 to 6.5e-2 on the producers' rows, inside what twelve sound
#   seeds read, and as shipped on the others: one key more among 512 moves a
#   query's output by less than bfloat16 does. The band's edge is held where
#   it weighs, at a window of 4 on the CPU (``tests/test_gpt_sambay.py``,
#   ``tests/test_flash_window.py``).
#
# What each row reads on a program with one mechanism left out (2 seeds each,
# the larger; loss, gradient norm, update norm, the scan's row, the
# attention's); in brackets the rows whose **lesser** reading is over the
# bound:
#   memory after the gate        1.3e-4  1.8e-3  1.6e-4 [7.7e-1] [2.1e-1]
#   K V from the window layer    1.4e-4 [4.3e-3] 9.4e-6 [2.2e-1] [7.6e-1]
#   lambda_init, local index     1.4e-4 [5.0e-2] 1.1e-4 [2.6e-1] [1.1e+0]
#   lambda_init at nought       [6.2e-4][2.2e-1][9.3e-4][6.8e-1] [2.6e+0]
#   LayerNorm biases dropped    [8.7e-4][1.5e-1] 9.0e-5 [1.2e+0] [1.3e+0]
#   decays in bfloat16           1.5e-5 [4.7e-3] 9.1e-5 [3.6e+1] [2.9e-1]
#   bfloat16 parameters          5.0e-6  1.0e-4 [5.1e-3] 3.7e-2   3.9e-2
#   a window of 513              2.0e-5  1.0e-4  8.1e-6  6.5e-2   5.5e-2
LOSS_RTOL = 1e-4
GNORM_RTOL = 6e-4
UPDATE_RTOL = 5e-4
SCAN_PRODUCER_RTOL = 1.1e-1
KV_PRODUCER_RTOL = 9e-2


def layer_spec(layer: reference.Layer) -> gpt.LayerSpec:
    """The program's description of one published layer."""
    common = dict(rope=False, ff="gated")
    if layer.kind == "mamba":
        return gpt.LayerSpec(
            mixer="s6", publishes=("s6_scan",) if layer.publishes else (),
            **common)
    if layer.kind == "gmu":
        return gpt.LayerSpec(mixer="gmu", reads=("s6_scan",), **common)
    if layer.kind == "cross":
        return gpt.LayerSpec(mixer="diff_cross", reads=("diff_kv",),
                             depth=layer.depth, **common)
    return gpt.LayerSpec(
        mixer="diff_attention", window=layer.window, depth=layer.depth,
        publishes=("diff_kv",) if layer.publishes else (), **common)


def producer_leaves(tree, layers) -> tuple:
    """``(the publishing Mamba layer's A_log, dt_proj and x_proj, the
    publishing attention layer's wk and wv)`` of a tree shaped as the
    parameters are."""
    scan, kv = (next(p for p, layer in zip(tree["layers"], layers)
                     if layer.publishes and layer.kind == kind)
                for kind in ("mamba", "full"))
    return ([scan["s6"][name] for name in ("A_log", "dt_proj", "x_proj")],
            [kv["wk"], kv["wv"]])


def _off(program, wanted) -> float:
    """The largest over the leaves of ``|g - r| / |r|``."""
    return max(float(jnp.linalg.norm((g - r).ravel())
                     / jnp.linalg.norm(r.ravel()))
               for g, r in zip(program, wanted))


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
        c = config
        if c["hidden_act"] != "silu" or not c["tie_word_embeddings"] \
                or c["mlp_bias"] or c["lm_head_bias"] or c["embd_pdrop"] \
                or c["resid_pdrop"] \
                or len(c["layer_indices"]) != c["num_hidden_layers"]:
            raise ValueError(
                "this job runs SiLU-gated feed-forwards without a bias, a "
                "tied head without one, no dropout, and one published index "
                "for each layer held")
        published = reference.published_layers(
            c["published"]["num_hidden_layers"], c["mb_per_layer"],
            c["sliding_window"])
        # What the reference is told of the model, from the published keys
        # and not from the program's own configuration below.
        self.layers = tuple(published[i] for i in c["layer_indices"])
        self.reference_model = dict(layers=self.layers,
                                    norm_eps=c["layer_norm_eps"])
        self.samples_per_step = self.batch * self.seq
        heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
        mamba = c["mamba"]
        self.cfg = gpt.GPTConfig(
            vocab_size=c["vocab_size"], num_layers=len(self.layers),
            num_heads=heads, num_kv_heads=kv_heads,
            head_dim=c["hidden_size"] // heads, embed_dim=c["hidden_size"],
            mlp_dim=c["intermediate_size"],
            dtype=jnp.dtype(c["compute_dtype"]), tp_axis=None, sp_axis=None,
            attention=c["attention"], remat=c["remat"],
            layers=tuple(layer_spec(layer) for layer in self.layers),
            tie_embeddings=True, norm_kind="layer",
            norm_eps=c["layer_norm_eps"], s6_inner=mamba["d_inner"],
            s6_dt_rank=mamba["dt_rank"], ssm_state=mamba["d_state"],
            ssm_conv=mamba["d_conv"])
        self.adamw = {k: c["optimizer"][k] for k in
                      ("lr", "b1", "b2", "eps", "weight_decay")}
        self.opt = hvd.DistributedOptimizer(optax.adamw(
            self.adamw["lr"], b1=self.adamw["b1"], b2=self.adamw["b2"],
            eps=self.adamw["eps"], weight_decay=self.adamw["weight_decay"]))
        kinds = tuple(layer.kind for layer in self.layers)
        shape = dict(heads=heads, kv_heads=kv_heads,
                     head_dim=self.cfg.head_dim)
        # Forward and backward for one token, recomputation not counted.
        self.flops_per_sample = flops_s6.sambay_train_flops(
            self.seq, kinds, self.cfg.embed_dim, window=c["sliding_window"],
            mlp=self.cfg.mlp_dim, vocab=self.cfg.vocab_size,
            inner=mamba["d_inner"], state=mamba["d_state"],
            dt_rank=mamba["dt_rank"], **shape)
        # What one step asks of its kernels on one chip. A checkpointed
        # block keeps the flash kernels' outputs and log-sum-exps and the
        # scan's output and entering states (``gpt.SAVED_NAMES``), so the
        # algorithm's share is, a differential layer, two forward and two
        # backward calls over its band's pairs and, a Mamba layer, a forward
        # pass of the scan and two for the backward.
        per_chip = self.batch // self.chips
        self.per_chip_tokens = per_chip * self.seq

        def flash_cost(windows) -> dict:
            parts = [flops_s6.diff_flash_cost(per_chip, self.seq, window=w,
                                              **shape) for w in windows]
            return {key: sum(p[key] for p in parts)
                    for key in ("ops", "bytes")}

        attention = [layer.window for layer in self.layers
                     if layer.kind in ("window", "full", "cross")]
        scan = flops_s6.scan_pass_cost(self.per_chip_tokens,
                                       mamba["d_inner"], mamba["d_state"])
        passes = 3 * kinds.count("mamba")
        self.kernel_costs = {
            "flash": {"match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
                      **flash_cost(attention)},
            "s6_scan": {"match": r"^hvd_s6_", "ops": passes * scan["ops"],
                        "bytes": passes * scan["bytes"]}}
        # The window layers' alone, for ``flash_window_roofline_pct``.
        self.window_flash_cost = flash_cost(
            [w for w in attention if w is not None])
        self.step = hvd.run_step(
            self._train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED, donate_argnums=(0, 1))
        self.first_call_s = None
        self.check_step = hvd.run_step(
            self._checked_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)
        # The reference's numbers by seed. A caller that checks several
        # programs on one seed's parameters (``scripts/check_sweep.py
        # --variants``) hands its jobs one dict and the reference is
        # computed once.
        self.reference_cache: dict = {}

    def init_params(self, key):
        """The model's parameters from the seed as a checkpoint taken
        mid-training holds them: **LayerNorm biases that are not zero**
        (normal with deviation 0.1, folded from the seed; ``models/gpt.py``
        makes zeros), so that the check sees them."""
        params = gpt.init_params(key, self.cfg)
        count = iter(range(1 << 30))

        def with_bias(node):
            if isinstance(node, dict) and set(node) == {"weight", "bias"}:
                return {**node, "bias": 0.1 * jax.random.normal(
                    jax.random.fold_in(key, 1000 + next(count)),
                    node["bias"].shape, jnp.float32)}
            if isinstance(node, dict):
                return {k: with_bias(v) for k, v in node.items()}
            if isinstance(node, list):
                return [with_bias(v) for v in node]
            return node

        return with_bias(params)

    def _checked_step(self, params, opt_state, data):
        """The timed step on the check's sample, reduced to numbers: the
        loss, the norm of the gradient as the optimizer received it from the
        exchange (AdamW's first moment after its first step is ``1 - b1``
        times that gradient), the norm of what the step added to the
        parameters, and the gradient itself of the producers' own
        parameters (five leaves, 30 MB)."""
        new_params, new_opt, loss = self._train_step(params, opt_state, data)
        moved = jax.tree.map(jnp.subtract, new_params, params)
        mu, scale = new_opt[0].mu, 1 - self.adamw["b1"]
        return (loss, optax.global_norm(mu) / scale,
                optax.global_norm(moved),
                jax.tree.map(lambda leaf: leaf / scale,
                             producer_leaves(mu, self.layers)))

    def _reference(self, data):
        """The reference's loss, gradient norm, update norm and producers'
        gradients on the check's sample, once a seed."""
        if self.seed not in self.reference_cache:
            k = self.config["check"]
            per_shard = (self.chips, k["sequences_per_chip"], k["seq_len"])
            with jax.default_matmul_precision("highest"):
                loss, grad = reference.loss_and_grad(
                    self._params, *(x.reshape(per_shard) for x in data[:2]),
                    **self.reference_model)
            self.reference_cache.clear()
            self.reference_cache[self.seed] = (
                loss, reference.shards.norm(grad),
                reference.adamw_first_update_norm(
                    self._params, grad, self.adamw["lr"],
                    self.adamw["weight_decay"], self.adamw["eps"]),
                jax.device_get(producer_leaves(grad, self.layers)))
        return self.reference_cache[self.seed]

    def check(self):
        """As ``gpt_dp``'s, at the timed shape, with the producers' rows."""
        k = self.config["check"]
        shape = (self.chips * k["sequences_per_chip"], k["seq_len"])
        data = gpt_dp._batch(np.random.default_rng(self.seed + 1), shape,
                             self.cfg.vocab_size)
        ref_loss, ref_gnorm, ref_moved, ref_producers = self._reference(data)
        *numbers, producers = self.check_step(
            self._params, self._opt_state, hvd.shard_batch(data))
        loss, gnorm, moved = map(float, numbers)
        scan_off, kv_off = (_off(got, want) for got, want in zip(
            jax.device_get(producers), ref_producers))
        rows = [("loss", loss, ref_loss, LOSS_RTOL),
                ("gradient norm after the exchange", gnorm, ref_gnorm,
                 GNORM_RTOL),
                ("update norm", moved, ref_moved, UPDATE_RTOL),
                ("publishing scan layer's own gradients off the reference's",
                 1.0 + scan_off, 1.0, SCAN_PRODUCER_RTOL),
                ("publishing attention layer's key and value gradients off "
                 "the reference's", 1.0 + kv_off, 1.0, KV_PRODUCER_RTOL)]
        return lambda: rows
