#!/usr/bin/env python3
"""A cell's correctness check over many seeds, in one process on the chip.

    chiprun --chips 1 -- python scripts/check_sweep.py --workload <cell> --seeds 10

For each seed it builds the cell's job as ``benchmarks/run.py`` does, runs
``check()`` and prints one JSON line with every row's relative error (and
what else the job kept of the check: tokens per expert, choices moved); the
last line gives each row's largest error. This is where the tolerances at the
top of a ``benchmarks/jobs/*.py`` come from. Lines are appended to
``chiprun_out/check_sweep.jsonl``.

``--variant NAME`` runs the same check on a program with one of its
mechanisms left out, on the shipped program's parameters (``VARIANTS``: the
band ignored, the selection bias out
of the choice or never updated, the weights' constant, the norms after the branches or the
shared expert dropped, the router's product in one bfloat16 pass; for a
linear-attention job the writing strength without its factor of two, ``q``
scaled for another head size, the norms before the branches instead of
after, a rotary embedding, no q/k norm, the running sums of the log decays
in bfloat16; for a CCA job the residual scaling or the routers' carried
state left out, the rotary embedding on the whole head, the MLP router's
products in one bfloat16 pass, the mix as its plain lines in bfloat16; for a
latent-attention job the logits scaled for the no-position part of a head
alone, no rotary embedding; for an early-routed job the router fed what the
experts read, SiLU for ReLU, the rotary embedding on every layer, the six
weights not renormalised, the early router's product in one bfloat16 pass,
the parameters rounded to bfloat16; for a latent-expert job ReLU for its
square, the experts fed the stream's first columns in place of the
down-projection; for a Kimi-delta-attention job the decay one a head, the
unbounded gate, the group limit left out, the head-wise gates left out, the
scan's decays, running sums and state in bfloat16, or the decays and the
state alone; for a block-diffusion job the noised rows let see their own
clean block, one kept tile left out of the grids' tables, or four experts a
row where the configuration says eight), against
the untouched reference: what a job's tolerances must catch. A variant
changes the job's ``GPTConfig`` after the job is built, before its step is
traced (or what the program's modules see, where no field says it); a job
without the field it needs fails by name.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _one_pass_dots(module):
    """``module``'s float32 products at the backend's default precision (on
    the TPU one bfloat16 pass of the MXU) where it asks for the highest: it
    sees a ``jax.numpy`` whose ``dot`` takes no notice of ``precision``.
    ``parallel/moe.py``: the expert layer's own router;
    ``models/decoder/experts.py``: the MLP router and the router that reads
    the block's input (no other line of it calls ``dot``). Returns what
    undoes it (``--variants`` runs several in one process)."""
    import jax.numpy as jnp
    from jax import lax

    class OnePass:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def dot(a, b, precision=None, **kw):
            return jnp.dot(a, b, precision=lax.Precision.DEFAULT, **kw)

    return _patched(module, "jnp", OnePass())


def _router_in_bfloat16():
    from horovod_tpu.parallel import moe

    return _one_pass_dots(moe)


def _mlp_router_in_bfloat16():
    from horovod_tpu.models.decoder import experts

    return _one_pass_dots(experts)


def _parameters_in_bfloat16(job) -> None:
    """The state held in bfloat16: the step reads parameters rounded to it
    and its updated parameters are rounded to it again (an update smaller
    than a parameter's last bit is lost), where the configuration states
    float32 master parameters."""
    import jax
    import jax.numpy as jnp

    def rounded(tree):
        return jax.tree.map(
            lambda p: p.astype(jnp.bfloat16).astype(p.dtype), tree)

    if not hasattr(job, "_step_with_aux"):    # a job without auxiliary terms
        whole = job._train_step

        def train_step(params, opt_state, data):
            params, opt_state, loss = whole(rounded(params), opt_state, data)
            return rounded(params), opt_state, loss

        job._train_step = train_step
        return
    real = job._step_with_aux

    def step(params, opt_state, data):
        (params, opt_state, loss), aux = real(rounded(params), opt_state,
                                              data)
        return (rounded(params), opt_state, loss), aux

    job._step_with_aux = step


def _norms_before(job) -> None:
    """The norms after the branches applied before them instead, the same
    weights: the block reads each under the name a norm before a branch
    has."""
    from horovod_tpu.models import gpt

    _replace(job, norms="pre")

    def loss(params, *data):
        layers = [{**lp, "mlp_norm": lp["mlp_post_norm"],
                   ("attn" if "wq" in lp else "gdn") + "_norm":
                   lp["mixer_post_norm"]} for lp in params["layers"]]
        return gpt.loss_fn({**params, "layers": layers}, *data, job.cfg)

    job._loss = loss


def _q_scaled_for_heads_of_128() -> None:
    """The linear mixer's ``q`` times ``1 / sqrt(128)`` (the lanes a key
    head rides) where the model scales by one over the root of its size.
    Since PR 50 the scan's kernels apply that scale to the rows they norm:
    ``gated_delta_chunked`` finds a ``_chunk_local`` that hands them the
    lanes' scale in place of the head's."""
    from horovod_tpu.ops import gated_delta

    real = gated_delta._chunk_local
    gated_delta._chunk_local = lambda scale, q, *rest: real(
        q.shape[-1] ** -0.5, q, *rest)


def _decay_sums_in_bfloat16() -> None:
    """The chunk's running sums of the log decays made in bfloat16:
    ``ops/gated_delta.py`` sees a ``jax.numpy`` whose ``cumsum`` rounds."""
    import jax.numpy as jnp
    from horovod_tpu.ops import gated_delta

    class RoundedSums:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def cumsum(x, axis):
            return jnp.cumsum(x.astype(jnp.bfloat16), axis=axis).astype(
                jnp.float32)

    gated_delta.jnp = RoundedSums()


def _no_router_state() -> None:
    """Every MLP router as a stage's first: no state from the layer
    before."""
    from horovod_tpu.models.decoder import experts

    real = experts._mlp_router
    experts._mlp_router = lambda cfg, r, h, state: real(cfg, r, h, None)


def _mix_in_bfloat16() -> None:
    """A CCA mixer's means, L2 norms, temperature, rotary embedding and
    grouped sums in bfloat16 where the program's kernels compute them in
    float32 from bfloat16 inputs: the mixer gets, in place of
    ``ops/cca.py::cca_mix``, the plain lines the kernels are held to
    (``cca_mix_reference``), and those see a ``jax.numpy`` whose
    ``float32`` is ``bfloat16``."""
    import jax.numpy as jnp
    from horovod_tpu.models.decoder.mixers import cca as mixer
    from horovod_tpu.ops import cca

    class Rounded:
        float32 = jnp.bfloat16

        def __getattr__(self, name):
            return getattr(jnp, name)

    def mix(*args, **kw):
        cca.jnp = Rounded()
        try:
            return cca.cca_mix_reference(*args, **kw)
        finally:
            cca.jnp = jnp

    mixer.cca_mix = mix


def _relu_for_its_square() -> None:
    """The un-gated experts' (and the shared expert's) activation ReLU where
    the model squares it."""
    import jax
    from horovod_tpu.parallel import moe

    moe.ACTIVATIONS["relu2"] = jax.nn.relu


def _no_latent_down(job) -> None:
    """The routed experts fed the normed stream's first ``moe_latent_dim``
    columns as they are, where the model projects the stream down to the
    latent: the block reads an identity's leading columns under the
    projection's name."""
    import jax.numpy as jnp

    real = job._loss

    def loss(params, *data):
        layers = [{**lp, "moe": {**lp["moe"], "latent_down": jnp.eye(
            *lp["moe"]["latent_down"].shape, dtype=jnp.float32)}}
            if "moe" in lp else lp for lp in params["layers"]]
        return real({**params, "layers": layers}, *data)

    job._loss = loss


def _patched(module, name: str, value):
    """``module.name`` set to ``value``; returns what sets it back (a
    process that runs several variants in turn, ``--variants``)."""
    real = getattr(module, name)
    setattr(module, name, value)
    return lambda: setattr(module, name, real)


def _own_clean_block():
    """The block-diffusion mask with its second clause one block too wide:
    a noised row sees its own clean block, the token it is to predict among
    its keys (``benchmarks/tests/test_bd_faults.py`` plants the same)."""
    from benchmarks.reference import gpt_bd_moe_dp as reference
    from horovod_tpu.ops import flash_attention as fa

    return _patched(fa.Mask, "keep", reference.own_clean_block_keep)


def _tile_dropped():
    """The block-diffusion grids' tables less one tile, the last noised
    query tile's first clean key tile: the one control of that cell that
    reads ``correct`` on some seeds (its job's header says what no row
    sees)."""
    from benchmarks.reference import gpt_bd_moe_dp as reference
    from horovod_tpu.ops import flash_attention as fa

    return _patched(fa.Mask, "tile_kept",
                    reference.tile_dropped(fa.Mask.tile_kept))


def _memory_after_gate():
    """A publishing Mamba-1 layer hands on ``y * silu(z)`` where the model
    hands on the scan's output before the gate."""
    from horovod_tpu.models.decoder.mixers import s6

    return _patched(s6, "_published", lambda y, gated: gated)


def _kv_from_the_window_layer(job) -> None:
    """The cross layers read the keys and values of the first differential
    attention layer of the stack (a window layer's) where the model's read
    the full layer's."""
    plan = list(job.cfg.plan)
    first, last = (i for i, spec in enumerate(plan)
                   if spec.mixer == "diff_attention")
    plan[first] = dataclasses.replace(plan[first], publishes=("diff_kv",))
    plan[last] = dataclasses.replace(plan[last], publishes=())
    _replace(job, layers=tuple(plan))


def _no_norm_bias():
    """The LayerNorms without their bias."""
    from horovod_tpu.models.decoder import parts

    real = parts._layernorm
    return _patched(parts, "_layernorm", lambda x, w, b, dtype, eps: real(
        x, w, 0.0 * b, dtype, eps))


def _s6_decays_in_bfloat16():
    """The selective scan's decays ``exp(dt A)`` rounded to bfloat16:
    ``ops/s6.py``'s kernels see a ``jax.numpy`` whose ``exp`` rounds."""
    import jax.numpy as jnp
    from horovod_tpu.ops import s6

    class RoundedDecays:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            return jnp.exp(x).astype(jnp.bfloat16).astype(jnp.float32)

    return _patched(s6, "jnp", RoundedDecays())


def _lambda_init_of(depth_of):
    """Every differential layer's ``lambda_init`` from ``depth_of(the
    published index)``."""
    from horovod_tpu.models.decoder.mixers import diff_attention

    real = diff_attention.lambda_init
    return _patched(diff_attention, "lambda_init",
                    lambda depth: real(depth_of(depth)))


def _kda_decay_a_head():
    """A Kimi-delta-attention layer's decay made one a head, the key
    channels' mean, where the model's is one a channel."""
    import jax.numpy as jnp
    from horovod_tpu.models.decoder.mixers import kda

    real = kda.log_decay

    def a_head(cfg, p, f):
        g = real(cfg, p, f)
        by_head = g.reshape(g.shape[:-1] + (cfg.kda_heads, cfg.kda_key_dim))
        return jnp.broadcast_to(jnp.mean(by_head, axis=-1, keepdims=True),
                                by_head.shape).reshape(g.shape)

    return _patched(kda, "log_decay", a_head)


def _kda_gate_unbounded():
    """A Kimi-delta-attention layer's log decay in the gated delta rule's
    form, ``-exp(A_log_h) softplus(h W_f + dt_bias)``, held at the bound the
    scan is told of (what a sub-block carries), where the model's is
    ``lower_bound * sigmoid(.)``."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.decoder.mixers import kda

    def unbounded(cfg, p, f):
        a = jnp.repeat(jnp.exp(p["A_log"]), cfg.kda_key_dim)
        return jnp.maximum(
            -a * jax.nn.softplus(f.astype(jnp.float32) + p["dt_bias"]),
            cfg.kda_lower_bound)

    return _patched(kda, "log_decay", unbounded)


def _no_head_gates(job):
    """Neither mixer's output under its gate a head: the KDA layers' normed
    output as it is, the MLA layer's attention as it is."""
    from horovod_tpu.models.decoder.mixers import kda

    _replace(job, mla_head_gate=False)
    return _patched(kda, "head_gate", lambda y, open_: y)


def _kda_in_bfloat16(sums: bool):
    """Kimi delta attention's decays rounded to bfloat16 (``ops/kda.py``
    sees a ``jax.numpy`` whose ``exp`` rounds) and the state its recurrence
    carries from chunk to chunk, and that state's cotangent, held in
    bfloat16; under ``sums`` the running sums of the decays' logarithms
    too (that ``jax.numpy``'s ``dot`` at a stated precision, the triangle
    of ones that sums the log decays and turns their cotangents round,
    rounds as well): what the configuration states as float32, in the
    precision below."""
    import jax.numpy as jnp
    from horovod_tpu.ops import kda

    class RoundedDecays:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            return jnp.exp(x).astype(jnp.bfloat16).astype(jnp.float32)

        @staticmethod
        def dot(a, b, precision=None, **kw):
            out = jnp.dot(a, b, precision=precision, **kw)
            return out if precision is None or not sums \
                else out.astype(jnp.bfloat16).astype(jnp.float32)

    undo = (_patched(kda, "jnp", RoundedDecays()),
            _patched(kda, "_STATE_DTYPE", jnp.bfloat16))
    return lambda: [back() for back in undo]


# name -> what it does to a job already built (its step not yet traced);
# what it returns, if anything, undoes it
VARIANTS = {
    "full_causal": lambda job: _replace(job, layers=tuple(
        dataclasses.replace(spec, window=None) for spec in job.cfg.plan)),
    "no_bias": lambda job: _replace(job, router_bias=False),
    "no_bias_update": lambda job: setattr(job, "bias_rate", 0.0),
    "no_route_scale": lambda job: _replace(job, route_scale=1.0),
    "no_post_norm": lambda job: _replace(job, post_norm=False),
    "no_shared": lambda job: _replace(job, shared_expert_dim=0),
    "router_bf16": lambda job: _router_in_bfloat16(),
    "bd_own_clean_block": lambda job: _own_clean_block(),
    "bd_tile_dropped": lambda job: _tile_dropped(),
    "top_k_4": lambda job: _replace(job, experts_per_token=4),
    "beta_sigmoid": lambda job: _replace(job, gdn_allow_neg_eigval=False),
    "q_scale_128": lambda job: _q_scaled_for_heads_of_128(),
    "norms_before": _norms_before,
    "rope": lambda job: _replace(job, rope=True),
    "no_qk_norm": lambda job: _replace(job, qk_norm=False),
    "decay_sums_bf16": lambda job: _decay_sums_in_bfloat16(),
    "no_residual_scaling": lambda job: _replace(job, residual_scaling=False),
    "no_router_state": lambda job: _no_router_state(),
    "whole_head_rotary": lambda job: _replace(job, rotary_dim=None),
    "router_mlp_bf16": lambda job: _mlp_router_in_bfloat16(),
    "cca_mix_bf16": lambda job: _mix_in_bfloat16(),
    "mla_scale_nope": lambda job: _replace(
        job, attention_multiplier=job.cfg.head_dim ** -0.5),
    "mla_no_rope": lambda job: _replace(job, layers=tuple(
        dataclasses.replace(spec, rope=False) for spec in job.cfg.plan)),
    "router_after_mixer": lambda job: _replace(job, router_reads="ff_input"),
    "silu": lambda job: _replace(job, expert_activation="silu"),
    "rope_everywhere": lambda job: _replace(job, layers=tuple(
        dataclasses.replace(spec, rope=True) for spec in job.cfg.plan)),
    "no_renormalize": lambda job: _replace(job, renormalize_experts=False),
    "router_early_bf16": lambda job: _mlp_router_in_bfloat16(),
    "params_bf16": _parameters_in_bfloat16,
    "relu_not_squared": lambda job: _relu_for_its_square(),
    "no_latent_down": _no_latent_down,
    "memory_after_gate": lambda job: _memory_after_gate(),
    "kv_from_window_layer": _kv_from_the_window_layer,
    "lambda_depth_local": lambda job: _replace(job, layers=tuple(
        spec if spec.depth is None else dataclasses.replace(spec, depth=i)
        for i, spec in enumerate(job.cfg.plan))),
    "lambda_init_zero": lambda job: _lambda_init_of(lambda depth: 0),
    "window_plus_one": lambda job: _replace(job, layers=tuple(
        spec if spec.window is None
        else dataclasses.replace(spec, window=spec.window + 1)
        for spec in job.cfg.plan)),
    "no_norm_bias": lambda job: _no_norm_bias(),
    "s6_decays_bf16": lambda job: _s6_decays_in_bfloat16(),
    "kda_decay_a_head": lambda job: _kda_decay_a_head(),
    "kda_gate_unbounded": lambda job: _kda_gate_unbounded(),
    "no_groups": lambda job: _replace(job, router_groups=1,
                                      router_groups_kept=1),
    "no_head_gates": _no_head_gates,
    "kda_state_bf16": lambda job: _kda_in_bfloat16(sums=True),
    "kda_decays_state_bf16": lambda job: _kda_in_bfloat16(sums=False),
}


def _replace(job, **fields) -> None:
    job.cfg = dataclasses.replace(job.cfg, **fields)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=10)
    parser.add_argument("--first-seed", type=int, default=2147484000)
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--variant", choices=sorted(VARIANTS))
    parser.add_argument(
        "--variants", nargs="+", choices=sorted(VARIANTS), default=(),
        help="for each seed the shipped program and then each of these, "
        "each undone before the next (a job with a reference_cache "
        "computes its reference once a seed)")
    args = parser.parse_args()
    from benchmarks import run

    bench = run.load_json(ROOT, "BENCHMARK.json")
    cell = run.find(bench["workloads"], args.workload, "workload")
    data = os.path.join(run.HERE, "tests", "data") if args.rehearsal \
        else run.HERE
    config = run.load_json(data, "configs", os.path.basename(
        run.find(bench["configs"], cell["config"], "config")["file"]))
    traffic = run.load_json(data, "traffic", cell["traffic"] + ".json")
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + (
            f" --xla_force_host_platform_device_count={cell['chips']}")
    else:
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    import jax
    import numpy as np

    import horovod_tpu as hvd

    print(f"platform: {jax.devices()[0].platform} device_kind: "
          f"{jax.devices()[0].device_kind} devices: {len(jax.devices())}",
          flush=True)
    hvd.init()
    jobs = importlib.import_module(f"benchmarks.jobs.{config['job']}")
    worst: dict = {}
    correct = 0
    references: dict = {}   # --variants: a seed's reference, computed once
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "check_sweep.jsonl"),
              "a") as out:
        for seed, variant in (
                (seed, variant)
                for seed in range(args.first_seed,
                                  args.first_seed + args.seeds)
                for variant in ((None, *args.variants) if args.variants
                                else (args.variant,))):
            if args.variants:
                # A kernel's call is traced once for a shape (``jit(...,
                # inline=True)``): a variant that changes what a kernel's
                # module sees needs it traced anew, and so does the next.
                jax.clear_caches()
            job = jobs.Job(config, traffic, seed)
            if args.variants and hasattr(job, "reference_cache"):
                job.reference_cache = references
            undo = None
            if variant:
                # The shipped program's parameters, made before; the
                # optimizer state comes after the reference, as in run.py
                # (16 bytes a parameter and the reference's gradient do
                # not fit a chip together).
                job._params
                undo = VARIANTS[variant](job)
            line = {"workload": args.workload, "seed": seed,
                    "rehearsal": args.rehearsal, "variant": variant}
            for what, got, want, rtol in job.check()():
                err = abs(got - want) / abs(want)
                line[what] = {"program": got, "reference": want, "rel": err,
                              "allowed": rtol}
                if variant == args.variant:     # --variants: the shipped
                    worst[what] = max(worst.get(what, 0.0), err)
            # ``run.py``'s own rule on these rows: off by no more than the
            # limit, each of them.
            line["missed"] = [what for what, row in line.items()
                              if isinstance(row, dict)
                              and not row["rel"] <= row["allowed"]]
            line["correct"] = not line["missed"]
            correct += line["correct"] and variant == args.variant
            if callable(undo):
                undo()
            counts = getattr(job, "expert_counts", None)
            if counts is not None:
                line["busiest_over_mean"] = float(
                    (counts.max(-1) / counts.mean(-1)).max())
                line["choices_moved"] = job.choices_moved
                line["choices"] = int(np.sum(counts))
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            # The next seed's state needs the room this one's holds.
            del job
            for array in jax.live_arrays():
                array.delete()
        last = {"workload": args.workload, "seeds": args.seeds,
                "variant": args.variant or list(args.variants) or None,
                "largest_rel": worst,
                "seeds_correct": correct}
        print(json.dumps(last), flush=True)
        out.write(json.dumps(last) + "\n")
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
