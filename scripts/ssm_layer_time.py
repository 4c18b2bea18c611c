#!/usr/bin/env python3
"""Time one recurrent block alone on the chip, as a checkpointed step runs
it, and split its time by scope.

    chiprun --chips 1 -- python scripts/ssm_layer_time.py [--repo DIR] [--trace]
    chiprun --chips 1 -- python scripts/ssm_layer_time.py --kind gdn [--trace]
    chiprun --chips 1 -- python scripts/ssm_layer_time.py --kind kda [--trace] [--repo DIR]
    chiprun --chips 1 -- python scripts/ssm_layer_time.py --kind head [--rows R ...]

At the ``granite-4.0-h-micro_s4096`` cell's shapes (2 x 4096 tokens of 2048;
64 heads of 64, state 128, one group, chunk 256; a gated feed-forward of
8192; bfloat16) it builds ``--layers`` Mamba-2 blocks of ``models/gpt.py``
under ``remat="full"`` and times, host clock around ``block_until_ready``,
their forward pass and forward with backward (the gradient of every
parameter and of the input). ``--trace`` also writes a device trace of four
calls of each under ``chiprun_out/ssm_layer_trace/<tag>[_fwd]/`` and prints
``benchmarks/scope_reduce.py``'s split of it: what XLA puts around the
scan's kernels (copies, layout changes) shows there and in no kernel's own
time. ``--repo DIR`` times another checkout (a copy of the parent commit) by
the same script. One JSON line, also appended to
``chiprun_out/ssm_layer_time.jsonl``.

``--kind gdn`` times gated-delta-rule blocks instead, at the
``qwen3-next-80b-a3b_s4096`` cell's shapes (4 x 4096 tokens of 2048; 16 key
and 32 value heads of 128, chunk 64; behind each mixer the cell's expert
block: 32 of 512 experts of 512 held, 10 a token, a shared expert): the
number the next change to ``ops/gated_delta.py`` is measured against first.
With ``--trace`` the split's innermost-scope rows ``hvd_gdn_fwd`` and
``hvd_gdn_bwd`` are the chunk-local kernels (since PR 50 with the L2 norms
of ``q`` and ``k`` inside: ``--repo`` a copy of an older commit times the
norms as XLA ran them around the kernels) and the row ``scan`` what XLA
runs around them (the running sums, the recurrence's small operands);
``scripts/gdn_kernel_time.py`` times the kernels alone. ``--kind gdn_dense``
is the ``olmo-hybrid-7b_s8192`` cell's block (1 x 8192 tokens of 3840; 30
key and 30 value heads of 96 by 192, ``beta`` in (0, 2); the norm after each
branch; a gated feed-forward of 11008).

``--kind kda`` is the ``ling-3.0-flash_s8192`` cell's first block (1 x 8192
tokens of 2560; 32 Kimi-delta-attention heads of 128 by 128, chunk 64, the
gate bounded at -5; the dense gated feed-forward of 6144): the number the
next change to ``ops/kda.py`` is measured against first. With ``--trace`` it
also prints ``kda_ops_ms_a_layer``: the ``hvd_kda_*`` kernels' and the
``reduce_precision`` passes' time and calls a layer a call of the forward
with backward (a block that keeps what the scan's forward kernels write runs
each once, PR 64; ``--repo`` a copy of an older commit runs them twice).
Since PR 68 what ``hvd_kda_fwd`` writes and the block keeps includes the
float32 ``T``, which the rule hands ``hvd_kda_bwd``: that row holds no
inverse (8.48 -> 5.14 ms a layer, ``hvd_kda_fwd`` 4.72 either way).

``--kind head`` times no block but the head and the loss alone (``--tokens``
rows of ``--embed`` against ``--vocab``, ``--tied``, ``--scaling``; without
``--vocab`` the four cells' shapes whose head is a sixth of the step or
more): the form ``models/gpt.py`` had before PR 41 (whole float32 logits,
``log_softmax``, autodiff) against ``gpt._head_loss``, the loss alone and
the loss with the gradients of the rows and the matrix, at the rows a block
``gpt.head_loss_rows`` gives and at each of ``--rows``. It holds
the compiled rule to the old form's numbers (``*_err``: the largest
difference over the old form's largest value) and exits 1 beyond
``--tolerance``. A line a shape a block size.

With ``--trace`` every other kind also prints ``conv_ms_a_layer``: the mixers'
``conv`` scope (``kda_conv`` in a ``"kda"`` mixer; the causal depthwise
convolution, its SiLU and the split after it:
``ops/conv.py::causal_conv_silu``, the kernels ``hvd_conv_fwd`` and
``hvd_conv_bwd``) forward, recomputed and backward, ms a layer a call.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timed(fn, *args, reps: int = 10) -> float:
    """ms a call: the mean of ``reps`` calls after two warm ones."""
    import jax
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def named_ops_ms(path: str, calls: int, layers: int, pattern: str) -> dict:
    """``{name: [ms, calls]}`` a layer a call of chip 0's operations whose
    name matches ``pattern`` in the trace at ``path``, by the name without
    XLA's number."""
    from benchmarks import trace_reduce
    out: dict = {}
    for op in trace_reduce.matching(trace_reduce.first_device(
            trace_reduce.read_xplane(path, {})), pattern):
        row = out.setdefault(trace_reduce.group_name(op.name), [0.0, 0.0])
        row[0] += 1e3 * (op.end - op.start) / (calls * layers)
        row[1] += 1 / (calls * layers)
    return {name: [round(ms, 4), n] for name, (ms, n) in sorted(out.items())}


def conv_scope_ms(path: str, calls: int, layers: int) -> dict:
    """ms a layer a call of chip 0's operations under a mixer's ``conv``
    scope in the trace at ``path``, by the pass they run in."""
    from benchmarks import scope_reduce, trace_reduce
    names = scope_reduce.program_names(path)
    ms = {"forward": 0.0, "recomputation": 0.0, "backward": 0.0}
    for op in trace_reduce.first_device(trace_reduce.read_xplane(path, {})):
        op_name, cls = names.get(op.name, ("", "unscoped"))
        if cls in ms and any(scope.endswith("conv") for scope
                             in scope_reduce.scope_of(op_name)):
            ms[cls] += 1e3 * (op.end - op.start) / (calls * layers)
    return ms


# (tokens, embed, vocab, tied, logits scaling): granite-4.0-h-micro_s4096,
# starcoder2-3b_s4096 and _s512, olmoe-1b-7b_s4096, qwen3-next-80b-a3b_s4096.
HEAD_SHAPES = ((8192, 2048, 100352, True, 8.0), (8192, 3072, 49152, False, 1.0),
               (8192, 2048, 50304, False, 1.0), (16384, 2048, 18992, False, 1.0))


def head_rows(gpt, shape, forced, tolerance: float):
    """One line a block size for the head and the loss alone at ``shape``."""
    import jax
    import jax.numpy as jnp
    tokens, embed, vocab, tied, scaling = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (tokens, embed), jnp.bfloat16)
    w = 0.02 * jax.random.normal(
        keys[1], (vocab, embed) if tied else (embed, vocab), jnp.float32)
    targets = jax.random.randint(keys[2], (tokens,), 0, vocab)
    targets = jnp.where(jnp.arange(tokens) % 17 == 0, -1, targets)

    def old(x, w):
        logits = gpt._logits(x, w.astype(x.dtype), tied, scaling)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(targets, 0)[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(targets >= 0, picked, 0.0)) / tokens

    def rule(rows, x, w):
        return gpt._head_loss(x, w.astype(x.dtype), targets, None, tied,
                              scaling, rows) / tokens

    grad = functools.partial(jax.value_and_grad, argnums=(0, 1))
    old_fwd, old_both = jax.jit(old), jax.jit(grad(old))
    want = old_both(x, w)
    base = {"kind": "head", "tokens": tokens, "embed": embed, "vocab": vocab,
            "tied": tied, "scaling": scaling,
            "old_fwd_ms": timed(old_fwd, x, w),
            "old_fwd_bwd_ms": timed(old_both, x, w)}
    del old_fwd, old_both
    given = gpt.head_loss_rows(tokens, vocab)
    for rows in dict.fromkeys([given, *forced]):
        both = jax.jit(grad(functools.partial(rule, rows)))
        got = both(x, w)
        err = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                     / jnp.max(jnp.abs(b)))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(
                   jax.tree.map(lambda t: t.astype(jnp.float32), want)))]
        out = dict(base, rows=rows, blocks=-(-tokens // rows),
                   given=rows == given,
                   fwd_ms=timed(jax.jit(functools.partial(rule, rows)), x, w),
                   fwd_bwd_ms=timed(both, x, w),
                   loss_err=err[0], dx_err=err[1], dw_err=err[2],
                   ok=max(err) <= tolerance)
        yield out


def head_main(args, gpt, device) -> int:
    shapes = HEAD_SHAPES if args.vocab is None else (
        (args.tokens, args.embed, args.vocab, args.tied, args.scaling),)
    ok = True
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    for shape in shapes:
        for out in head_rows(gpt, shape, args.rows, args.tolerance):
            line = json.dumps(dict(out, tag=args.tag,
                                   device_kind=device.device_kind))
            print(line, flush=True)
            with open(os.path.join(HERE, "chiprun_out",
                                   "ssm_layer_time.jsonl"), "a") as f:
                f.write(line + "\n")
            ok = ok and out["ok"]
    return 0 if ok else 1


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--repo", default=HERE)
    parser.add_argument("--tag", default="change")
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--embed", type=int, default=2048)
    parser.add_argument("--mlp", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--state", type=int, default=128)
    parser.add_argument("--groups", type=int, default=1)
    parser.add_argument("--chunk", type=int, default=256)
    parser.add_argument("--kind", choices=("ssm", "gdn", "gdn_dense", "kda",
                                           "head"), default="ssm")
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--vocab", type=int)
    parser.add_argument("--tied", action="store_true")
    parser.add_argument("--scaling", type=float, default=1.0)
    parser.add_argument("--rows", type=int, nargs="*", default=[],
                        help="--kind head: block sizes to time beside the "
                        "one the shapes give")
    parser.add_argument("--tolerance", type=float, default=2e-2)
    parser.add_argument("--trace", action="store_true")
    parser.add_argument(
        "--conv-minor", choices=("channels", "tokens"),
        help="force the axis the convolution's kernels take on the lanes "
        "(the mixers pick it; this is how their pick was timed)")
    args = parser.parse_args()
    if args.kind == "gdn" and args.batch == 2:
        args.batch = 4
    if args.kind == "gdn_dense":
        args.batch, args.seq, args.embed, args.mlp = 1, 8192, 3840, 11008
    if args.kind == "kda":
        args.batch, args.seq, args.embed, args.mlp = 1, 8192, 2560, 6144
    root = os.path.abspath(args.repo)
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import gpt

    device = jax.devices()[0]
    print(f"platform: {device.platform} device_kind: {device.device_kind} "
          f"repo: {root}", flush=True)
    if args.kind == "head":
        return head_main(args, gpt, device)
    if args.conv_minor:
        # ops/conv.py's, whatever ``--repo``: in the mixers' modules, or in
        # ``models/gpt.py`` of a checkout from before PR 57.
        def forced(shipped):
            return lambda *a, minor=None, **k: shipped(
                *a, minor=args.conv_minor, **k)

        for module in [m for name, m in sys.modules.items()
                       if name.startswith("horovod_tpu.models.")
                       and hasattr(m, "causal_conv_silu")]:
            module.causal_conv_silu = forced(module.causal_conv_silu)
    if args.kind == "gdn":
        cfg = gpt.GPTConfig(
            vocab_size=256, num_layers=args.layers, num_heads=16,
            num_kv_heads=2, head_dim=256, embed_dim=args.embed, mlp_dim=512,
            dtype=jnp.bfloat16, tp_axis=None, sp_axis=None,
            attention="flash", remat="full", norm_zero_centered=True,
            layer_kinds=("gdn",) * args.layers, gdn_key_heads=16,
            gdn_value_heads=32, gdn_key_dim=128, gdn_value_dim=128,
            gdn_chunk=64, moe_every=1, num_experts=512, experts_held=32,
            experts_per_token=10, renormalize_experts=True,
            shared_expert_dim=512)
    elif args.kind == "gdn_dense":
        cfg = gpt.GPTConfig(
            vocab_size=256, num_layers=args.layers, num_heads=30,
            num_kv_heads=30, head_dim=128, embed_dim=args.embed,
            mlp_dim=args.mlp, dtype=jnp.bfloat16, tp_axis=None, sp_axis=None,
            attention="flash", remat="full", norm_eps=1e-6, norms="post",
            qk_norm=True, rope=False, gated_mlp=True,
            layer_kinds=("gdn",) * args.layers, gdn_key_heads=30,
            gdn_value_heads=30, gdn_key_dim=96, gdn_value_dim=192,
            gdn_chunk=64, gdn_allow_neg_eigval=True, tie_embeddings=False)
    elif args.kind == "kda":
        cfg = gpt.GPTConfig(
            vocab_size=256, num_layers=args.layers, num_heads=32,
            head_dim=128, embed_dim=args.embed, mlp_dim=args.mlp,
            dtype=jnp.bfloat16, tp_axis=None, sp_axis=None,
            attention="flash", remat="full", norm_eps=1e-6,
            layers=(gpt.LayerSpec(mixer="kda", ff="gated"),) * args.layers,
            kda_heads=32, kda_key_dim=128, kda_value_dim=128, kda_conv=4,
            kda_chunk=64, kda_lower_bound=-5.0)
    else:
        cfg = gpt.GPTConfig(
            vocab_size=256, num_layers=args.layers, num_heads=32,
            num_kv_heads=8, head_dim=64, embed_dim=args.embed,
            mlp_dim=args.mlp, dtype=jnp.bfloat16, tp_axis=None, sp_axis=None,
            attention="flash", norm_eps=1e-5, remat="full", gated_mlp=True,
            rope=False, tie_embeddings=True, residual_multiplier=0.22,
            layer_kinds=("ssm",) * args.layers, ssm_heads=args.heads,
            ssm_head_dim=args.head_dim, ssm_state=args.state,
            ssm_groups=args.groups, ssm_conv=4, ssm_chunk=args.chunk)
    layers = gpt.init_params(jax.random.PRNGKey(0), cfg)["layers"]
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.batch, args.seq, args.embed), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(args.seq, dtype=jnp.int32),
                                 (args.batch, args.seq))
    block = gpt._block_fn(cfg)

    def blocks(layers, x):
        for i, (spec, lp) in enumerate(zip(cfg.plan, layers)):
            with jax.named_scope(f"layer{i}"):
                x, *_ = block(cfg, spec, lp, x, positions)
        return jnp.sum(jnp.sin(x.astype(jnp.float32)))

    fwd = jax.jit(blocks)
    both = jax.jit(jax.value_and_grad(blocks, argnums=(0, 1)))
    out = {"tag": args.tag, "kind": args.kind, "layers": args.layers,
           "device_kind": device.device_kind,
           "fwd_ms_a_layer": timed(fwd, layers, x) / args.layers,
           "fwd_bwd_ms_a_layer": timed(both, layers, x) / args.layers}
    line = json.dumps(out)
    print(line, flush=True)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "ssm_layer_time.jsonl"),
              "a") as f:
        f.write(line + "\n")
    if args.trace:
        sys.path.insert(0, HERE)
        from benchmarks import scope_reduce, trace_reduce
        log_dir = os.path.join(HERE, "chiprun_out", "ssm_layer_trace",
                               args.tag)
        with jax.profiler.trace(log_dir):
            for _ in range(4):
                jax.block_until_ready(both(layers, x))
        print(scope_reduce.describe(trace_reduce.find_xplane(log_dir)),
              flush=True)
        with jax.profiler.trace(log_dir + "_fwd"):
            for _ in range(4):
                jax.block_until_ready(fwd(layers, x))
        print(scope_reduce.describe(
            trace_reduce.find_xplane(log_dir + "_fwd")), flush=True)
        print(json.dumps({
            "tag": args.tag, "kind": args.kind, "conv_ms_a_layer": {
                name: round(ms, 4) for name, ms in conv_scope_ms(
                    trace_reduce.find_xplane(log_dir), 4,
                    args.layers).items()}}), flush=True)
        if args.kind == "kda":
            print(json.dumps({
                "tag": args.tag, "kind": args.kind,
                "kda_ops_ms_a_layer": named_ops_ms(
                    trace_reduce.find_xplane(log_dir), 4, args.layers,
                    r"^hvd_kda_|reduce[-_]precision")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
