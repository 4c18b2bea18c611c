"""GPT training with combined parallelism (TPU-first; no reference analog —
Horovod is data-parallel only, SURVEY.md §2.7).

Composes data + tensor + sequence parallelism over one mesh, with ring
attention for long sequences:

    python examples/gpt_parallel.py --dp 2 --tp 2 --sp 2
"""

import argparse

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--sp", type=int, default=2)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--embed-dim", type=int, default=128)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--attention", default="ring",
                        choices=["ring", "ulysses"],
                        help="how attention crosses the sp axis: 'ring' "
                             "passes K/V blocks round it (ppermute); "
                             "'ulysses' trades sequence for heads "
                             "(all-to-all; needs heads divisible by tp*sp) "
                             "and runs the flash kernel on each device")
    args = parser.parse_args()

    hvd.init(mesh_shape={"dp": args.dp, "tp": args.tp, "sp": args.sp})
    cfg = gpt.GPTConfig(
        vocab_size=512, num_layers=args.layers, embed_dim=args.embed_dim,
        num_heads=args.heads, head_dim=args.embed_dim // args.heads,
        mlp_dim=args.embed_dim * 4, tp_axis="tp", sp_axis="sp",
        attention=args.attention, dtype=jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    params = gpt.init_params(rng, cfg)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)

    batch = 4 * args.dp
    tokens = jax.random.randint(rng, (batch, args.seq_len), 0, 512)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(args.seq_len),
                                 (batch, args.seq_len))

    def fwd_bwd(p, t, tg, pos):
        # Per-dp-shard loss, averaged over dp to the global mean; gradient
        # allreduce over dp makes the grads replicated there.
        loss = gpt.loss_fn(p, t, tg, pos, cfg)
        loss = hvd.allreduce_p(loss, op=hvd.Sum, axis="dp") / args.dp
        grads = jax.grad(lambda q: gpt.loss_fn(q, t, tg, pos, cfg))(p)
        grads = hvd.allreduce_gradients(grads, op=hvd.Average)
        return loss, grads

    step = hvd.run_step(
        fwd_bwd,
        in_specs=(gpt.param_specs(cfg), P("dp", "sp"), P("dp", "sp"),
                  P("dp", "sp")),
        out_specs=(hvd.REPLICATED, gpt.param_specs(cfg)))

    update = jax.jit(lambda g, s, p: opt.update(g, s, p))
    for i in range(args.steps):
        loss, grads = step(params, tokens, targets, positions)
        updates, opt_state = update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if hvd.rank() == 0:
            print(f"step {i}: loss {float(loss):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
