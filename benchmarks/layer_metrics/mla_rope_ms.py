"""Latent attention layer: device time per step of the operations under ``attn/mla_rope`` (the rotary embedding on the queries' rotary part and on the shared key, that key's broadcast to every head, the concatenations that make ``q`` and ``k`` whole, the split of the value), all passes: what making the kernels' operands costs beside the kernels."""

from benchmarks.layer_metrics.mla_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("mla_rope",))
