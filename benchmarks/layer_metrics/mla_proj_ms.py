"""Latent attention layer: device time per step of the operations under ``attn/mla_proj`` (the query projection, the down-projection to the latent and the shared rotary key, the latent's norm, the up-projection to the heads' keys and values, the output projection), all passes."""

from benchmarks.layer_metrics.mla_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("mla_proj",))
