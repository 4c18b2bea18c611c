"""Linear-attention layer: the share of the lanes the scan's four token tensors (``q``, ``k`` at the key heads, ``v``, ``o`` at the value heads) occupy in the ``hvd_gdn_*`` kernels that hold data, ``100 (Hk K + Hv V) / (Hk key_lanes + Hv value_lanes)``, from the program's own counts at trace time (``hvdtpu_spmd_gdn_layer_traces_total``'s head counts and sizes, ``hvdtpu_spmd_gdn_kernel_traces_total``'s ``key_lanes`` and ``value_lanes``): 100 where a head is whole lane tiles, 75 for heads of 96 by 192 carried at 128 by 256."""

LAYERS = "hvdtpu_spmd_gdn_layer_traces_total"
KERNELS = "hvdtpu_spmd_gdn_kernel_traces_total"


def read(ctx):
    import horovod_tpu as hvd

    families = hvd.metrics()
    layers = families.get(LAYERS, {}).get("samples", [])
    kernels = families.get(KERNELS, {}).get("samples", [])
    # A program without the counters, without a linear-attention layer, or
    # whose kernel counter has no lanes yet (the parent commit's): nothing
    # to read.
    if not layers or not kernels or "key_lanes" not in kernels[0][1]:
        return None
    held = carried = 0.0
    for _, layer, traces in layers:
        key_heads, heads, key_dim, width = (
            int(layer[name]) for name in ("key_heads", "value_heads",
                                          "key_dim", "value_dim"))
        # The lanes the kernels gave a head of this size: the next multiple
        # of a lane tile that some kernel call carried.
        lanes = {(int(k["key_lanes"]), int(k["value_lanes"]))
                 for _, k, _ in kernels
                 if int(k["key_lanes"]) >= key_dim
                 and int(k["value_lanes"]) >= width}
        if not lanes:
            return None
        key_lanes, value_lanes = min(lanes)
        held += traces * (key_heads * key_dim + heads * width)
        carried += traces * (key_heads * key_lanes + heads * value_lanes)
    return 100.0 * held / carried
