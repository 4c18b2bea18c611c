"""The block-diffusion description of ``ops/flash_attention.py::Mask``
(training by diffusion over blocks: the noised and the clean copy of a
sequence as one ``2 L``-row pass): the kernels in interpret mode against the
dense reference under the same mask, the tables of kept tiles against a
brute force over ``keep``, the refusals, and the tables of the masks that
were there before it, unchanged."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.attention import (block_diffusion_mask,
                                       default_attention, repeat_kv_heads)


def _padded(rows: int) -> int:
    """The rows the kernels pad a sequence to."""
    return rows + (-rows) % 128


def _qkv(length, heads, kv_heads, dim=32, batch=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (batch, 2 * length, h, dim),
                                   jnp.float32)
                 for key, h in zip(keys, (heads, kv_heads, kv_heads)))


# L = 128: two 128-row tiles, no padding; L = 96: 192 rows padded to 256,
# the halves' boundary inside the first tile; L = 320: 640 rows, five tiles,
# the boundary in the middle of the third.
@pytest.mark.parametrize("length", [128, 96, 320])
@pytest.mark.parametrize("block", [1, 4, 32])
@pytest.mark.parametrize("heads, kv_heads", [(8, 1), (4, 4)])
def test_kernels_agree_with_the_dense_path(heads, kv_heads, block, length):
    """Forward, dQ, dK and dV of the flash kernels against
    ``default_attention`` under the same mask, K/V at their own head count
    against K/V tiled up."""
    q, k, v = _qkv(length, heads, kv_heads)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_diffusion=block,
                                  _blocks=(128, 128))

    def dense(q, k, v):
        return default_attention(q, repeat_kv_heads(k, heads),
                                 repeat_kv_heads(v, heads),
                                 block_diffusion=block)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    w = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.float32)
    got, want = (jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2)))(q, k, v)
        for f in (flash, dense))
    for name, g, r in zip("qkv", got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, err_msg=f"d{name}")


# batch, H, Hkv, D, the entry a caller asks for (PR 70: ``heads_major`` hands
# the kernels [B, H, S, D]; the default merges to [B*H, S, D]).
BY_ENTRY = [(1, 8, 4, 128, False), (2, 4, 4, 128, True), (4, 8, 4, 128, True),
            (2, 4, 4, 256, True), (2, 8, 2, 128, False), (2, 8, 2, 128, True),
            (2, 4, 4, 64, False), (2, 4, 4, 64, True)]


@pytest.mark.parametrize("length", [128, 96])
@pytest.mark.parametrize("batch, heads, kv_heads, dim, heads_major", BY_ENTRY)
def test_kernels_agree_with_the_dense_path_through_either_entry(
        batch, heads, kv_heads, dim, heads_major, length):
    """The same comparison through either entry at batches of one, two and
    four, in blocks of 4 (the ``sdar-30b-a3b-chat_s8192`` cell's), the halves'
    boundary on a tile's edge and inside the first tile."""
    q, k, v = _qkv(length, heads, kv_heads, dim=dim, batch=batch, seed=dim)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_diffusion=4,
                                  heads_major=heads_major,
                                  _blocks=(128, 128))

    def dense(q, k, v):
        return default_attention(q, repeat_kv_heads(k, heads),
                                 repeat_kv_heads(v, heads),
                                 block_diffusion=4)

    w = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.float32)

    def both(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(o * w), o))(f(*a)),
            argnums=(0, 1, 2), has_aux=True))(q, k, v)

    ((_, out), got), ((_, ref), want) = both(flash), both(dense)
    np.testing.assert_allclose(out, ref, atol=4e-5)
    for name, g, r in zip("qkv", got, want):
        np.testing.assert_allclose(g, r, atol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("rank", [3, 4])
def test_the_pair_of_backward_kernels_agrees_too(rank):
    """Where a head's dK and dV would not fit VMEM the backward pass is the
    dKdV kernel, its table column by column, and the dQ kernel: the same
    numbers as the one kernel under this mask, on operands merged to
    ``[B*H, S, D]`` and at rank 4, ``[B, H, S, D]``."""
    q, k, v = _qkv(256, 4, 2)
    s, (bh, bkv) = 512, (8, 4)
    mask = fa.Mask(block_diffusion=4, half=256)
    to = lambda x: x.transpose(0, 2, 1, 3).reshape(
        (-1, s, x.shape[3]) if rank == 3 else (2, -1, s, x.shape[3]))
    q, k, v = to(q), to(k), to(v)
    scale = q.shape[-1] ** -0.5
    o, lse = fa._fwd_call(q, k, v, scale, mask, s, (128, 128))
    do = jax.random.normal(jax.random.PRNGKey(2), o.shape, jnp.float32)
    delta = jnp.sum(do * o, axis=-1).reshape(bh, s)
    rows = lse[:, None, :, 0], delta[:, None, :]
    dq, dk, dv = fa._bwd_call(q, k, v, do, *rows, scale, mask, s, (128, 128))
    dk2, dv2 = fa._dkdv_call(q, k, v, do, *rows, scale, mask, s, (128, 128))
    dq2 = fa._dq_call(q, k, v, do, lse, jnp.broadcast_to(
        delta[..., None], lse.shape), scale, mask, s, (128, 128))
    for a, b in ((dq, dq2), (dk, dk2), (dv, dv2)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("length, block", [
    (256, 4), (192, 32), (320, 1), (128, 128), (384, 64), (1024, 4)])
def test_keep_is_the_three_clauses_and_no_row_or_column_is_empty(length,
                                                                 block):
    mask = fa.Mask(block_diffusion=block, half=length)
    rows = 2 * length
    at = np.arange(_padded(rows))
    keep = np.asarray(mask.keep(at[:, None], at[None, :], rows))
    assert (keep[:rows, :rows] == block_diffusion_mask(rows, block)).all()
    # Every row (padding included: a row's output is written) and every
    # real column holds a kept pair; no real row sees a padded key.
    assert keep.any(axis=1).all() and keep[:rows, :rows].any(axis=0).all()
    assert not keep[:rows, rows:].any()
    assert mask.kept_pairs() == block_diffusion_mask(rows, block).sum() \
        == length * (length + block)


@pytest.mark.parametrize("by_column", [False, True])
@pytest.mark.parametrize("length, block, block_q, block_k", [
    (length, block, block_q, block_k)
    for length, block in ((768, 4), (192, 32), (384, 1), (384, 64),
                          (1536, 4))
    for block_q, block_k in ((128, 128), (256, 128), (128, 256), (512, 512),
                             (384, 128))
    if not (_padded(2 * length) % block_q or _padded(2 * length) % block_k)])
def test_kept_tiles_against_a_brute_force_over_keep(length, block, block_q,
                                                    block_k, by_column):
    """The table lists a tile where any pair of it is kept, for any tile,
    the halves' boundary on a tile's edge or inside one, in the order a
    kernel walks it, each run opened and closed once."""
    rows = 2 * length
    padded = _padded(rows)
    mask = fa.Mask(block_diffusion=block, half=length)
    at = np.arange(padded)
    keep = np.asarray(mask.keep(at[:, None], at[None, :], rows))
    n_q, n_k = padded // block_q, padded // block_k
    brute = [(i, j) for i in range(n_q) for j in range(n_k)
             if keep[i * block_q:(i + 1) * block_q,
                     j * block_k:(j + 1) * block_k].any()]
    if by_column:
        brute.sort(key=lambda tile: (tile[1], tile[0]))
    table = mask.kept_tiles(n_q, n_k, block_q, block_k, by_column)
    assert list(zip(table[fa.TILE_Q].tolist(),
                    table[fa.TILE_K].tolist())) == brute
    run = [tile[1 if by_column else 0] for tile in brute]
    first = [a != b for a, b in zip([None] + run, run)]
    assert table[fa.TILE_FIRST].tolist() == first
    assert table[fa.TILE_LAST].tolist() == first[1:] + [True]
    assert len(set(run)) == (n_k if by_column else n_q)
    assert mask.tiles(n_q, n_k, block_q, block_k) == {
        "kept": len(brute),
        "skipped_block_diffusion": n_q * n_k - len(brute)}


def test_the_cells_table():
    """8,192 data tokens in blocks of 4 under 1024-wide tiles: 36
    clean-clean, 36 noised-clean and 8 noised-noised tiles of 256, where the
    causal triangle of 16,384 rows keeps 136; the tiles are 80% full."""
    mask = fa.Mask(block_diffusion=4, half=8192)
    table = mask.kept_tiles(16, 16, 1024, 1024)
    q, k = table[fa.TILE_Q], table[fa.TILE_K]
    assert table.shape == (4, 80)
    assert ((q >= 8) & (k >= 8)).sum() == 36 and ((q < 8) & (k >= 8)).sum() \
        == 36 and ((q < 8) & (k < 8)).sum() == 8 and not ((q >= 8)
                                                         & (k < 8)).any()
    assert fa.Mask().kept_tiles(16, 16, 1024, 1024).shape == (4, 136)
    assert mask.kept_pairs() / (80 * 1024 * 1024) == pytest.approx(0.8004,
                                                                  abs=1e-4)
    assert mask.name == "block_diffusion"


def _tables_before(causal, window, n_q, n_k, block_q, block_k, by_column):
    """``Mask.kept_tiles`` as it stood before the block-diffusion
    description, written out."""
    tiles = []
    for i in range(n_q):
        for j in range(n_k):
            kept = True
            if causal:
                kept = (i + 1) * block_q - 1 >= j * block_k
                if window is not None:
                    kept = kept and ((j + 1) * block_k + window - 2
                                     >= i * block_q)
            if kept:
                tiles.append((i, j))
    run = 1 if by_column else 0
    tiles.sort(key=lambda tile: (tile[run], tile[1 - run]))
    runs = [tile[run] for tile in tiles]
    first = [a != b for a, b in zip([None] + runs, runs)]
    return np.array([*zip(*tiles), first, first[1:] + [True]], np.int32)


@pytest.mark.parametrize("by_column", [False, True])
@pytest.mark.parametrize("causal, window", [
    (True, None), (True, 1), (True, 100), (True, 512), (True, 2048),
    (False, None)])
@pytest.mark.parametrize("n, block_q, block_k", [
    (8, 128, 128), (4, 256, 128), (16, 1024, 1024), (1, 512, 512)])
def test_the_masks_that_were_there_keep_their_tables(n, block_q, block_k,
                                                     causal, window,
                                                     by_column):
    n_q, n_k = n, n * block_q // block_k
    mask = fa.Mask(causal, window)
    got = mask.kept_tiles(n_q, n_k, block_q, block_k, by_column)
    want = _tables_before(causal, window, n_q, n_k, block_q, block_k,
                          by_column)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    assert mask.name == ("window" if window is not None
                         else "causal" if causal else "full")
    assert set(mask.tiles(n_q, n_k, block_q, block_k)) == {
        "kept", "skipped", "skipped_band"}
    assert mask.kept_pairs() is None


def test_refusals():
    q, k, v = _qkv(64, 2, 2, dim=16)
    for mask in (dict(causal=False, block_diffusion=4, half=64),
                 dict(window=8, block_diffusion=4, half=64),
                 dict(block_diffusion=4), dict(half=64),
                 dict(block_diffusion=3, half=63),
                 dict(block_diffusion=0, half=64),
                 dict(block_diffusion=32, half=48)):
        with pytest.raises(ValueError, match="block_diffusion"):
            fa.Mask(**mask)
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(q, k, v, window=8, block_diffusion=4)
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(q, k, v, causal=False, block_diffusion=4)
    with pytest.raises(ValueError, match="two halves"):     # an odd S
        fa.flash_attention(q[:, :127], k[:, :127], v[:, :127],
                           block_diffusion=1)
    with pytest.raises(ValueError, match="block_diffusion"):  # B does not
        fa.flash_attention(q[:, :120], k[:, :120], v[:, :120],  # divide L
                           block_diffusion=8)
    for more in (dict(window=8), dict(causal=False)):
        with pytest.raises(ValueError, match="block_diffusion"):
            default_attention(q, k, v, block_diffusion=4, **more)
    with pytest.raises(ValueError, match="two halves"):
        default_attention(q[:, :120], k[:, :120], v[:, :120],
                          block_diffusion=8)


@pytest.mark.parametrize("layers", [
    (gpt.LayerSpec(window=8), gpt.LayerSpec()),
    (gpt.LayerSpec(mixer="ssm"), gpt.LayerSpec())])
def test_layer_plan_refuses_it_beside_a_window_or_another_mixer(layers):
    with pytest.raises(ValueError, match="diffusion_block=4"):
        gpt.GPTConfig(num_layers=2, layers=layers, diffusion_block=4).plan
    assert gpt.GPTConfig(num_layers=2, diffusion_block=4).plan


def _traced(attention, sp_bound, block=4):
    cfg = gpt.GPTConfig(
        vocab_size=64, num_layers=1, num_heads=2, num_kv_heads=1,
        head_dim=16, embed_dim=32, mlp_dim=64, dtype=jnp.float32,
        tp_axis=None, sp_axis="sp", attention=attention,
        diffusion_block=block)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 256), jnp.int32)
    positions = jnp.tile(jnp.arange(128), (2, 2))
    forward = lambda p, t, pos: gpt.forward(p, t, pos, cfg)
    if sp_bound:
        seq = P(None, "sp")
        forward = jax.shard_map(forward, mesh=hvd.mesh(),
                                in_specs=(P(), seq, seq), out_specs=seq)
    return str(jax.make_jaxpr(forward)(params, tokens, positions))


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_ring_and_ulysses_refuse_it_under_a_bound_sp_axis(make_runtime,
                                                          attention):
    make_runtime(mesh_shape={"dp": 1, "sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"{attention!r}.*no block_diffusion "
                                         "mask.*diffusion_block=4"):
        _traced(attention, True)
    # Without the axis either is the flash kernel under the mask.
    assert "hvd_flash_fwd" in _traced(attention, False)
