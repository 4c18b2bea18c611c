"""The flash kernels under a causal band (a sliding window) against
``default_attention`` under the same band: outputs and all three gradients
element by element (the backward pass as the one kernel every shape here
takes, and as the pair of dKdV and dQ it replaced), the tiles each grid keeps
counted against the formula, the one mask description (``fa.Mask``) the
kernels read, and the lists of kept tiles it makes, which are the kernels'
grids.

Interpret mode on the CPU (as ``test_flash_attention.py``): it says nothing
of Mosaic lowering, which ``test_flash_mosaic_compile.py`` holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.observability import sample_value
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.attention import default_attention, repeat_kv_heads
from horovod_tpu.ops.flash_attention import flash_attention

from benchmarks import flops, flops_window

KERNELS = (fa.KERNEL_FWD, fa.KERNEL_DKDV, fa.KERNEL_DQ)


def _qkv(b, s, h, hkv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d)) * 0.5,
            jax.random.normal(ks[1], (b, s, hkv, d)) * 0.5,
            jax.random.normal(ks[2], (b, s, hkv, d)) * 0.5,
            jax.random.normal(ks[3], (b, s, h, d)))


def _dense(q, k, v, window):
    h = q.shape[2]
    return default_attention(q, repeat_kv_heads(k, h), repeat_kv_heads(v, h),
                             causal=True, window=window)


def _both(s, window, blocks, h=2, hkv=2, d=32, seed=0, b=1,
          heads_major=False):
    """``((out, dq, dk, dv) of the kernels, the same of the reference)``."""
    q, k, v, w = _qkv(b, s, h, hkv, d, seed)
    flash = lambda q, k, v: flash_attention(q, k, v, window=window,
                                            heads_major=heads_major,
                                            _blocks=blocks)
    dense = lambda q, k, v: _dense(q, k, v, window)
    return tuple(
        (fn(q, k, v), *jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                        argnums=(0, 1, 2)))(q, k, v))
        for fn in (flash, dense))


def _assert_close(got, want):
    for g, r, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=5e-4,
                                   atol=5e-5, err_msg=name)


# sequence, window, forced tile: a window of one key, one that is no
# multiple of the tile, one of a whole tile, two tiles, and tiles that are
# not square either way.
@pytest.mark.parametrize("s,window,blocks", [
    (384, 1, (128, 128)),
    (384, 100, (128, 128)),
    (512, 128, (128, 128)),
    (512, 200, (128, 128)),
    (512, 256, (128, 128)),
    (512, 200, (256, 128)),
    (512, 200, (128, 256)),
    (512, 300, None),
])
def test_band_matches_dense_outputs_and_gradients(s, window, blocks):
    _assert_close(*_both(s, window, blocks))


@pytest.mark.parametrize("window", [384, 385, 5000])
def test_window_of_the_sequences_length_or_more_is_the_causal_program(window):
    q, k, v, _ = _qkv(1, 384, 2, 2, 32, seed=3)
    banded = jax.make_jaxpr(lambda *a: flash_attention(*a, window=window))(
        q, k, v)
    causal = jax.make_jaxpr(lambda *a: flash_attention(*a))(q, k, v)
    assert str(banded) == str(causal)
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, window=window)),
        np.asarray(flash_attention(q, k, v)))


def test_a_mask_is_a_static_value_with_a_name():
    """The description rides the kernels' partial arguments and the custom
    VJP's non-differentiable ones: equal masks are one key, and the counter's
    ``mask`` label is the name."""
    assert fa.Mask(window=64) == fa.Mask(causal=True, window=64)
    assert hash(fa.Mask(window=64)) == hash(fa.Mask(causal=True, window=64))
    assert fa.Mask(window=64) != fa.Mask(window=65)
    assert [m.name for m in (fa.Mask(), fa.Mask(causal=False),
                             fa.Mask(window=8))] \
        == ["causal", "full", "window"]


def test_grouped_query_heads_under_a_band():
    _assert_close(*_both(384, 150, (128, 128), h=4, hkv=2, seed=7))
    _assert_close(*_both(256, 70, (128, 128), h=4, hkv=1, seed=8))


# batch, sequence, window, forced tile, H, Hkv, D: the band through either
# entry (PR 70: a caller's ``heads_major`` hands the kernels ``[B, H, S, D]``,
# the default merges to ``[B*H, S, D]``) at batches of one, two and four, the
# benchmark cells' groups (12, 8, 7) among them, the band inside one tile,
# across whole tiles and across tiles that are not square.
BANDS_BY_ENTRY = [
    (1, 384, 100, (128, 128), 24, 2, 128),
    (2, 512, 200, (256, 128), 32, 4, 128),
    (4, 384, 150, (128, 128), 28, 4, 128),
    (2, 512, 128, (128, 256), 8, 4, 256),
    (4, 300, 129, None, 4, 4, 256),
    (2, 384, 150, (128, 128), 24, 2, 128),
    (2, 384, 100, (128, 128), 8, 2, 64),
    (4, 300, 129, None, 4, 4, 64),
]


@pytest.mark.parametrize("heads_major", [False, True],
                         ids=["rank3", "rank4"])
@pytest.mark.parametrize("b,s,window,blocks,h,hkv,d", BANDS_BY_ENTRY)
def test_band_matches_dense_through_either_entry(b, s, window, blocks, h, hkv,
                                                 d, heads_major):
    _assert_close(*_both(s, window, blocks, h=h, hkv=hkv, d=d, seed=17, b=b,
                         heads_major=heads_major))


@pytest.mark.parametrize("d", [64, 128])
def test_band_backward_as_the_pair_at_a_batch_of_two(monkeypatch, d):
    """The pair of dKdV and dQ under a band through either entry at two
    sequences: dK and dV the one kernel's bit for bit, dQ to float32
    rounding, all three the dense reference's."""
    args = dict(h=8, hkv=4, d=d, seed=19, b=2, heads_major=d == 128)
    one, want = _both(384, 150, (128, 128), **args)
    monkeypatch.setattr(fa, "backward_is_fused", lambda *a: False)
    pair, _ = _both(384, 150, (128, 128), **args)
    _assert_close(pair, want)
    for name, x, y in zip(("dk", "dv"), one[2:], pair[2:]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)
    np.testing.assert_allclose(np.asarray(one[1]), np.asarray(pair[1]),
                               rtol=1e-5, atol=1e-7)


# sequence, window, forced tile, query heads a K/V head: the band inside one
# tile, across tiles that are not square either way, a group of twelve.
BANDS = [(512, 200, (256, 128), 1), (512, 200, (128, 256), 4),
         (384, 100, (128, 128), 12), (512, 300, None, 2)]


@pytest.mark.parametrize("s,window,blocks,group", BANDS)
def test_band_backward_as_one_kernel_and_as_the_pair(monkeypatch, s, window,
                                                     blocks, group):
    """Under a band the one backward kernel walks the tiles the pair walks
    (the dQ kernel's list, under each query head; the dKdV kernel's is the
    same tiles column by column): both match the dense reference; dK and dV
    are the pair's bit for bit (the same float32 sums in the same order), dQ
    to float32 rounding (its product contracts the tile's other axis)."""
    one, want = _both(s, window, blocks, h=group, hkv=1, seed=13)
    _assert_close(one, want)
    # What a sequence too long for a head's dK and dV in VMEM keeps.
    monkeypatch.setattr(fa, "backward_is_fused", lambda *a: False)
    pair, _ = _both(s, window, blocks, h=group, hkv=1, seed=13)
    _assert_close(pair, want)
    for name, a, b in zip(("dk", "dv"), one[2:], pair[2:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    np.testing.assert_allclose(np.asarray(one[1]), np.asarray(pair[1]),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("s,window", [(200, 60), (300, 129), (130, 128)])
def test_padded_length_under_a_band(s, window):
    # The kernels pad to 128 rows; the padded keys lie inside a padded
    # query's band and outside every real one's.
    _assert_close(*_both(s, window, None, seed=11))


def test_bidirectional_call_refuses_a_window():
    q, k, v, _ = _qkv(1, 128, 1, 1, 32)
    with pytest.raises(ValueError, match="causal band"):
        flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="causal band"):
        default_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="at least one"):
        fa.Mask(causal=True, window=0)


def test_dense_reference_band_by_hand():
    # Four tokens, a window of two: query i sees keys i - 1 and i.
    q = jnp.zeros((1, 4, 1, 2))
    v = jnp.arange(8.0).reshape(1, 4, 1, 2)
    out = default_attention(q, q, v, window=2)[0, :, 0]
    want = [v[0, 0, 0], (v[0, 0, 0] + v[0, 1, 0]) / 2,
            (v[0, 1, 0] + v[0, 2, 0]) / 2, (v[0, 2, 0] + v[0, 3, 0]) / 2]
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.stack(want)),
                               rtol=1e-6)


def _tiles_by_hand(n_q, n_k, bq, bk, window):
    """Tiles that hold a kept pair, pair by pair."""
    kept = 0
    for i in range(n_q):
        for j in range(n_k):
            qs = np.arange(i * bq, (i + 1) * bq)[:, None]
            ks = np.arange(j * bk, (j + 1) * bk)[None, :]
            keep = qs >= ks
            if window is not None:
                keep &= qs - ks < window
            kept += bool(keep.any())
    return kept


@pytest.mark.parametrize("s,window,bq,bk", [
    (8192, 2048, 1024, 1024), (4096, 2048, 1024, 1024), (1024, 1, 128, 128),
    (1024, 129, 128, 128), (1024, 300, 256, 128), (1024, 300, 128, 256),
    (1024, None, 128, 128)])
def test_kept_tiles_follow_the_formula(s, window, bq, bk):
    mask = fa.Mask(True, window)
    tiles = mask.tiles(s // bq, s // bk, bq, bk)
    assert sum(tiles.values()) == (s // bq) * (s // bk)
    assert tiles["kept"] == _tiles_by_hand(s // bq, s // bk, bq, bk, window)
    # The benchmark's own count (a file that imports nothing of the program).
    kept, causal = flops_window.band_tiles(s, window, bq, bk)
    assert (kept, causal) == (tiles["kept"],
                              tiles["kept"] + tiles["skipped_band"])
    if (s, window, bq) == (8192, 2048, 1024):
        assert (kept, causal) == (21, 36)


def _pairs_kept(mask, i, j, bq, bk, kv_len):
    """Whether tile ``(i, j)`` holds a pair ``mask.keep`` keeps, pair by
    pair."""
    qs = np.arange(i * bq, (i + 1) * bq)[:, None]
    ks = np.arange(j * bk, (j + 1) * bk)[None, :]
    return bool(np.any(mask.keep(qs, ks, kv_len)))


# mask, sequence, tile: causal, a band and no mask at tiles that are not
# square either way; a band narrower than a tile (Phi-4's, at a tile of
# 1024 and at the tile that follows it); a band of one key; one tile.
KEPT_TILE_CASES = [
    (fa.Mask(), 1024, 256, 128), (fa.Mask(), 1024, 128, 256),
    (fa.Mask(True, 300), 1024, 256, 128),
    (fa.Mask(True, 300), 1024, 128, 256),
    (fa.Mask(False), 1024, 256, 128), (fa.Mask(False), 768, 128, 256),
    (fa.Mask(True, 512), 16384, 1024, 1024),
    (fa.Mask(True, 512), 4096, 512, 512),
    (fa.Mask(True, 4096), 16384, 1024, 1024),
    (fa.Mask(True, 1), 512, 128, 128), (fa.Mask(), 512, 512, 512),
]


@pytest.mark.parametrize(
    "mask, n, bq, bk", KEPT_TILE_CASES,
    ids=lambda x: f"{x.name}{x.window or ''}" if isinstance(x, fa.Mask)
    else str(x))
def test_kept_tile_lists_are_the_brute_enumeration(mask, n, bq, bk):
    """The table a grid walks (``kept_tiles``) is the tiles ``tile_kept``
    holds, which are the tiles that hold a kept pair, each once: row-major
    with a query block's tiles together and the keys ascending, column-major
    with a key block's together and the queries ascending; the flags mark
    each run's first and last tile and no other; and the count is the
    counter's (``tiles``)."""
    n_q, n_k = n // bq, n // bk
    brute = [(i, j) for i in range(n_q) for j in range(n_k)
             if mask.tile_kept(i, j, bq, bk)]
    if n <= 1024:
        assert brute == [(i, j) for i in range(n_q) for j in range(n_k)
                         if _pairs_kept(mask, i, j, bq, bk, n)]
    assert len(brute) == mask.tiles(n_q, n_k, bq, bk)["kept"]
    for by_column, run in ((False, fa.TILE_Q), (True, fa.TILE_K)):
        table = mask.kept_tiles(n_q, n_k, bq, bk, by_column)
        assert table.dtype == np.int32 and table.shape == (4, len(brute))
        tiles = list(zip(table[fa.TILE_Q].tolist(),
                         table[fa.TILE_K].tolist()))
        assert tiles == sorted(brute, key=lambda t: t[::-1] if by_column
                               else t)
        runs = table[run]
        # Every row (column) of the rectangle has a run, the runs ascend,
        # and a run's tiles are neighbours (the band has no hole).
        assert sorted(set(runs.tolist())) \
            == list(range(n_k if by_column else n_q))
        first = np.r_[True, runs[1:] != runs[:-1]]
        last = np.r_[runs[1:] != runs[:-1], True]
        np.testing.assert_array_equal(table[fa.TILE_FIRST], first)
        np.testing.assert_array_equal(table[fa.TILE_LAST], last)
        other = table[fa.TILE_K if run == fa.TILE_Q else fa.TILE_Q]
        assert np.all((np.diff(other) == 1) | first[1:])


def test_a_k_blocks_run_is_walked_once_a_query_head_of_the_group():
    """The dKdV kernel of the pair sums a k block over the group's query
    heads, the heads the outer order: its table holds each column's run
    once a head, opened under the first and closed under the last."""
    table = fa.Mask(True, 300).kept_tiles(4, 8, 256, 128, by_column=True)
    walked = fa._over_group(table, 3)
    assert walked.shape == (5, 3 * table.shape[1])
    steps = list(zip(*walked[[fa.TILE_K, fa.TILE_HEAD, fa.TILE_Q]].tolist()))
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    for j in range(8):
        of_j = walked[:, walked[fa.TILE_K] == j]
        assert of_j[fa.TILE_FIRST].tolist() == [1] + [0] * (of_j.shape[1] - 1)
        assert of_j[fa.TILE_LAST].tolist() == [0] * (of_j.shape[1] - 1) + [1]
    np.testing.assert_array_equal(fa._over_group(table, 1)[:4], table)


def test_band_pairs_by_hand():
    assert flops_window.band_pairs(8, 3) == 1 + 2 + 3 * 6 == 21
    assert flops_window.band_pairs(8192, 2048) \
        == 2048 * 2049 // 2 + 6144 * 2048 == 14_681_088
    assert flops_window.band_pairs(8, 8) == flops_window.band_pairs(8, None) \
        == flops.causal_pairs(8) == 36
    mask = np.asarray(jnp.tril(jnp.ones((64, 64), bool))
                      & ~jnp.tril(jnp.ones((64, 64), bool), -10))
    assert mask.sum() == flops_window.band_pairs(64, 10)


@pytest.mark.parametrize("backward", ["fused", "pair"])
def test_metrics_count_the_tiles_kept_and_skipped(make_runtime, monkeypatch,
                                                  backward):
    make_runtime(devices=jax.devices()[:1])
    if backward == "pair":
        monkeypatch.setattr(fa, "backward_is_fused", lambda *a: False)
    q, k, v, _ = _qkv(1, 512, 2, 1, 32)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, window=200, _blocks=(128, 128))))(q)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, _blocks=(128, 128))))(q)
    fams = hvd.metrics()
    assert fams["hvdtpu_spmd_flash_tiles_total"]["type"] == "counter"

    def count(kernel, mask, tiles):
        return sample_value(fams, "hvdtpu_spmd_flash_tiles_total",
                            kernel=kernel, mask=mask, tiles=tiles,
                            seq="512")

    # 4 x 4 tiles: the triangle keeps 10; a band of 200 keeps the diagonal,
    # the one below it and the one below that (4 + 3 + 2). The one backward
    # kernel walks the grid once, under the dKdV kernel's name.
    for kernel in KERNELS[:2 if backward == "fused" else 3]:
        assert count(kernel, "window", "kept") == 9
        assert count(kernel, "window", "skipped_band") == 1
        assert count(kernel, "window", "skipped") == 6
        assert count(kernel, "causal", "kept") == 10
        assert count(kernel, "causal", "skipped") == 6
        assert count(kernel, "causal", "skipped_band") == 0
    if backward == "fused":
        assert count(fa.KERNEL_DQ, "window", "kept") is None


def _pallas_grids(jaxpr):
    """``(kernel name, grid)`` of every ``pallas_call`` in ``jaxpr``,
    nested jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        tuple(eqn.params["grid_mapping"].grid)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_grids(sub))
    return out


@pytest.mark.parametrize("backward", ["fused", "pair"])
@pytest.mark.parametrize("causal, window, s", [
    (True, None, 512), (True, 200, 512), (True, 64, 768), (False, None, 400)])
def test_grid_steps_are_the_kept_tiles(make_runtime, monkeypatch, backward,
                                       causal, window, s):
    """No grid step is a tile the mask drops: the counter of the steps a
    head's grid walks equals the kept tiles' in every traced call, and it is
    the grid the call was given (a head's steps, times the group's query
    heads where a K/V head's grid walks them all)."""
    make_runtime(devices=jax.devices()[:1])
    if backward == "pair":
        monkeypatch.setattr(fa, "backward_is_fused", lambda *a: False)
    q, k, v, _ = _qkv(1, s, 4, 2, 32)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, window=window, _blocks=(128, 256))),
        argnums=(0, 1, 2))
    grids = _pallas_grids(jax.make_jaxpr(grad)(q, k, v).jaxpr)
    fams = hvd.metrics()
    mask = fa.Mask(causal, window).name
    seq = str(s + (-s) % 128)
    kernels = KERNELS[:2 if backward == "fused" else 3]
    assert sorted(name for name, _ in grids) == sorted(kernels)
    for kernel in kernels:
        labels = dict(kernel=kernel, mask=mask, seq=seq)
        steps = sample_value(fams, "hvdtpu_spmd_flash_grid_steps_total",
                             **labels)
        kept = sample_value(fams, "hvdtpu_spmd_flash_tiles_total",
                            tiles="kept", **labels)
        assert steps == kept > 0
        # q at 4 heads walks a head's steps; a K/V head's grid (2 of them)
        # walks them under each of its two query heads: an axis of their
        # own in the one backward kernel, in the pair's dKdV the
        # column-major list once a head.
        want = {fa.KERNEL_FWD: (4, steps), fa.KERNEL_DQ: (4, steps),
                fa.KERNEL_DKDV: (2, 2, steps) if backward == "fused"
                else (2, 2 * steps)}
        assert dict(grids)[kernel] == want[kernel]
    if not causal:
        assert kept == (int(seq) // 128) * (int(seq) // 256)
