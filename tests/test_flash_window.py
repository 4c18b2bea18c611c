"""The flash kernels under a causal band (a sliding window) against
``default_attention`` under the same band: outputs and all three gradients
element by element (the backward pass as the one kernel every shape here
takes, and as the pair of dKdV and dQ it replaced), the tiles each grid keeps
counted against the formula, and the one mask description (``fa.Mask``) the
kernels read.

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


def _both(s, window, blocks, h=2, hkv=2, d=32, seed=0):
    """``((out, dq, dk, dv) of the kernels, the same of the reference)``."""
    q, k, v, w = _qkv(1, s, h, hkv, d, seed)
    flash = lambda q, k, v: flash_attention(q, k, v, window=window,
                                            _blocks=blocks)
    dense = lambda q, k, v: _dense(q, k, v, window)
    return tuple(
        (fn(q, k, v), *jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                argnums=(0, 1, 2))(q, k, v))
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


# sequence, window, forced tile, query heads a K/V head: the band inside one
# tile, across tiles that are not square either way, a group of twelve.
BANDS = [(512, 200, (256, 128), 1), (512, 200, (128, 256), 4),
         (384, 100, (128, 128), 12), (512, 300, None, 2)]


@pytest.mark.parametrize("s,window,blocks,group", BANDS)
def test_band_backward_as_one_kernel_and_as_the_pair(monkeypatch, s, window,
                                                     blocks, group):
    """Under a band the one backward kernel skips the tiles the pair skips
    and clamps the K/V blocks it names as the dQ kernel does: both match the
    dense reference; dK and dV are the pair's bit for bit (the same float32
    sums in the same order), dQ to float32 rounding (its product contracts
    the tile's other axis)."""
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


def test_tile_predicates_and_block_ranges_agree_with_the_count():
    """What the kernels read a tile at a time (``tile_kept``) and what the
    index maps clamp to (``k_blocks``, ``q_blocks``) describe the same
    band: every kept tile lies inside both ranges and the ranges' ends are
    kept tiles."""
    bq, bk, n = 256, 128, 1024
    mask = fa.Mask(True, 300)
    n_q, n_k = n // bq, n // bk
    kept = np.array([[bool(mask.tile_kept(jnp.int32(i), jnp.int32(j), bq, bk))
                      for j in range(n_k)] for i in range(n_q)])
    assert kept.sum() == mask.tiles(n_q, n_k, bq, bk)["kept"]
    for i in range(n_q):
        first, last = (int(x) for x in mask.k_blocks(jnp.int32(i), bq, bk))
        assert list(np.flatnonzero(kept[i])) == list(range(first, last + 1))
    for j in range(n_k):
        first, last = (int(x) for x in mask.q_blocks(jnp.int32(j), bq, bk))
        assert list(np.flatnonzero(kept[:, j])) \
            == list(range(first, min(last, n_q - 1) + 1))


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
