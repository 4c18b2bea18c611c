"""``hvd.shard_batch``'s two paths (ISSUE 62): a C-contiguous ``numpy`` leaf of
rank 3 or more, split on its leading dimension, crosses as its ``[N, rest]``
view and takes its shape on the device (``flat``); every other leaf is handed
to ``jax.device_put`` as it is (``direct``). Either way the caller gets what
``jax.device_put(x, sharding)`` gives: the step's executable is one."""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

import horovod_tpu as hvd
from horovod_tpu import step as step_module
from horovod_tpu.observability import sample_value

N = 16
RNG = np.random.default_rng(62)
IMAGES = RNG.integers(0, 256, (N, 8, 8, 3), dtype=np.uint8)
LABELS = RNG.integers(0, 10, (N,), dtype=np.int32)
WIDE = RNG.standard_normal((N, 6, 10)).astype(np.float32)


def leaves_placed() -> dict:
    m = hvd.metrics()
    return {path: sample_value(m, "hvdtpu_spmd_shard_batch_leaves_total",
                               path=path) for path in ("flat", "direct")}


def assert_as_device_put(placed, host, sharding):
    want = jax.device_put(host, sharding)
    assert placed.shape == want.shape and placed.dtype == want.dtype
    assert placed.sharding == want.sharding
    assert placed.format == want.format
    assert [s.index for s in placed.addressable_shards] \
        == [s.index for s in want.addressable_shards]
    np.testing.assert_array_equal(np.asarray(placed), np.asarray(host))


# name -> (batch, shard_batch's keywords, leaves by path: flat, direct)
CASES = {
    "uint8_images": (lambda: IMAGES, {}, (1, 0)),
    "float32_rank3": (lambda: WIDE, {}, (1, 0)),
    "images_and_labels": (lambda: (IMAGES, LABELS), {}, (1, 1)),
    "dict_of_leaves": (lambda: {"x": IMAGES, "y": LABELS, "w": WIDE}, {},
                       (2, 1)),
    "rank1": (lambda: LABELS, {}, (0, 1)),
    "rank2": (lambda: WIDE.reshape(N, -1), {}, (0, 1)),
    "jax_array": (lambda: jnp.asarray(IMAGES), {}, (0, 1)),
    "strided": (lambda: IMAGES[:, ::2], {}, (0, 1)),
    "transposed": (lambda: np.ascontiguousarray(
        IMAGES.transpose(3, 1, 2, 0)).transpose(3, 1, 2, 0), {}, (0, 1)),
    "fortran_order": (lambda: np.asfortranarray(WIDE), {}, (0, 1)),
    "dim1": (lambda: IMAGES.reshape(2, 8, 8, 8, 3), {"dim": 1}, (0, 1)),
    "empty_rows": (lambda: np.zeros((N, 0, 3), np.float32), {}, (0, 1)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_placed_as_device_put_gives_it(spmd8, name):
    make, keywords, (flat, direct) = CASES[name]
    batch = make()
    sharding = NamedSharding(hvd.mesh(),
                             hvd.batch_spec(keywords.get("dim", 0)))
    before = leaves_placed()
    placed = hvd.shard_batch(batch, **keywords)
    after = leaves_placed()
    assert (after["flat"] - before["flat"],
            after["direct"] - before["direct"]) == (flat, direct)
    assert jax.tree.structure(placed) == jax.tree.structure(batch)
    for got, host in zip(jax.tree.leaves(placed), jax.tree.leaves(batch)):
        assert_as_device_put(got, host, sharding)


@pytest.mark.parametrize("rows, path", [(IMAGES, "flat"), (LABELS, "direct")])
def test_a_mesh_argument_places_on_that_mesh(spmd8, rows, path):
    mesh = Mesh(np.array(jax.devices()[:4]), ("half",))
    before = leaves_placed()
    placed = hvd.shard_batch(rows, axis="half", mesh=mesh)
    assert leaves_placed()[path] == before[path] + 1
    assert_as_device_put(placed, rows,
                         NamedSharding(mesh, hvd.batch_spec(0, "half")))
    assert placed.sharding.device_set == set(jax.devices()[:4])


def test_one_executable_for_both_paths(spmd8):
    step = hvd.run_step(
        lambda x: hvd.allreduce(x.astype(jnp.float32).sum(), op=hvd.Sum),
        in_specs=hvd.batch_spec(0), out_specs=hvd.REPLICATED)
    sharding = NamedSharding(hvd.mesh(), hvd.batch_spec())
    direct = float(step(jax.device_put(IMAGES, sharding)))
    assert step._cache_size() == 1
    before = leaves_placed()["flat"]
    flat = float(step(hvd.shard_batch(IMAGES)))
    assert leaves_placed()["flat"] == before + 1
    assert step._cache_size() == 1
    assert flat == direct == float(IMAGES.sum(dtype=np.float64))


def test_the_flat_array_does_not_outlive_the_call(spmd8):
    jax.block_until_ready(hvd.shard_batch(IMAGES))      # compiled
    held = {id(a) for a in jax.live_arrays()}
    placed = jax.block_until_ready(hvd.shard_batch(IMAGES))
    new = [a for a in jax.live_arrays() if id(a) not in held]
    assert [a.shape for a in new] == [IMAGES.shape]
    assert new[0] is placed


def test_the_reshape_compiles_once_a_shape(spmd8, tmp_path):
    step_module._restorer.cache_clear()
    path = tmp_path / "timeline.json"
    hvd.start_timeline(str(path))
    jax.block_until_ready(hvd.shard_batch(IMAGES))
    compiled_by_ns = time.time_ns()
    jax.block_until_ready(hvd.shard_batch(IMAGES + 1))
    jax.block_until_ready(hvd.shard_batch((IMAGES, LABELS)))
    hvd.stop_timeline()
    compiles = [e["args"]["start_ns"] < compiled_by_ns
                for e in json.loads(path.read_text())["traceEvents"]
                if e["name"].startswith("compile/")]
    assert compiles and all(compiles)       # the first call's, and no other
    assert step_module._restorer.cache_info().currsize == 1
