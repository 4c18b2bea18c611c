"""Hierarchical (cross-slice) allreduce tests.

Reference: NCCLHierarchicalAllreduce (nccl_operations.cc:204) — intra-node
reduce-scatter, cross-node allreduce, intra-node allgather. Here: inner=ICI
axis, outer=DCN axis of a 2D mesh; results must equal the flat allreduce.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.adasum import adasum_reference


@pytest.fixture
def mesh42():
    """4 (ici) x 2 (dcn) mesh over the 8 virtual devices."""
    hvd.shutdown()
    hvd.init(mesh_shape={"dcn": 2, "ici": 4})
    yield hvd
    hvd.shutdown()


def _per_rank_values(shape, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(8, *shape).astype(np.float32)


@pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
@pytest.mark.parametrize("n_elems", [64, 37])  # 37: pad path (not % 4)
def test_matches_flat_allreduce(mesh42, op, n_elems):
    vals = _per_rank_values((n_elems,))

    def body(x):
        return hvd.hierarchical_allreduce_p(x, op=op, inner_axis="ici",
                                            outer_axis="dcn")

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=hvd.REPLICATED)
    out = np.asarray(step(jnp.asarray(vals.reshape(-1))))
    expect = vals.sum(axis=0)
    if op == hvd.Average:
        expect = expect / 8.0
    np.testing.assert_allclose(out, np.tile(expect, 1), rtol=1e-5, atol=1e-5)


def test_min_max_delegate(mesh42):
    vals = _per_rank_values((16,), seed=3)

    def body(x):
        return (hvd.hierarchical_allreduce_p(x, op=hvd.Min, inner_axis="ici",
                                             outer_axis="dcn"),
                hvd.hierarchical_allreduce_p(x, op=hvd.Max, inner_axis="ici",
                                             outer_axis="dcn"))

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=(hvd.REPLICATED, hvd.REPLICATED))
    mn, mx = step(jnp.asarray(vals.reshape(-1)))
    np.testing.assert_allclose(np.asarray(mn), vals.min(axis=0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mx), vals.max(axis=0), rtol=1e-6)


def test_adasum_vhdd(mesh42):
    """VHDD: sum within the inner axis, Adasum across the outer axis
    (reference: adasum_gpu_operations.h). Validated against the NumPy
    reference model on the slice-sums."""
    vals = _per_rank_values((32,), seed=7)

    def body(x):
        return hvd.hierarchical_allreduce_p(x, op=hvd.Adasum,
                                            inner_axis="ici",
                                            outer_axis="dcn")

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=hvd.REPLICATED)
    out = np.asarray(step(jnp.asarray(vals.reshape(-1))))
    # Mesh layout: device (dcn=d, ici=i) holds vals[d*4+i]. The inner
    # reduce-scatter leaves chunk i of each dcn-group sum on ici rank i;
    # Adasum then combines the two groups PER CHUNK (dot products over the
    # chunk, matching the reference's per-buffer VHDD math), and allgather
    # concatenates the chunks.
    s0, s1 = vals[0:4].sum(axis=0), vals[4:8].sum(axis=0)
    chunk = len(s0) // 4
    expect = np.concatenate([
        adasum_reference([s0[i * chunk:(i + 1) * chunk],
                          s1[i * chunk:(i + 1) * chunk]])
        for i in range(4)])
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_allreduce_gradients_hierarchical(mesh42):
    """The gradient API routes a pytree through the hierarchical path."""
    vals = _per_rank_values((8,), seed=11)

    def body(x):
        grads = {"a": x, "b": 2.0 * x}
        return hvd.allreduce_gradients(grads, op=hvd.Average,
                                       hierarchical=("ici", "dcn"))

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=hvd.REPLICATED)
    out = step(jnp.asarray(vals.reshape(-1)))
    expect = vals.mean(axis=0)
    np.testing.assert_allclose(np.asarray(out["a"]), expect, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]), 2 * expect, rtol=1e-5,
                               atol=1e-6)


def test_eager_raises(mesh42):
    with pytest.raises(ValueError, match="in-step only"):
        hvd.allreduce_gradients({"g": jnp.ones(4)},
                                hierarchical=("ici", "dcn"))


def test_hierarchical_allgather_matches_flat(mesh42):
    """ICI gather then DCN slab gather == the flat gather in global rank
    order (reference: MPIHierarchicalAllgather, mpi_operations.cc:236-240)."""
    vals = _per_rank_values((3, 5), seed=13)  # 3 rows per rank, 2-d payload

    def body(x):
        return hvd.hierarchical_allgather_p(x, inner_axis="ici",
                                            outer_axis="dcn")

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=hvd.REPLICATED)
    hier = step(jnp.asarray(vals.reshape(-1, 5)))
    # The flat-gather result in global rank order IS the input restacked:
    # device (o, i) = rank o*4+i holds rows [rank*3, rank*3+3).
    expect = vals.reshape(-1, 5)
    np.testing.assert_allclose(np.asarray(hier), expect, rtol=1e-6)


@pytest.mark.parametrize("reduction", ["scatter_allgather", "allgather"])
def test_hierarchical_compressed_allreduce(mesh42, reduction):
    """Dense ICI reduce-scatter + compressed DCN hop + dense ICI allgather
    approximates the flat average (8-bit maxmin keeps quantization error
    small); exact with the lossless fp16-style compressor is tested via
    high-bit quantization tolerance here."""
    from horovod_tpu.compression import (MaxMinQuantizer,
                                         hierarchical_compressed_allreduce_p)
    vals = _per_rank_values((48,), seed=23)
    comp = MaxMinQuantizer(bits=8, use_pallas=False)

    def body(x):
        return hierarchical_compressed_allreduce_p(
            x, comp, inner_axis="ici", outer_axis="dcn",
            reduction=reduction, op=hvd.Average)

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=hvd.REPLICATED)
    out = np.asarray(step(jnp.asarray(vals.reshape(-1))))
    expect = vals.mean(axis=0)
    # 8-bit bucketed maxmin on the 2-way DCN hop: error bounded by one
    # quantization unit of the shard's bucket range, scaled by 1/8 average.
    scale = np.abs(vals.sum(axis=0)).max() / 255.0 / 8.0 * 2
    np.testing.assert_allclose(out, expect, atol=max(scale, 1e-4))


def test_hierarchical_compressed_invariant_input(mesh42):
    """Invariant (already autodiff-psummed) input: the compressed path must
    only normalize, like allreduce_p / hierarchical_allreduce_p — not
    re-sum (round-4 review finding: world-size-times-larger result)."""
    from horovod_tpu.compression import (MaxMinQuantizer,
                                         hierarchical_compressed_allreduce_p)
    comp = MaxMinQuantizer(bits=8, use_pallas=False)
    x = jnp.arange(8.0, dtype=jnp.float32)

    def body(x):
        # x comes in replicated (invariant over both axes).
        return (hierarchical_compressed_allreduce_p(
                    x, comp, inner_axis="ici", outer_axis="dcn",
                    op=hvd.Average),
                hierarchical_compressed_allreduce_p(
                    x, comp, inner_axis="ici", outer_axis="dcn",
                    op=hvd.Sum))

    step = hvd.run_step(body, in_specs=P(), out_specs=(P(), P()))
    avg, total = step(x)
    np.testing.assert_allclose(np.asarray(avg), np.arange(8.0) / 8.0,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.arange(8.0),
                               rtol=1e-6)


def test_hierarchical_compressed_outer_invariant(mesh42):
    """Input already reduced over the OUTER axis only (varying over inner):
    the compressed exchange must be skipped, matching the dense path —
    round-4 review repro showed an n_outer-times-too-large sum here."""
    from horovod_tpu.compression import (MaxMinQuantizer,
                                         hierarchical_compressed_allreduce_p)
    comp = MaxMinQuantizer(bits=8, use_pallas=False)
    x = jnp.arange(4.0, dtype=jnp.float32)

    def body(x):
        xv = hvd.pvary(x, "ici")  # varying over ici, invariant over dcn
        dense = hvd.hierarchical_allreduce_p(xv, op=hvd.Sum,
                                             inner_axis="ici",
                                             outer_axis="dcn")
        compressed = hierarchical_compressed_allreduce_p(
            xv, comp, inner_axis="ici", outer_axis="dcn", op=hvd.Sum)
        return dense, compressed

    step = hvd.run_step(body, in_specs=P(), out_specs=(P(), P()))
    dense, compressed = step(x)
    # Every ici rank holds the same x: sum over ici = 4x; dcn already done.
    np.testing.assert_allclose(np.asarray(dense), 4.0 * np.arange(4.0),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(compressed), np.asarray(dense),
                               rtol=1e-2, atol=1e-2)


def test_allgather_rejects_auto_tuple(mesh42):
    """allgather(hierarchical=("auto", ...)) must fail with a clear
    message, not the misleading in-step-only error."""
    def body(x):
        return hvd.allgather(x, hierarchical=("auto", "ici", "dcn"))

    with pytest.raises(ValueError, match="allreduce_gradients"):
        hvd.run_step(body, in_specs=P(("dcn", "ici")),
                     out_specs=hvd.REPLICATED)(jnp.ones((8, 2)))


def test_hierarchical_compressed_residual(mesh42):
    """Error feedback on the DCN hop: shard-shaped residual round-trips and
    the compounded result stays close to the true average."""
    from horovod_tpu.compression import (MaxMinQuantizer,
                                         hierarchical_compressed_allreduce_p)
    vals = _per_rank_values((32,), seed=29)
    comp = MaxMinQuantizer(bits=4, use_pallas=False)
    shard_elems = 32 // 4  # flat 32 elems reduce-scattered over ici=4

    def body(x, res):
        return hierarchical_compressed_allreduce_p(
            x, comp, inner_axis="ici", outer_axis="dcn",
            reduction="scatter_allgather", op=hvd.Average, residual=res)

    step = hvd.run_step(body, in_specs=(P(("dcn", "ici")), P(("dcn", "ici"))),
                        out_specs=(hvd.REPLICATED, P(("dcn", "ici"))))
    res = jnp.zeros((8 * shard_elems,), jnp.float32)
    out, new_res = step(jnp.asarray(vals.reshape(-1)), res)
    assert np.asarray(new_res).shape == (8 * shard_elems,)
    # 4-bit is coarse; just require the result within the bucket range error.
    expect = vals.mean(axis=0)
    scale = np.abs(vals.sum(axis=0)).max() / 15.0 / 8.0 * 2
    np.testing.assert_allclose(np.asarray(out), expect, atol=scale)


def test_distributed_optimizer_hierarchical(mesh42):
    """DistributedOptimizer(hierarchical=...) reduces gradients over the
    cross-slice path; the update equals the flat-mesh update."""
    import optax

    vals = _per_rank_values((4,), seed=31)
    params = {"w": jnp.ones((4,), jnp.float32)}

    def make_step(hierarchical):
        opt = hvd.DistributedOptimizer(optax.sgd(0.5),
                                       hierarchical=hierarchical)

        def body(p, x):
            grads = {"w": x}  # per-device "gradient"
            updates, _ = opt.update(grads, opt.init(p), p)
            return optax.apply_updates(p, updates)

        return hvd.run_step(body, in_specs=(hvd.REPLICATED,
                                            P(("dcn", "ici"))),
                            out_specs=hvd.REPLICATED)

    out = make_step(("ici", "dcn"))(params, jnp.asarray(vals.reshape(-1)))
    expect = 1.0 - 0.5 * vals.mean(axis=0)
    np.testing.assert_allclose(np.asarray(out["w"]), expect, rtol=1e-5,
                               atol=1e-6)


def test_optimizer_hierarchical_invariant_grads(mesh42):
    """The common drop-in usage: replicated params + jax.value_and_grad
    WITHOUT hvd.pvary — autodiff already psums the gradient (invariant
    vma), so the hierarchical route must only normalize, exactly like the
    dense path (round-4 review finding: it re-summed, a world-size-times-
    larger update)."""
    import optax

    vals = _per_rank_values((6,), seed=37)
    w0 = jnp.zeros((6,), jnp.float32)

    def make_step(hierarchical, axis=None):
        opt = hvd.DistributedOptimizer(optax.sgd(1.0), axis=axis,
                                       hierarchical=hierarchical)

        def body(p, x):
            # d/dp of mean(p * x_local) psums across devices under
            # check_vma: grads arrive INVARIANT (already globally summed).
            loss, grads = jax.value_and_grad(
                lambda q: (q["w"] * x).sum() / 8.0)(p)
            updates, _ = opt.update(grads, opt.init(p), p)
            return optax.apply_updates(p, updates)

        return hvd.run_step(body, in_specs=(hvd.REPLICATED,
                                            P(("dcn", "ici"))),
                            out_specs=hvd.REPLICATED)

    hier = make_step(("ici", "dcn"))({"w": w0},
                                     jnp.asarray(vals.reshape(-1)))
    # Dense baseline over BOTH axes explicitly (on a 2-axis mesh the
    # default dp_axis is just the first axis).
    dense = make_step(None, axis=("dcn", "ici"))(
        {"w": w0}, jnp.asarray(vals.reshape(-1)))
    np.testing.assert_allclose(np.asarray(hier["w"]),
                               np.asarray(dense["w"]), rtol=1e-5,
                               atol=1e-6)
    # And both equal the analytic average-gradient step.
    expect = -vals.sum(axis=0) / 8.0 / 8.0
    np.testing.assert_allclose(np.asarray(hier["w"]), expect, rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(ValueError, match="compressor"):
        from horovod_tpu.compression import MaxMinQuantizer
        hvd.DistributedOptimizer(optax.sgd(0.5), hierarchical=("ici", "dcn"),
                                 compression=MaxMinQuantizer(bits=4))


def test_optimizer_hierarchical_predivide_eager_raises(mesh42):
    """hierarchical + gradient_predivide_factor outside a trace must give
    the clear in-step-only error, not an unbound-axis failure."""
    import optax

    opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                   hierarchical=("ici", "dcn"),
                                   gradient_predivide_factor=2.0)
    state = opt.init({"w": jnp.ones(3)})
    with pytest.raises(ValueError, match="in-step only"):
        opt.update({"w": jnp.ones(3)}, state, {"w": jnp.ones(3)})


def test_fused_hierarchical_group_reduction(mesh42, monkeypatch):
    """allreduce_gradients(hierarchical=...) fuses same-dtype same-vma
    leaves into ONE hierarchical reduction per group (reference:
    FuseResponses, controller.cc:686) and stays numerically equal to the
    per-leaf result."""
    from horovod_tpu.ops import collectives as C

    calls = []
    real = C.hierarchical_allreduce_p

    def counting(x, **kw):
        calls.append(x.shape)
        return real(x, **kw)

    monkeypatch.setattr(C, "hierarchical_allreduce_p", counting)
    vals = _per_rank_values((4,), seed=41)

    def body(x):
        grads = {"a": x, "b": 2.0 * x,            # f32 varying group
                 "c": x.astype(jnp.bfloat16),     # bf16 varying group
                 "s": x[0]}                       # f32 varying scalar
        return hvd.allreduce_gradients(grads, op=hvd.Average,
                                       hierarchical=("ici", "dcn"))

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=hvd.REPLICATED)
    out = step(jnp.asarray(vals.reshape(-1)))
    # Two groups -> two hierarchical reductions, not four.
    assert len(calls) == 2, calls
    expect = vals.mean(axis=0)
    np.testing.assert_allclose(np.asarray(out["a"]), expect, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]), 2 * expect, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["c"]),
                               expect.astype(np.float32), rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(out["s"]), expect[0], rtol=1e-5,
                               atol=1e-6)


def test_hierarchical_allgather_via_public_api(mesh42):
    """hvd.allgather(hierarchical=...) routes in-step; eager raises."""
    vals = _per_rank_values((2, 4), seed=17)

    def body(x):
        return hvd.allgather(x, hierarchical=("ici", "dcn"))

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=hvd.REPLICATED)
    out = step(jnp.asarray(vals.reshape(-1, 4)))
    np.testing.assert_allclose(np.asarray(out), vals.reshape(-1, 4),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="in-step only"):
        hvd.allgather(jnp.ones((2, 2)), hierarchical=("ici", "dcn"))

def test_hierarchical_compressed_residual_bootstrap(mesh42):
    """residual="init" bootstraps error feedback without the caller knowing
    the internal shard layout (round-4 advisor finding: the documented
    'zeros of the returned residual's shape' was undiscoverable). The
    returned residual feeds the next call unchanged."""
    from horovod_tpu.compression import (MaxMinQuantizer,
                                         hierarchical_compressed_allreduce_p)
    comp = MaxMinQuantizer(bits=4, use_pallas=False)
    vals = _per_rank_values((48,), seed=31)

    def body(x):
        y1, res1 = hierarchical_compressed_allreduce_p(
            x, comp, inner_axis="ici", outer_axis="dcn", op=hvd.Average,
            residual="init")
        y2, res2 = hierarchical_compressed_allreduce_p(
            x, comp, inner_axis="ici", outer_axis="dcn", op=hvd.Average,
            residual=res1)
        return y1, y2, res1, res2

    step = hvd.run_step(body, in_specs=P(("dcn", "ici")),
                        out_specs=(hvd.REPLICATED, hvd.REPLICATED,
                                   P(("dcn", "ici")), P(("dcn", "ici"))))
    y1, y2, res1, res2 = step(jnp.asarray(vals.reshape(-1)))
    expect = vals.mean(axis=0)
    scale = np.abs(vals.sum(axis=0)).max() / 15.0 / 8.0 * 2
    np.testing.assert_allclose(np.asarray(y1), expect, atol=max(scale, 1e-4))
    # Error feedback: the second call's result (fed the first residual)
    # must not be wildly off either, and residual shapes must agree.
    assert res1.shape == res2.shape
    np.testing.assert_allclose(np.asarray(y2), expect, atol=max(scale, 1e-4))
