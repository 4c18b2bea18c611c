"""Torch interop surface tests (reference: test/test_torch.py shapes).

Multi-process over localhost TCP per SURVEY.md §4, plus single-process
behavioral checks that don't need a world.
"""

import os

import pytest

from conftest import assert_all_ok, launch_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "data", "torch_worker.py")


@pytest.mark.parametrize("n", [2, 3])
def test_torch_surface_multiprocess(n):
    assert_all_ok(launch_world(n, WORKER, timeout=150))


class TestSingleProcess:
    """SPMD-mode semantics on torch tensors (size == device count; eager ops
    follow the documented replicated-input semantics)."""

    def test_allreduce_and_grad(self, spmd8):
        import torch
        import horovod_tpu.torch as hvd
        n = hvd.size()
        t = torch.ones(4, requires_grad=True)
        out = hvd.allreduce(t, op=hvd.Sum)
        assert torch.allclose(out.detach(), torch.full((4,), float(n)))
        out.sum().backward()
        assert torch.allclose(t.grad, torch.full((4,), float(n)))

    def test_optimizer_trains(self, spmd8):
        import numpy as np
        import torch
        import horovod_tpu.torch as hvd
        torch.manual_seed(0)
        model = torch.nn.Linear(8, 1)
        opt = hvd.DistributedOptimizer(
            torch.optim.Adam(model.parameters(), lr=5e-2),
            named_parameters=model.named_parameters())
        rng = np.random.RandomState(0)
        X = torch.tensor(rng.randn(32, 8), dtype=torch.float32)
        Y = X.sum(dim=1, keepdim=True)
        losses = []
        for _ in range(120):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(X), Y)
            loss.backward()
            opt.step()
            losses.append(float(loss.detach()))
        assert losses[-1] < losses[0] * 0.2, losses[::10]

    def test_torch_state_commit_restore(self, spmd8):
        """TorchState captures and restores model/optimizer by value
        (reference: test_elastic_torch.py state semantics)."""
        import torch
        import horovod_tpu.torch as hvd
        model = torch.nn.Linear(4, 2)
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        state = hvd.elastic.TorchState(model=model, optimizer=opt, batch=7)
        before = {k: v.clone() for k, v in model.state_dict().items()}
        state.commit()
        with torch.no_grad():
            for p in model.parameters():
                p.add_(1.0)
        state.batch = 99
        state.restore()
        for k, v in model.state_dict().items():
            assert torch.equal(v, before[k]), k
        assert state.batch == 7

    def test_torch_state_durable_resume(self, spmd8, tmp_path):
        """TorchState(checkpoint_dir=...): durable commits survive a
        simulated full-job restart (parity with TpuState's durable layer)."""
        import torch
        import horovod_tpu.torch as hvd
        path = str(tmp_path / "tstate")
        model = torch.nn.Linear(4, 2)
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        state = hvd.elastic.TorchState(model=model, optimizer=opt,
                                       checkpoint_dir=path, epoch=0)
        with torch.no_grad():
            for p in model.parameters():
                p.fill_(3.0)
        state.epoch = 4
        state.commit()
        expect = {k: v.clone() for k, v in model.state_dict().items()}

        fresh_model = torch.nn.Linear(4, 2)
        fresh_opt = torch.optim.SGD(fresh_model.parameters(), lr=0.1)
        fresh = hvd.elastic.TorchState(model=fresh_model,
                                       optimizer=fresh_opt,
                                       checkpoint_dir=path, epoch=0)
        # Construction must NOT write a durable step (untrained params
        # would shadow the real latest commit for the next restart).
        from horovod_tpu import latest_checkpoint_step
        assert latest_checkpoint_step(path) == 1
        assert fresh.load_from_checkpoint() is True
        assert fresh.epoch == 4
        for k, v in fresh_model.state_dict().items():
            assert torch.equal(v, expect[k]), k

        nothing = hvd.elastic.TorchState(
            model=torch.nn.Linear(2, 2),
            checkpoint_dir=str(tmp_path / "none"))
        assert nothing.load_from_checkpoint() is False

        # sync() (run by hvd.elastic.run BEFORE training) must stay
        # in-memory: a durable write there would record untrained params
        # as the newest step (round-4 review finding).
        synced = hvd.elastic.TorchState(
            model=torch.nn.Linear(2, 2),
            checkpoint_dir=str(tmp_path / "sync"), epoch=0)
        synced.sync()
        assert latest_checkpoint_step(str(tmp_path / "sync")) is None
        synced.commit()
        assert latest_checkpoint_step(str(tmp_path / "sync")) == 1

    def test_named_parameters_validation(self, spmd8):
        """Reference: optimizer.py:44-63 — non-tuple sequences, duplicate
        names, and partially-named models are user errors."""
        import torch
        import horovod_tpu.torch as hvd
        model = torch.nn.Linear(4, 2)
        with pytest.raises(ValueError, match="tuples"):
            hvd.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=list(model.parameters()))
        with pytest.raises(ValueError, match="duplicates"):
            hvd.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=[("p", p) for p in model.parameters()])
        with pytest.raises(ValueError, match="not named"):
            hvd.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=list(model.named_parameters())[:1])

    def test_non_cpu_grad_rejected(self, spmd8):
        """Host-only scope (optimizer.py module docstring): a gradient on
        any non-CPU device reaching _allreduce_grad_async must raise a clear
        ValueError naming the device and the fix, not silently round-trip
        (or corrupt) device memory. The meta device stands in for CUDA/XLA —
        the guard is on device.type != 'cpu', so any accelerator device
        takes the same path."""
        import torch
        import horovod_tpu.torch as hvd
        model = torch.nn.Linear(4, 2)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters())
        p = model.weight
        meta_p = torch.nn.Parameter(torch.empty(2, 4, device="meta"))
        meta_p.grad = torch.empty(2, 4, device="meta")
        opt._param_names[id(meta_p)] = "meta.weight"
        with pytest.raises(ValueError, match="host-only.*meta"):
            opt._allreduce_grad_async(meta_p)
        # CPU grads still pass the guard (full path covered by the training
        # tests above).
        p.grad = torch.zeros_like(p)
        handle, _ctx = opt._allreduce_grad_async(p)
        assert handle is not None

    def test_predivide_requires_average(self, spmd8):
        import torch
        import horovod_tpu.torch as hvd
        model = torch.nn.Linear(4, 2)
        with pytest.raises(ValueError, match="op != Average"):
            hvd.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                op=hvd.Sum, gradient_predivide_factor=2.0)

    def test_resume_with_accumulation(self, spmd8):
        """load_state_dict mid-accumulation must reset delay counters
        (reference: optimizer.py:81-89; round-2 verdict weak #4: stale
        counters were a real hang risk after resume)."""
        import torch
        import horovod_tpu.torch as hvd
        model = torch.nn.Linear(4, 1)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
            backward_passes_per_step=2)
        sd = opt.state_dict()
        model(torch.ones(2, 4)).sum().backward()  # mid-window (delay 1)
        opt.load_state_dict(sd)
        for p in model.parameters():
            assert opt._allreduce_delay[p] == 2
        assert opt._handles == {}
        opt.zero_grad()
        for micro in range(2):
            model(torch.ones(2, 4) * (micro + 1)).sum().backward()
        opt.step()  # completes without hanging on a stale counter

    def test_set_backward_passes_per_step(self, spmd8):
        import torch
        import horovod_tpu.torch as hvd
        model = torch.nn.Linear(4, 1)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
            backward_passes_per_step=2)
        opt.set_backward_passes_per_step(3)
        assert opt.backward_passes_per_step == 3
        assert all(v == 3 for v in opt._allreduce_delay.values())
        opt.zero_grad()
        for micro in range(3):
            model(torch.ones(2, 4)).sum().backward()
        opt.step()

    def test_sync_batch_norm_matches_local_when_replicated(self, spmd8):
        """SPMD eager semantics: identical per-rank batches make SyncBN
        numerically equal to local BN (global stats == local stats)."""
        import torch
        import horovod_tpu.torch as hvd
        torch.manual_seed(3)
        bn = hvd.SyncBatchNorm(5)
        ref = torch.nn.BatchNorm2d(5)
        ref.load_state_dict({k: v.clone() for k, v in bn.state_dict().items()})
        x = torch.randn(6, 5, 3, 3)
        xa = x.clone().requires_grad_(True)
        xb = x.clone().requires_grad_(True)
        out = bn(xa)
        expect = ref(xb)
        assert torch.allclose(out, expect, atol=1e-5)
        w = torch.randn_like(out)
        (out * w).sum().backward()
        (expect * w).sum().backward()
        assert torch.allclose(xa.grad, xb.grad, atol=1e-5)
        assert torch.allclose(bn.running_mean, ref.running_mean, atol=1e-6)
        # running_var differs only by the unbiased correction: SyncBN uses
        # the GLOBAL count (8 ranks x 54) where local BN uses 54.
        count_local = x.numel() // x.size(1)
        count_global = count_local * hvd.size()
        var_biased = (ref.running_var - 0.9) / 0.1 * \
            (count_local - 1) / count_local
        expect_var = 0.9 + 0.1 * var_biased * count_global / (count_global - 1)
        assert torch.allclose(bn.running_var, expect_var, atol=1e-5)

    def test_compression_fp16_roundtrip(self):
        import torch
        from horovod_tpu.torch.compression import Compression
        t = torch.randn(16, dtype=torch.float32)
        c, ctx = Compression.fp16.compress(t)
        assert c.dtype == torch.float16
        out = Compression.fp16.decompress(c, ctx)
        assert out.dtype == torch.float32
        assert torch.allclose(out, t, atol=1e-2)

    def test_bfloat16_numpy_bridge(self):
        """bf16 — the dominant TPU training dtype — must round-trip through
        the numpy bridge bit-exactly (ADVICE r1: Tensor.numpy() raises on
        bf16; reference torch binding supports bf16 natively)."""
        import torch
        from horovod_tpu.torch import _to_numpy, _to_torch
        t = torch.randn(64).to(torch.bfloat16)
        a = _to_numpy(t)
        assert a.itemsize == 2  # stays 2-byte on the wire
        back = _to_torch(a, t)
        assert back.dtype == torch.bfloat16
        assert torch.equal(back, t)

    def test_bfloat16_allreduce(self, spmd8):
        import torch
        import horovod_tpu.torch as hvd
        n = hvd.size()
        t = torch.ones(8, dtype=torch.bfloat16)
        out = hvd.allreduce(t, op=hvd.Sum)
        assert out.dtype == torch.bfloat16
        assert torch.allclose(out.float(), torch.full((8,), float(n)))

    def test_unused_param_synchronize(self, spmd8):
        """A param whose hook never fires (unused in the graph) must still
        be reduced on synchronize() so all ranks issue the same collectives
        (ADVICE r1 high; reference optimizer.py:153-166)."""
        import torch
        import horovod_tpu.torch as hvd
        used = torch.nn.Linear(4, 1)
        unused = torch.nn.Linear(4, 1)
        params = list(used.parameters()) + list(unused.parameters())
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(params, lr=0.1))
        opt.zero_grad()
        loss = used(torch.ones(2, 4)).sum()
        loss.backward()
        opt.step()  # must not raise / hang; unused params get zero grads
        for p in unused.parameters():
            assert p.grad is not None
            assert torch.count_nonzero(p.grad) == 0

    def test_accumulation_forced_on_synchronize(self, spmd8):
        """backward_passes_per_step=2 with a manual synchronize() after one
        pass: the mid-accumulation param must be force-launched (reference
        handle-None handling)."""
        import torch
        import horovod_tpu.torch as hvd
        model = torch.nn.Linear(4, 1)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            backward_passes_per_step=2)
        opt.zero_grad()
        model(torch.ones(2, 4)).sum().backward()
        opt.synchronize()  # one backward pass so far: handles are parked None
        for p in model.parameters():
            assert p.grad is not None
        with opt.skip_synchronize():
            opt.step()
