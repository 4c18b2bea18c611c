"""Wire-level compression tests (ISSUE 3).

Covers the cross-implementation parity contract — the native bucket-512
max-min quantizer (native/compressed.{h,cpp}) must produce byte-identical
(min, unit) headers and codes to the JAX-level MaxMinQuantizer
(compression/quantize.py) on identical inputs, so the wire and in-step
paths can never silently diverge — plus the process-mode integration:
compressed allreduce correctness, the min-bytes bypass and bias/norm skip
list, timeline raw/wire byte counters (int8 >= 3.5x), error feedback at
the wire level, and a slow-marked small-model training run whose loss
curve must match the dense baseline.
"""

import ctypes
import json
import os

import numpy as np
import pytest

from conftest import assert_all_ok, launch_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")

INT8, INT4 = 2, 3  # hvdtpu::WireCompression


def _wire_lib():
    # The shared _C_API table covers the wire codec trio (version-gated);
    # registering through it keeps this file out of the ABI-MIRROR lint's
    # "registration outside the canonical table" findings.
    from horovod_tpu import basics
    return basics.register_c_api(ctypes.CDLL(basics._ensure_built()))


def _native_compress(lib, mode, x, residual=None):
    count = x.shape[0]
    nbytes = lib.hvdtpu_wire_compressed_bytes(mode, count)
    wire = np.zeros(nbytes, np.uint8)
    rc = lib.hvdtpu_wire_compress(
        mode, x.ctypes.data, count, wire.ctypes.data,
        residual.ctypes.data if residual is not None else None)
    assert rc == 0
    return wire


class TestNativeJaxParity:
    """Native int8/int4 wire quantizer vs compression/quantize.py on
    identical inputs: same bucket-512 (min, unit) encoding, same codes."""

    @pytest.mark.parametrize("mode,bits", [(INT8, 8), (INT4, 4)])
    @pytest.mark.parametrize("count", [512, 1300, 7, 513])
    def test_codes_and_headers_match(self, mode, bits, count):
        from horovod_tpu.compression.quantize import (MaxMinQuantizer,
                                                      unpack_bits)
        import jax.numpy as jnp

        rng = np.random.RandomState(7 + count)
        x = rng.uniform(-3.0, 3.0, count).astype(np.float32)
        lib = _wire_lib()
        wire = _native_compress(lib, mode, x)

        nb = -(-count // 512)
        header = np.frombuffer(wire[:nb * 8].tobytes(),
                               np.float32).reshape(nb, 2)
        codes_bytes = wire[nb * 8:]
        if bits == 8:
            native_codes = codes_bytes[:count]
        else:
            lo = codes_bytes & 0x0F
            hi = codes_bytes >> 4
            native_codes = np.stack([lo, hi], axis=1).reshape(-1)[:count]

        q = MaxMinQuantizer(bits=bits, bucket_size=512, use_pallas=False)
        payload, ctx = q.compress(jnp.asarray(x))
        jax_codes = np.asarray(unpack_bits(payload["q"], bits,
                                           nb * 512))[:count]

        np.testing.assert_array_equal(native_codes, jax_codes)
        np.testing.assert_array_equal(header[:, 0],
                                      np.asarray(payload["min"]).reshape(-1))
        np.testing.assert_array_equal(header[:, 1],
                                      np.asarray(payload["unit"]).reshape(-1))

        # Decompression parity: both sides decode mn + code * unit.
        out = np.zeros(count, np.float32)
        lib.hvdtpu_wire_decompress(mode, wire.ctypes.data, count,
                                   out.ctypes.data)
        jd = np.asarray(q.decompress(payload, ctx))
        np.testing.assert_allclose(out, jd, rtol=0, atol=1e-7)

    def test_error_feedback_residual_shrinks_error(self):
        """The standalone C API's residual argument implements the same
        error feedback the data plane applies: two compressions of the same
        vector leave a residual that reconstructs it far better than one."""
        lib = _wire_lib()
        rng = np.random.RandomState(3)
        x = rng.uniform(-1.0, 1.0, 1024).astype(np.float32)
        residual = np.zeros(1024, np.float32)
        acc = np.zeros(1024, np.float64)
        T = 50
        for _ in range(T):
            wire = _native_compress(lib, INT4, x, residual)
            out = np.zeros(1024, np.float32)
            lib.hvdtpu_wire_decompress(INT4, wire.ctypes.data, 1024,
                                       out.ctypes.data)
            acc += out
        one_shot = np.abs(
            np.asarray(acc / T) - x).max()  # already EF-averaged
        # The mean of T EF-quantized decodes telescopes to x +- r_T / T.
        wire0 = _native_compress(lib, INT4, x)
        raw = np.zeros(1024, np.float32)
        lib.hvdtpu_wire_decompress(INT4, wire0.ctypes.data, 1024,
                                   raw.ctypes.data)
        single = np.abs(raw - x).max()
        assert single > 1e-4  # int4 really quantizes
        assert one_shot <= single / 8.0, (one_shot, single)


@pytest.mark.parametrize("mode", ["none", "fp16", "int8", "int4"])
def test_process_mode_compressed_allreduce(tmp_path, mode):
    """2-rank process-mode world under each wire mode: quantized-sum
    accuracy, min-bytes bypass, skip regex, wire-level error feedback, and
    the timeline compression tag + raw/wire counters (int8 >= 3.5x)."""
    results = launch_world(
        2, os.path.join(DATA, "compressed_worker.py"),
        extra_env={
            "HVDTPU_COMPRESSION": mode,
            "TEST_TIMELINE_PATH": str(tmp_path / "tl"),
        })
    assert_all_ok(results)


def test_process_mode_compressed_world_4(tmp_path):
    """Compression across a 4-rank world (ragged ring chunks + shm lanes)."""
    results = launch_world(
        4, os.path.join(DATA, "compressed_worker.py"),
        extra_env={
            "HVDTPU_COMPRESSION": "int8",
            "TEST_TIMELINE_PATH": str(tmp_path / "tl"),
        })
    assert_all_ok(results)


def test_bad_compression_value_rejected():
    from horovod_tpu.utils import envvars as ev
    with pytest.raises(ValueError):
        ev.get_wire_compression("int7")
    assert ev.get_wire_compression("int8") == 2
    assert ev.get_wire_compression("maxmin", bits=8) == 2
    assert ev.get_wire_compression("maxmin", bits=4) == 3
    assert ev.get_wire_compression("topk") == 0
    assert ev.get_wire_compression("auto") == 4


def _run_training(mode):
    results = launch_world(
        2, os.path.join(DATA, "compressed_train_worker.py"),
        extra_env={
            "HVDTPU_COMPRESSION": mode,
            "HVDTPU_COMPRESSION_MIN_BYTES": "512",
        }, timeout=150)
    assert_all_ok(results)
    for _rc, out, _err in results:
        for line in out.splitlines():
            if line.startswith("LOSSES "):
                return json.loads(line[len("LOSSES "):])
    raise AssertionError("no LOSSES line in worker output")


@pytest.mark.slow
def test_compressed_training_matches_dense_loss_curve():
    """int8+EF gradient compression must track the uncompressed loss curve
    within tolerance and converge to (near-)identical final loss — the
    reference fork's end-to-end claim, at the wire level."""
    dense = _run_training("none")
    comp = _run_training("int8")
    assert len(dense) == len(comp)
    # Final loss: compressed within 20% of dense (both near the noise floor).
    assert comp[-1] <= dense[-1] * 1.2 + 1e-4, (dense[-1], comp[-1])
    # The curves track pointwise over the second half of training.
    for a, b in zip(dense[len(dense) // 2:], comp[len(comp) // 2:]):
        assert abs(a - b) <= 0.2 * max(abs(a), abs(b)) + 1e-4, (a, b)
