import os, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import horovod_tpu as hvd

hvd.init()
r, n = hvd.rank(), hvd.size()
assert hvd.mode() == "process", hvd.mode()

# allreduce average
x = np.full((4, 3), float(r), np.float32)
out = hvd.allreduce(x, name="t1", op=hvd.Average)
expect = np.full((4, 3), sum(range(n)) / n)
np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)

# allreduce sum with prescale
out = hvd.allreduce(x, name="t2", op=hvd.Sum, prescale_factor=2.0)
np.testing.assert_allclose(np.asarray(out), np.full((4, 3), 2.0 * sum(range(n))), rtol=1e-6)

# broadcast
b = np.arange(5, dtype=np.float64) * (r + 1)
out = hvd.broadcast(b, root_rank=1, name="b1")
np.testing.assert_allclose(np.asarray(out), np.arange(5) * 2.0)

# allgather with varying first dim
g = np.full((r + 1, 2), float(r), np.float32)
out = np.asarray(hvd.allgather(g, name="g1"))
assert out.shape == (sum(range(1, n + 1)), 2), out.shape
off = 0
for i in range(n):
    np.testing.assert_allclose(out[off:off + i + 1], float(i)); off += i + 1

# alltoall even splits
a = np.arange(n * 2, dtype=np.int32).reshape(n, 2) + 100 * r
out = np.asarray(hvd.alltoall(a, name="a1"))
expect = np.stack([np.arange(2, dtype=np.int32) + 2 * r + 100 * i for i in range(n)])
np.testing.assert_array_equal(out, expect)

# alltoall UNEVEN splits + received_splits (reference: operations.cc:1055;
# the controller negotiates the full splits matrix). Rank r sends r+1 rows
# to rank 0 and 1 row to every other rank.
sp = np.ones(n, np.int32)
sp[0] = r + 1
rows = int(sp.sum())
u = (np.arange(rows, dtype=np.int32) + 1000 * r).reshape(rows, 1)
out, recv = hvd.alltoall(u, splits=sp, name="a2")
out = np.asarray(out)
np.testing.assert_array_equal(np.asarray(recv),
                              [i + 1 if r == 0 else 1 for i in range(n)])
# Rank 0 receives each source's first i+1 rows; others receive one row at
# offset (i+1) + (r-1) of source i's buffer.
if r == 0:
    expect = np.concatenate(
        [(np.arange(i + 1, dtype=np.int32) + 1000 * i) for i in range(n)])
else:
    expect = np.array([(i + 1) + (r - 1) + 1000 * i for i in range(n)],
                      np.int32)
np.testing.assert_array_equal(out.reshape(-1), expect)

# int64 min/max
m = np.array([r, -r, 7], dtype=np.int64)
np.testing.assert_array_equal(np.asarray(hvd.allreduce(m, name="mn", op=hvd.Min)), [0, -(n - 1), 7])
np.testing.assert_array_equal(np.asarray(hvd.allreduce(m, name="mx", op=hvd.Max)), [n - 1, 0, 7])

# bfloat16
import ml_dtypes
bf = np.ones((8,), dtype=ml_dtypes.bfloat16) * (r + 1)
out = np.asarray(hvd.allreduce(bf, name="bf", op=hvd.Sum))
np.testing.assert_allclose(out.astype(np.float32), float(sum(range(1, n + 1))))

# grouped (fusion path)
outs = hvd.grouped_allreduce([np.full(3, float(r), np.float32), np.full(5, 2.0 * r, np.float32)], name="grp", op=hvd.Average)
np.testing.assert_allclose(np.asarray(outs[0]), sum(range(n)) / n, rtol=1e-6)
np.testing.assert_allclose(np.asarray(outs[1]), 2 * sum(range(n)) / n, rtol=1e-6)

# broadcast_object / allgather_object
obj = hvd.broadcast_object({"lr": 0.1 * (r + 1), "step": r}, root_rank=0)
assert obj == {"lr": 0.1, "step": 0}, obj
objs = hvd.allgather_object(f"rank{r}")
assert objs == [f"rank{i}" for i in range(n)], objs

# error agreement: mismatched shapes
try:
    hvd.allreduce(np.ones((r + 1,), np.float32), name="bad_shape")
    print(f"[{r}] ERROR: no exception", file=sys.stderr); sys.exit(1)
except hvd.TensorShapeMismatchError as e:
    pass

# error agreement: mismatched dtype
try:
    hvd.allreduce(np.ones(3, np.float32 if r == 0 else np.float64), name="bad_dtype")
    sys.exit(1)
except hvd.TensorDtypeMismatchError:
    pass

# error agreement: mismatched OP KIND under one name (reference:
# ConstructResponse op validation) — every rank gets the same error.
# (This menu always runs at n >= 2; mismatches need a second rank.)
assert n >= 2, "error-agreement menu requires world size >= 2"
try:
    if r == 0:
        hvd.allreduce(np.ones(3, np.float32), name="bad_op")
    else:
        hvd.allgather(np.ones(3, np.float32), name="bad_op")
    sys.exit(1)
except hvd.HvdTpuInternalError as e:
    assert "Mismatched collective operations" in str(e), e

# error agreement: mismatched broadcast root
try:
    hvd.broadcast(np.ones(2, np.float32), root_rank=r % 2, name="bad_root")
    sys.exit(1)
except hvd.HvdTpuInternalError as e:
    assert "Mismatched broadcast root ranks" in str(e), e

# adasum
v = np.zeros(4, np.float32); v[r % 4] = r + 1.0
out = np.asarray(hvd.allreduce(v, name="ad", op=hvd.Adasum))
from horovod_tpu.ops.adasum import adasum_reference
vals = []
for i in range(n):
    w = np.zeros(4, np.float32); w[i % 4] = i + 1.0; vals.append(w)
np.testing.assert_allclose(out, adasum_reference(vals), rtol=1e-4, atol=1e-5)

# join
last = hvd.join()
print(f"[{r}] ALL OK last_joined={last}")
hvd.shutdown()
