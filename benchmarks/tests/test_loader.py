"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as files of their own and entries appended to BENCHMARK.json, and
edits no file that is there. Shown on a copy, in rehearsal."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _digests(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _run(root, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "horovod_tpu"),
               os.path.join(root, "horovod_tpu"))
    return root


def test_new_cell_config_and_metric_are_files_and_entries_only(copy):
    before = _digests(copy)
    bench_dir = os.path.join(copy, "benchmarks")
    # A configuration: its file (real sizes) and its rehearsal twin.
    tiny = {"name": "tinygpt", "job": "gpt_dp", "hidden_size": 32,
            "intermediate_size": 64, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 16,
            "num_hidden_layers": 1, "vocab_size": 128,
            "sliding_window": 4096, "compute_dtype": "bfloat16",
            "optimizer": {"name": "adamw", "lr": 1e-4, "b1": 0.9, "b2": 0.999,
                          "eps": 1e-8, "weight_decay": 1e-4},
            "attention": "flash", "remat": "none",
            "check": {"seq_len": 128, "sequences_per_chip": 1}}
    mix = {"global_batch": 4, "seq_len": 128, "log_every": 2}
    for base in (bench_dir, os.path.join(bench_dir, "tests", "data")):
        with open(os.path.join(base, "configs", "tinygpt.json"), "w") as f:
            json.dump(tiny, f)
        with open(os.path.join(base, "traffic", "s128_b4.json"), "w") as f:
            json.dump(mix, f)
    # A per-layer metric: a reader of its own.
    with open(os.path.join(bench_dir, "layer_metrics",
                           "tok_fence_ms.py"), "w") as f:
        f.write('def read(ctx):\n    return ctx.span_median_ms("fence")\n')
    # Entries appended; nothing that was there is touched.
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tinygpt", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tinygpt.json"})
    bench["workloads"].append({
        "name": "tinygpt_s128", "config": "tinygpt", "traffic": "s128_b4",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "tok_fence_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "Compiled step",
        "moves": "tok_s_chip", "workloads": ["tinygpt_s128"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("tok_s_chip", "tok_dispatch_ms"):
            m["workloads"].append("tinygpt_s128")
    with open(path, "w") as f:
        json.dump(bench, f)

    done = _run(copy, "--workload", "tinygpt_s128", "--seed", "0",
                "--seconds", "1", "--trace", "1", "--rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True and "metrics" not in result
    assert all(line.startswith("[rehearsal platform: cpu] ")
               for line in lines[:-1])
    read = [ln for ln in lines if "metrics read" in ln][0].split(": ")[-1]
    # The new metric and an old one the cell was added to; not the rest.
    assert set(read.split()) >= {"tok_fence_ms", "tok_dispatch_ms"}
    assert "tok_placement_ms" not in read.split()

    after = _digests(copy)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == sorted([
        "benchmarks/configs/tinygpt.json",
        "benchmarks/traffic/s128_b4.json",
        "benchmarks/tests/data/configs/tinygpt.json",
        "benchmarks/tests/data/traffic/s128_b4.json",
        "benchmarks/layer_metrics/tok_fence_ms.py"])


def test_no_chip_no_result(copy):
    """Off the TPU and without --rehearsal the run fails and prints no
    result line."""
    done = _run(copy, "--workload", "resnet50_dp1", "--seconds", "1")
    assert done.returncode != 0
    assert '"correct"' not in done.stdout and '"metrics"' not in done.stdout


def test_unknown_cell(copy):
    done = _run(copy, "--workload", "nope", "--rehearsal")
    assert done.returncode != 0 and "no workload named" in done.stderr
