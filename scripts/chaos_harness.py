#!/usr/bin/env python3
"""Chaos harness: kill / hang / partition / delay ranks mid-collective and
prove clean, fast recovery across the whole {algo x transport x hier x
compression} matrix (docs/fault-tolerance.md; ROADMAP open item 4).

Each scenario launches a REAL elastic job on localhost (two host aliases so
a blacklisted "host" leaves survivors), arms one one-shot fault via
``HVDTPU_CHAOS`` at a RANDOMIZED non-root rank and collective/hop index,
and verifies from the workers' result lines that:

* the job completes (rc == 0) with CORRECT allreduce results throughout,
* survivors detected the failure (``hvdtpu_failures_detected_total``) and
  recorded a recovery (``hvdtpu_recovery_seconds``),
* kill/drop recoveries re-form within the latency budget (detection to
  re-initialization; hang recoveries include respawning the wedged worker
  — a fresh interpreter boot — so they get a looser budget),
* a ``delay`` hiccup does NOT trip detection (no false positives).

Usage::

    python scripts/chaos_harness.py --smoke          # CI: kill+hang, tcp ring
    python scripts/chaos_harness.py                  # full kill matrix + scenario sweep
    python scripts/chaos_harness.py --algos ring --transports shm \
        --scenarios kill,drop --runs-per-combo 2
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WORKER = os.path.join(REPO, "tests", "data", "chaos_worker.py")

ALGOS = ("ring", "recursive_doubling", "tree", "scatter_allgather",
         "parameter_server")
TRANSPORTS = ("tcp", "shm")
HIERS = ("0", "1")
COMPRESSIONS = ("none", "fp16", "int8", "int4")
SCENARIOS = ("kill", "hang", "drop", "delay")
# Which collective carries the fault: the first-class op menu
# (docs/collectives.md "Reduce-scatter & allgather", "Broadcast &
# alltoall"). Every op except allreduce runs one fixed schedule (the
# ring / block rotation / binomial tree / pairwise exchange), so those
# sweeps pin algo=ring, hier=0.
OPS = ("allreduce", "reducescatter", "allgather", "broadcast", "alltoall")

# Detection-to-reformation budgets (seconds, per recovery observation).
# kill/drop: survivors only re-form — the acceptance bound. hang: recovery
# waits for the settle watchdog to terminate + respawn the wedged worker,
# and the replacement pays a fresh interpreter + jax boot.
RECOVERY_BUDGET = {"kill": 2.0, "drop": 2.0, "hang": 30.0}


def _worker_env(extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    prev = env.get("PYTHONPATH")
    env["PYTHONPATH"] = REPO + (os.pathsep + prev if prev else "")
    env.update(extra)
    return env


def run_scenario(scenario, algo, transport, hier, compression, np_, batches,
                 rng, op="allreduce", verbose=False):
    """One elastic chaos run; returns a result dict (ok + diagnostics)."""
    from horovod_tpu.runner.elastic import (ElasticSettings,
                                            HostDiscoveryScript, run_elastic)

    tmp = tempfile.mkdtemp(prefix="hvdtpu_chaos_")
    hosts = os.path.join(tmp, "hosts.txt")
    half = np_ // 2
    with open(hosts, "w") as f:
        # Two aliases of this machine: a blacklisted "host" leaves the other
        # alias's slots alive, and hier=1 sees a real two-host topology.
        f.write(f"127.0.0.1:{np_ - half}\nlocalhost:{half}\n")
    script = os.path.join(tmp, "discover.sh")
    with open(script, "w") as f:
        f.write(f"#!/bin/sh\ncat {hosts}\n")
    os.chmod(script, 0o755)

    target = rng.randrange(1, np_)        # non-root rank
    if rng.random() < 0.5:
        trigger = f"op={rng.randrange(2, max(3, batches - 1))}"
    else:
        trigger = f"hop={rng.randrange(1, 12)}"
    action = {"kill": "kill", "hang": "hang", "drop": "drop",
              "delay": "delay=300"}[scenario]
    spec = f"rank{target}:{action}@{trigger}"

    results = os.path.join(tmp, "results.txt")
    env = _worker_env({
        "CHAOS_RESULT_FILE": results,
        "CHAOS_TARGET_BATCHES": str(batches),
        "HVDTPU_CHAOS": spec,
        "HVDTPU_CHAOS_MARKER": os.path.join(tmp, "chaos.marker"),
        "CHAOS_OP": op,
        "HVDTPU_ALLREDUCE_ALGO": algo,
        "HVDTPU_SHM": "1" if transport == "shm" else "0",
        "HVDTPU_ALLREDUCE_HIER": hier,
        "HVDTPU_COMPRESSION": compression,
        # Fast-hang/partition detection: the read deadline is the only
        # signal for a live-but-silent lane. Delay=300ms must NOT trip it.
        "HVDTPU_READ_DEADLINE_SECONDS": "1",
        "HVDTPU_STALL_CHECK_DISABLE": "1",
    })
    settings = ElasticSettings(min_np=2, max_np=np_,
                               discovery_interval_s=0.3,
                               elastic_timeout_s=120,
                               settle_timeout_s=2.0)
    t0 = time.time()
    rc = run_elastic(HostDiscoveryScript(script), settings,
                     [sys.executable, WORKER], env, verbose=verbose)
    wall = time.time() - t0

    res = {"scenario": scenario, "op": op, "algo": algo,
           "transport": transport, "hier": hier, "compression": compression,
           "spec": spec, "rc": rc, "wall_s": round(wall, 2), "ok": False,
           "why": ""}
    lines = open(results).read().splitlines() if os.path.exists(results) \
        else []
    done = [ln for ln in lines if ln.startswith("done ")]
    if rc != 0:
        res["why"] = f"job failed rc={rc}"
        return res
    if any(ln.startswith("WRONG") for ln in lines):
        res["why"] = f"incorrect {op} result after recovery"
        return res
    if not done:
        res["why"] = "no worker finished"
        return res

    def field(ln, key):
        for part in ln.split():
            if part.startswith(key + "="):
                return part.split("=", 1)[1]
        return None

    recoveries = [(float(field(ln, "recovery_count") or 0),
                   float(field(ln, "recovery_sum") or 0)) for ln in done]
    recovered = [(c, s) for c, s in recoveries if c > 0]
    if scenario == "delay":
        if recovered:
            res["why"] = "delay tripped failure detection (false positive)"
            return res
    else:
        if not recovered:
            res["why"] = "no survivor recorded a recovery"
            return res
        worst = max(s / c for c, s in recovered)
        res["worst_recovery_s"] = round(worst, 3)
        if worst > RECOVERY_BUDGET[scenario]:
            res["why"] = (f"recovery took {worst:.2f}s > "
                          f"{RECOVERY_BUDGET[scenario]}s budget")
            return res
    res["ok"] = True
    return res


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="CI smoke: one kill + one hang on the tcp ring")
    p.add_argument("--np", type=int, default=4, dest="np_")
    p.add_argument("--batches", type=int, default=8)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--scenarios", default="kill",
                   help=f"comma list of {SCENARIOS} for the matrix sweep")
    p.add_argument("--algos", default=",".join(ALGOS))
    p.add_argument("--transports", default=",".join(TRANSPORTS))
    p.add_argument("--hier", default=",".join(HIERS))
    p.add_argument("--compression", default=",".join(COMPRESSIONS))
    p.add_argument("--ops", default="allreduce",
                   help=f"comma list of {OPS}; every op but allreduce "
                        "pins algo=ring, hier=0 (single-schedule ops)")
    p.add_argument("--out", default=None, help="write results JSON here")
    p.add_argument("--verbose", "-v", action="store_true")
    args = p.parse_args(argv)

    seed = args.seed if args.seed is not None else random.randrange(1 << 30)
    rng = random.Random(seed)
    print(f"chaos harness: seed={seed}", file=sys.stderr)

    combos = []
    if args.smoke:
        combos = [("kill", "allreduce", "ring", "tcp", "0", "none"),
                  ("hang", "allreduce", "ring", "tcp", "0", "none")]
    else:
        for scenario in args.scenarios.split(","):
            for op in args.ops.split(","):
                # RS/AG/broadcast/alltoall run one fixed schedule each:
                # the algo/hier dimensions are allreduce-only, so
                # collapse them to the ring.
                algos = args.algos.split(",") if op == "allreduce" \
                    else ["ring"]
                hiers = args.hier.split(",") if op == "allreduce" else ["0"]
                for algo in algos:
                    for transport in args.transports.split(","):
                        for hier in hiers:
                            for comp in args.compression.split(","):
                                combos.append((scenario, op, algo, transport,
                                               hier, comp))

    results, failed = [], 0
    for i, (scenario, op, algo, transport, hier, comp) in enumerate(combos):
        label = (f"{scenario:6s} {op:13s} {algo:18s} {transport:3s} "
                 f"hier={hier} {comp}")
        print(f"[{i + 1}/{len(combos)}] {label} ...", file=sys.stderr,
              flush=True)
        res = run_scenario(scenario, algo, transport, hier, comp, args.np_,
                           args.batches, rng, op=op, verbose=args.verbose)
        results.append(res)
        status = "OK" if res["ok"] else f"FAIL ({res['why']})"
        rec = res.get("worst_recovery_s")
        print(f"[{i + 1}/{len(combos)}] {label} -> {status}"
              + (f" recovery={rec}s" if rec is not None else "")
              + f" wall={res['wall_s']}s",
              file=sys.stderr, flush=True)
        if not res["ok"]:
            failed += 1

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"seed": seed, "results": results}, f, indent=2)
    print(f"chaos harness: {len(combos) - failed}/{len(combos)} scenarios "
          f"passed (seed={seed})", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
