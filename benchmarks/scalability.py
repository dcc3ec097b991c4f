"""Figure 12: cross-process scalability — launch the ``dist`` backend at
1..N OS processes and report wall / wire-byte / skew curves.

This is the CPU gloo harness: each cell spawns ``nd`` single-device CPU
worker subprocesses through :func:`repro.launch.dist_worker.launch_local`
(a stand-in for one-command-per-host launches), and the run refuses to
start on a host whose JAX backend is an accelerator, where one process
drives all chips through ``mode="spmd"``.  It replays the *same* configuration
in-process with ``mode="sim"`` as the parity reference.  Three gates run
after the artifact is written:

* every process's ``bytes_wire_*_dev`` entries sum exactly to the sim
  run's scalar wire totals (the per-device attribution is complete);
* the dist embedding count equals the sim count at every N;
* max-per-process communication bytes strictly decrease as N grows for
  N >= 2 on the bfs-partitioned powerlaw cell (the paper's scalability
  claim: more machines, less traffic per machine).

Wall-clock on this container is oversubscribed-CPU simulation, so the
wall curve is descriptive; the byte curves are the scalable quantities.
The ``wall_skew`` column (max over mean of the per-process engine-span
``wall_us``, from :func:`repro.core.driver.merge_process_stats`) factors
subprocess startup out of that noise: it is the honest straggler signal
per N even when absolute wall is not comparable across N.
When the jaxlib build lacks gloo CPU collectives the dist columns degrade
to ``null`` and the gates are skipped — the artifact still records the
sim-side curves so downstream tooling always has the file.
"""
from __future__ import annotations

import dataclasses
import json
import time

from benchmarks.common import emit
from repro.core import Pattern, rads_enumerate
from repro.core.driver import merge_process_stats
from repro.graph import load_dataset, partition
from repro.launch.dist_worker import (build_argparser, dist_available,
                                      launch_local, require_cpu_backend,
                                      worker_config)
from repro.configs.rads import QUERIES

JSON_PATH = "BENCH_scalability.json"
# the paper's locality-aware partitioner: on the powerlaw cells it is the
# method whose per-process traffic actually shrinks with N (hash/block
# spread the hubs so one process's request traffic grows with peer count)
PARTITION = "bfs"
NDEVS = (1, 2, 4)

# small power-of-two caps: the n=700 powerlaw cell fits with headroom and
# every subprocess compiles in seconds instead of minutes
CAPS = dict(frontier_cap=1 << 13, fetch_cap=1 << 10, verify_cap=1 << 12,
            region_budget=1 << 12)


def _worker_args(dataset: str, query: str, wire: str) -> list[str]:
    return ["--dataset", dataset, "--query", query,
            "--partition", PARTITION, "--wire", wire, "--no-cache",
            "--frontier-cap", str(CAPS["frontier_cap"]),
            "--fetch-cap", str(CAPS["fetch_cap"]),
            "--verify-cap", str(CAPS["verify_cap"]),
            "--region-budget", str(CAPS["region_budget"])]


def _sim_reference(g, pat, nd: int, wargs: list[str]):
    """In-process ``sim`` run of the exact worker configuration."""
    cfg = worker_config(build_argparser().parse_args(wargs))
    if cfg.pipeline_depth == "auto":
        # the dist driver pins auto -> 2 for cross-process determinism;
        # mirror it so wave scheduling is identical on both sides
        cfg = dataclasses.replace(cfg, pipeline_depth=2)
    pg = partition(g, nd, method=PARTITION)
    t0 = time.perf_counter()
    r = rads_enumerate(pg, pat, cfg, mode="sim", return_embeddings=False)
    return r, time.perf_counter() - t0


def _gate_cell(workers: list[dict], sim_res) -> list[str]:
    """Per-cell parity checks; returns human-readable failure strings."""
    fails = []
    merged = merge_process_stats([w["stats"] for w in workers])
    counts = sorted({int(w["count"]) for w in workers})
    if len(counts) != 1:
        fails.append(f"per-process counts diverged: {counts}")
    elif counts[0] != sim_res.count:
        fails.append(f"dist count {counts[0]} != sim count {sim_res.count}")
    for phase in ("fetch", "verify"):
        sim_total = float(sim_res.stats[f"bytes_wire_{phase}"])
        for w in workers:
            dev_sum = float(sum(w["stats"][f"bytes_wire_{phase}_dev"]))
            if dev_sum != sim_total:
                fails.append(
                    f"proc {w['process_id']} bytes_wire_{phase}_dev sums to "
                    f"{dev_sum} != sim total {sim_total}")
        if float(merged[f"bytes_wire_{phase}"]) != sim_total:
            fails.append(
                f"dist bytes_wire_{phase} {merged[f'bytes_wire_{phase}']} "
                f"!= sim {sim_total}")
    return fails


def run(dataset="dblp_bench", queries=("q1", "q2"), ndevs=NDEVS,
        wire="raw", smoke=False, json_path=JSON_PATH):
    require_cpu_backend()
    if smoke:
        queries = queries[:1]
    g = load_dataset(dataset)
    have_dist = dist_available()
    doc = dict(dataset=dataset, partition=PARTITION, wire=wire, cache=False,
               ndevs=list(ndevs), dist_available=have_dist,
               queries={}, gate_failures=[])

    for q in queries:
        pat = Pattern.from_edges(QUERIES[q])
        wargs = _worker_args(dataset, q, wire)
        curve = dict(count=None, wall_s=[], wall_s_mean=[], sim_wall_s=[],
                     bytes_wire_total=[], bytes_wire_max_dev=[],
                     comm_skew=[], wall_skew=[], parity=[])
        for nd in ndevs:
            sim_res, sim_wall = _sim_reference(g, pat, nd, wargs)
            curve["count"] = int(sim_res.count)
            curve["sim_wall_s"].append(round(sim_wall, 4))
            workers = launch_local(nd, wargs) if have_dist or nd == 1 \
                else None
            if workers is None:
                have_dist = False
                doc["dist_available"] = False
                for k in ("wall_s", "wall_s_mean", "bytes_wire_total",
                          "bytes_wire_max_dev", "comm_skew", "wall_skew",
                          "parity"):
                    curve[k].append(None)
                emit(f"scale/{dataset}/{q}/ndev{nd}", sim_wall * 1e6,
                     f"count={sim_res.count};dist=unavailable")
                continue
            try:
                fails = _gate_cell(workers, sim_res)
                merged = merge_process_stats([w["stats"] for w in workers])
            except ValueError as e:   # cross-process logical divergence
                fails, merged = [str(e)], None
            doc["gate_failures"].extend(f"{q}/ndev{nd}: {f}" for f in fails)
            if merged is None:
                for k in ("wall_s", "wall_s_mean", "bytes_wire_total",
                          "bytes_wire_max_dev", "comm_skew", "wall_skew"):
                    curve[k].append(None)
                curve["parity"].append(False)
                continue
            walls = [float(w["wall_s"]) for w in workers]
            total = (float(merged["bytes_wire_fetch"])
                     + float(merged["bytes_wire_verify"]))
            curve["wall_s"].append(round(max(walls), 4))
            curve["wall_s_mean"].append(round(sum(walls) / len(walls), 4))
            curve["bytes_wire_total"].append(total)
            curve["bytes_wire_max_dev"].append(
                float(merged["bytes_wire_max_dev"]))
            curve["comm_skew"].append(float(merged["comm_skew"]))
            # engine-clock honesty columns: per-process wall from the span
            # clock inside rads_enumerate (subprocess startup excluded),
            # max-merged + skew by merge_process_stats — the straggler
            # signal the wall_s subprocess timing can't separate out
            curve["wall_skew"].append(round(float(merged["wall_skew"]), 4))
            curve["parity"].append(not fails)
            emit(f"scale/{dataset}/{q}/ndev{nd}", max(walls) * 1e6,
                 f"count={workers[0]['count']};wire_bytes={total:.0f};"
                 f"max_dev={merged['bytes_wire_max_dev']:.0f};"
                 f"skew={merged['comm_skew']:.3f};"
                 f"wall_skew={merged['wall_skew']:.3f};"
                 f"engine_wall_us={merged['wall_us']:.0f};"
                 f"parity={'ok' if not fails else 'FAIL'}")
        # the scalability claim: per-process traffic shrinks as N grows
        maxdev = [m for nd, m in zip(ndevs, curve["bytes_wire_max_dev"])
                  if m is not None and nd >= 2]
        if len(maxdev) >= 2 and any(b >= a for a, b in zip(maxdev,
                                                           maxdev[1:])):
            doc["gate_failures"].append(
                f"{q}: max-per-process wire bytes not strictly "
                f"decreasing over ndevs>=2: {maxdev}")
        doc["queries"][q] = curve

    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1, default=float)
    emit("scale_json", 0.0, f"path={json_path}")
    # gates run AFTER the artifact lands so a red run still leaves evidence
    if doc["gate_failures"]:
        raise AssertionError("scalability gates failed: "
                             + "; ".join(doc["gate_failures"]))
