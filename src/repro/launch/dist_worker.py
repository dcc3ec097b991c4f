"""Per-process worker + local launcher for the ``dist`` exchange backend.

One OS process per graph partition, joined into a single JAX computation
by ``jax.distributed``:

``python -m repro.launch.dist_worker --coordinator HOST:PORT \
    --num-processes N --process-id I --dataset dblp_bench --query q1``

The bootstrap order is load-bearing and lives in :mod:`repro.compat`
(:func:`~repro.compat.enable_cpu_collectives` MUST run before the CPU
backend client exists, :func:`~repro.compat.distributed_initialize`
before any device use) — this module only sequences the calls before the
heavy imports.  Exit code ``3`` means "multi-process bootstrap
unavailable on this build": callers (tests, the scalability harness)
treat it as a clean skip, never a failure.

Every process loads the same deterministic dataset, computes the same
partition, and runs :func:`repro.core.driver.rads_enumerate` with
``mode="dist"`` over a mesh spanning all processes — per-process results
are byte-identical by construction (the replicated finalize), so each
worker writes its full stats JSON to ``--out`` and the launcher merges
them with :func:`repro.core.driver.merge_process_stats`, which *asserts*
that identity.

:func:`launch_local` spawns N single-device worker subprocesses against a
coordinator on a free localhost port — the CPU gloo harness of the ``dist``
backend, a stand-in for real multi-host launches (same flags, one host).
It forces ``JAX_PLATFORMS=cpu`` on its workers, so it refuses to run on a
host whose JAX backend is an accelerator (:func:`require_cpu_backend`):
there one process drives every chip through ``mode="spmd"``.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

# exit code contract with launch_local / tests: clean "cannot run here"
EXIT_BOOTSTRAP_UNAVAILABLE = 3


def dist_available() -> bool:
    """Cheap probe: does this jaxlib ship gloo CPU collectives at all?"""
    from repro import compat

    return compat.HAS_MULTIPROCESS_CPU


def build_argparser():
    import argparse

    ap = argparse.ArgumentParser(
        description="one process of a multi-process dist enumeration run")
    ap.add_argument("--coordinator", default="127.0.0.1:0",
                    help="jax.distributed coordinator HOST:PORT (process 0 "
                         "binds it; all processes dial it)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--dataset", default="dblp_bench")
    ap.add_argument("--query", default="q1")
    ap.add_argument("--partition", default="bfs",
                    choices=["bfs", "block", "hash"])
    ap.add_argument("--wire", default="raw",
                    choices=["raw", "varint", "auto"])
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the foreign-adjacency cache")
    ap.add_argument("--comm-pipeline", action="store_true",
                    help="chunked back-to-back sub-exchanges per a2a")
    ap.add_argument("--comm-chunks", type=int, default=4)
    # engine capacities (power-of-two ladder; defaults = EngineConfig's) —
    # the scalability harness passes these so its in-process sim parity
    # runs share the exact configuration, making stats byte-comparable
    ap.add_argument("--frontier-cap", type=int, default=0,
                    help="0 = EngineConfig default")
    ap.add_argument("--fetch-cap", type=int, default=0)
    ap.add_argument("--verify-cap", type=int, default=0)
    ap.add_argument("--region-budget", type=int, default=0)
    ap.add_argument("--out", default="",
                    help="write {count, wall_s, stats} JSON here")
    ap.add_argument("--trace", default="",
                    help="write this process's Chrome trace-event JSON "
                         "(with >1 process the process id is inserted "
                         "before the extension: out.json -> out.p0.json; "
                         "merge lanes with `python -m tools.merge_traces`)")
    ap.add_argument("--metrics-out", default="",
                    help="export this process's metrics registry (*.prom = "
                         "Prometheus textfile, else JSON; per-process path "
                         "derivation as for --trace)")
    return ap


def _per_process_path(path: str, process_id: int, nproc: int) -> str:
    """launch_local hands every worker identical args, so per-process
    artifact paths derive from the shared one: ``t.json -> t.p2.json``."""
    if nproc <= 1:
        return path
    root, ext = os.path.splitext(path)
    return f"{root}.p{process_id}{ext or '.json'}"


def worker_config(args):
    """The EngineConfig a worker invocation resolves to — shared with the
    scalability harness's in-process ``sim`` parity runs so both sides
    compare byte-for-byte."""
    import dataclasses

    from repro.configs.rads import DEFAULT_ENGINE

    cfg = dataclasses.replace(DEFAULT_ENGINE,
                              wire_format=args.wire,
                              enable_cache=not args.no_cache,
                              comm_pipeline=args.comm_pipeline,
                              comm_chunks=args.comm_chunks)
    caps = dict(frontier_cap=args.frontier_cap, fetch_cap=args.fetch_cap,
                verify_cap=args.verify_cap,
                region_group_budget=args.region_budget)
    return dataclasses.replace(
        cfg, **{k: v for k, v in caps.items() if v})


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)

    # ---- bootstrap (before any jax device use — see module docstring) ----- #
    from repro import compat

    if args.num_processes > 1:
        if not compat.enable_cpu_collectives():
            return EXIT_BOOTSTRAP_UNAVAILABLE
        if not compat.distributed_initialize(args.coordinator,
                                             args.num_processes,
                                             args.process_id):
            return EXIT_BOOTSTRAP_UNAVAILABLE

    import jax

    if jax.device_count() != args.num_processes:
        # one device per process is the launch contract (the engine mesh
        # axis is the process axis); a mismatched topology would silently
        # change the partition count, so refuse as "unavailable"
        print(f"[dist] device/process topology mismatch: "
              f"{jax.device_count()} global devices for "
              f"{args.num_processes} processes", file=sys.stderr)
        return EXIT_BOOTSTRAP_UNAVAILABLE

    from repro.configs.rads import CLIQUE_QUERIES, QUERIES
    from repro.core import Pattern, rads_enumerate
    from repro.graph import load_dataset, partition
    from repro.launch.mesh import make_engine_mesh

    pattern = Pattern.from_edges({**QUERIES, **CLIQUE_QUERIES}[args.query])
    g = load_dataset(args.dataset)          # deterministic: identical on
    pg = partition(g, args.num_processes,   # every process by construction
                   method=args.partition)
    cfg = worker_config(args)
    mesh = make_engine_mesh(args.num_processes)
    tracer = None
    if args.trace:
        from repro.obs import TraceRecorder

        # the Chrome pid lane IS the process index — merged traces keep
        # one lane group per process (see repro.obs dist merge contract)
        tracer = TraceRecorder(pid=args.process_id)
    t0 = time.perf_counter()
    res = rads_enumerate(pg, pattern, cfg, mode="dist", mesh=mesh,
                         return_embeddings=False, tracer=tracer)
    wall_s = time.perf_counter() - t0
    payload = dict(count=int(res.count), wall_s=wall_s,
                   process_id=args.process_id,
                   num_processes=args.num_processes,
                   dataset=args.dataset, query=args.query,
                   stats=res.stats)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, default=float)
    if tracer is not None:
        tracer.save(_per_process_path(args.trace, args.process_id,
                                      args.num_processes))
    if args.metrics_out:
        mpath = _per_process_path(args.metrics_out, args.process_id,
                                  args.num_processes)
        if mpath.endswith(".prom"):
            res.registry.export_prometheus(mpath)
        else:
            res.registry.export_json(mpath)
    print(f"[dist] p{args.process_id}/{args.num_processes} "
          f"{args.dataset}/{args.query}: count={res.count} "
          f"wall={wall_s:.2f}s wire="
          f"{res.stats['bytes_wire_fetch'] + res.stats['bytes_wire_verify']:.0f}B | "
          + res.registry.summary(("wall_us", "compiles", "comm_pipeline")))
    return 0


# --------------------------------------------------------------------------- #
# Local multi-process launcher (container stand-in for multi-host)
# --------------------------------------------------------------------------- #
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _src_dir() -> str:
    import repro

    # repro is a namespace package (no __init__.py): __file__ is None, so
    # resolve the source root from __path__ instead
    return os.path.dirname(os.path.abspath(next(iter(repro.__path__))))


def require_cpu_backend() -> None:
    """Raise unless this process's JAX backend is the CPU: the local
    multi-process harness measures CPU worker processes, which on an
    accelerator host would stand in silently for the chips."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"launch_local is the CPU gloo harness of the dist backend, but "
            f"this host's JAX backend is {backend!r}; on an accelerator host "
            f"one process drives every chip: run mode='spmd' on "
            f"make_engine_mesh(n)")


def launch_local(nproc: int, worker_args: list[str],
                 timeout_s: float = 1200.0) -> list[dict] | None:
    """Run one ``dist`` enumeration across ``nproc`` local CPU subprocesses
    (the gloo harness; raises on an accelerator host, see
    :func:`require_cpu_backend`).

    Each worker gets exactly one CPU device
    (``--xla_force_host_platform_device_count=1``) so the process axis IS
    the device axis — the same flags drive a real multi-host launch with
    one command per host.  Returns the per-process result payloads
    ordered by process id, or ``None`` when the bootstrap is unavailable
    (any worker exited ``EXIT_BOOTSTRAP_UNAVAILABLE``); any other failure
    raises with the worker's output attached."""
    require_cpu_backend()
    port = _free_port()
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _src_dir() + os.pathsep + env.get("PYTHONPATH", "")
    outs = [tempfile.NamedTemporaryFile(suffix=f".dist{i}.json",
                                        delete=False).name
            for i in range(nproc)]
    procs = []
    try:
        for i in range(nproc):
            cmd = [sys.executable, "-m", "repro.launch.dist_worker",
                   "--coordinator", f"127.0.0.1:{port}",
                   "--num-processes", str(nproc), "--process-id", str(i),
                   *worker_args, "--out", outs[i]]
            procs.append(subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        deadline = time.monotonic() + timeout_s
        logs = []
        for p in procs:
            remaining = max(1.0, deadline - time.monotonic())
            try:
                out, _ = p.communicate(timeout=remaining)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise RuntimeError(
                    f"dist worker timed out after {timeout_s:.0f}s")
            logs.append(out or "")
        codes = [p.returncode for p in procs]
        if any(c == EXIT_BOOTSTRAP_UNAVAILABLE for c in codes):
            return None
        if any(c != 0 for c in codes):
            detail = "\n".join(
                f"--- worker {i} (exit {codes[i]}) ---\n{logs[i][-2000:]}"
                for i in range(nproc) if codes[i] != 0)
            raise RuntimeError(f"dist workers failed:\n{detail}")
        results = []
        for i, path in enumerate(outs):
            with open(path) as f:
                results.append(json.load(f))
        return results
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for path in outs:
            try:
                os.remove(path)
            except OSError:
                pass


if __name__ == "__main__":
    sys.exit(main())
