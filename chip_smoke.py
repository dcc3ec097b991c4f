"""Run RADS end to end on TPU: triangle enumeration (q1) over ``dblp_synth``
at DBLP's published vertex count, through the normal entry points
(``load_dataset`` -> ``partition`` -> ``make_engine_mesh`` ->
``rads_enumerate`` in ``mode="spmd"``, bucketed storage).

    python chip_smoke.py             # one chip: SM-E on, then every seed
                                     # through fetch/expand/verify and the
                                     # one-device all-to-all; cold + warm
    python chip_smoke.py --chips 4   # four chips, one process: a 4-way bfs
                                     # partition, raw and varint wire

Every count must equal a host count made without the engine (sparse
algebra on the original graph, sum(A^2 * A) / 6).  The last line of
standard output is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, printing no result, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DATASET = "dblp_synth"
# frontier_cap 2^14: the SM-E waves of 4096 seeds then split least often
# per unit of frontier work at this graph's hub degrees
CFG_KW = dict(storage_format="bucketed", frontier_cap=1 << 14)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def tpu_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        fail(f"needs {chips} chips; JAX found {len(devs)}")
    return devs


def host_triangles(g) -> int:
    """Triangles of the host CSR graph by sparse algebra (engine-free)."""
    import numpy as np
    import scipy.sparse as sp

    a = sp.csr_matrix((np.ones(g.indices.shape[0], np.int64), g.indices,
                       g.indptr), shape=(g.n, g.n))
    return int((a @ a).multiply(a).sum()) // 6


def peak_bytes(devs) -> int:
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devs)


def kernel_stages(runner) -> list[str]:
    """Stage keys whose compiled program holds a Pallas TPU kernel."""
    return sorted({k if isinstance(k, str) else k[0]
                   for (k, _, _), fn in runner.executables().items()
                   if "tpu_custom_call" in fn.as_text()})


def enumerate_q1(pg, cfg, mesh, cache: dict):
    from repro.configs.rads import QUERIES
    from repro.core import Pattern, rads_enumerate

    t0 = time.perf_counter()
    res = rads_enumerate(pg, Pattern.from_edges(QUERIES["q1"]), cfg,
                         mode="spmd", mesh=mesh, return_embeddings=False,
                         runner_cache=cache)
    return res, time.perf_counter() - t0


def one_chip(g, want: int, devs) -> None:
    from repro.configs.rads import EngineConfig
    from repro.graph import partition
    from repro.launch.mesh import make_engine_mesh

    t0 = time.perf_counter()
    pg = partition(g, 1, method="bfs")
    print(f"partition ndev=1: {time.perf_counter() - t0} s", flush=True)
    mesh = make_engine_mesh(1)
    base = EngineConfig(**CFG_KW)
    n_seeds = int((g.degrees >= 2).sum())
    for label, cfg in (("sme", base),
                       ("no-sme", dataclasses.replace(base,
                                                      enable_sme=False))):
        cache: dict = {}
        cold, t_cold = enumerate_q1(pg, cfg, mesh, cache)
        warm, t_warm = enumerate_q1(pg, cfg, mesh, cache)
        st = cold.stats
        (runner,) = [v[-1] for v in cache.values()]
        kernels = kernel_stages(runner)
        print(f"q1 {label}: count cold {cold.count} warm {warm.count} "
              f"host {want} | seeds sme {st['n_sme_seeds']} dist "
              f"{st['n_dist_seeds']} | waves {st['n_waves']} retries "
              f"{st['overflow_retries']} escalations "
              f"{st['cap_escalations']} | cold wall {t_cold} s, compile "
              f"{st['compile_s']} s over {st['compiles']} stages | warm "
              f"wall {t_warm} s, compiles {warm.stats['compiles']} | "
              f"bytes fetch {st['bytes_fetch']} verify {st['bytes_verify']}"
              f" | peak_bytes_in_use {peak_bytes(devs[:1])} | "
              f"tpu_custom_call in {kernels}", flush=True)
        if cold.count != want or warm.count != want:
            fail(f"q1 {label}: counts {cold.count}/{warm.count} != host "
                 f"{want}")
        if warm.stats["compiles"] != 0:
            fail(f"q1 {label}: warm call compiled "
                 f"{warm.stats['compiles']} stages")
        if "expand" not in kernels:
            fail(f"q1 {label}: no compiled Pallas kernel in the expand "
                 f"stage")
        sme, dist = st["n_sme_seeds"], st["n_dist_seeds"]
        if (label == "sme" and (sme, dist) != (n_seeds, 0)) or \
                (label == "no-sme" and (sme, dist) != (0, n_seeds)):
            fail(f"q1 {label}: seeds sme {sme} dist {dist} of {n_seeds}")
        if label == "no-sme" and "verify" not in kernels:
            fail("q1 no-sme: no compiled Pallas kernel in the verify stage")


def four_chips(g, want: int, devs) -> None:
    import jax

    from repro.configs.rads import EngineConfig
    from repro.graph import partition
    from repro.launch.mesh import make_engine_mesh

    t0 = time.perf_counter()
    pg = partition(g, 4, method="bfs")
    print(f"partition ndev=4 (bfs): {time.perf_counter() - t0} s, stride "
          f"{pg.stride}", flush=True)
    mesh = make_engine_mesh(4)
    for wire in ("raw", "varint"):
        cfg = EngineConfig(**CFG_KW, wire_format=wire)
        cache: dict = {}
        res, t = enumerate_q1(pg, cfg, mesh, cache)
        st = res.stats
        (runner,) = [v[-1] for v in cache.values()]
        kernels = kernel_stages(runner)
        placed = {frozenset(s.device.id for s in leaf.addressable_shards
                            if s.data.shape[0] == 1)
                  for leaf in jax.tree.leaves(runner.g)}
        print(f"q1 4 chips wire={wire}: count {res.count} host {want} | "
              f"seeds sme {st['n_sme_seeds']} dist {st['n_dist_seeds']} | "
              f"waves {st['n_waves']} | wall {t} s, compile "
              f"{st['compile_s']} s over {st['compiles']} stages | wire "
              f"bytes fetch {st['bytes_wire_fetch']} verify "
              f"{st['bytes_wire_verify']} | cache hit rate "
              f"{st['cache_hit_rate']} | graph shards on devices "
              f"{sorted(set().union(*placed))} | peak_bytes_in_use "
              f"{peak_bytes(devs[:4])} | tpu_custom_call in {kernels}",
              flush=True)
        if res.count != want:
            fail(f"q1 4 chips wire={wire}: count {res.count} != host "
                 f"{want}")
        if placed != {frozenset(d.id for d in mesh.devices.flat)} or \
                len(mesh.devices.flat) != 4:
            fail(f"graph shards not one per device on 4 devices: {placed}")
        need = {"expand", "verify"} | ({"fetch"} if wire == "varint"
                                       else set())
        if not need <= set(kernels):
            fail(f"q1 4 chips wire={wire}: compiled kernels only in "
                 f"{kernels}, expected {sorted(need)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devs = tpu_devices(args.chips)
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)
    from repro.graph import load_dataset
    from repro.runtime.compile_cache import configure_jax_cache

    print(f"compile cache: {configure_jax_cache()}", flush=True)
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)
    t0 = time.perf_counter()
    g = load_dataset(DATASET)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = host_triangles(g)
    print(f"graph {DATASET}: n={g.n} m={g.n_edges} max_degree="
          f"{g.max_degree} (generated in {t_gen} s) | host triangles "
          f"{want} ({time.perf_counter() - t0} s)", flush=True)
    if args.chips == 1:
        one_chip(g, want, devs)
    else:
        four_chips(g, want, devs)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
