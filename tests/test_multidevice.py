"""Multi-device correctness via subprocess (8 forced host devices):
* SPMD engine (real all_to_all under shard_map) == sim engine == oracle,
  across both DeviceGraph storage formats (dense / bucketed) incl. a
  skewed power-law graph, with foreign-adjacency-cache on/off parity
  (identical counts, sim==spmd hit accounting, byte conservation)
* sharded train step == single-device train step
* compressed_psum == plain psum within quantization error
Each test spawns one python subprocess so the main pytest process keeps the
single real device (see conftest note).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_spmd_engine_matches_oracle():
    res = run_sub(textwrap.dedent("""
        import json, jax, numpy as np
        from repro.graph import erdos_graph, partition
        from repro.core import Pattern, rads_enumerate, enumerate_oracle, canonicalize
        from repro.configs.rads import QUERIES, EngineConfig
        from repro.launch.mesh import make_engine_mesh
        mesh = make_engine_mesh(8)
        cfg = EngineConfig(frontier_cap=1<<12, fetch_cap=256, verify_cap=1024,
                           region_group_budget=1<<11)
        g = erdos_graph(120, 5.0, seed=5)
        pg = partition(g, 8, method='bfs')
        ok = True
        for q in ['q1', 'q2', 'q6']:
            pat = Pattern.from_edges(QUERIES[q])
            oracle = canonicalize(enumerate_oracle(g, pat), pat)
            spmd = rads_enumerate(pg, pat, cfg, mode='spmd', mesh=mesh)
            sim = rads_enumerate(pg, pat, cfg, mode='sim')
            ok &= canonicalize(spmd.embeddings, pat) == oracle
            ok &= canonicalize(sim.embeddings, pat) == oracle
            ok &= spmd.stats['bytes_fetch'] == sim.stats['bytes_fetch']
            ok &= spmd.stats['bytes_verify'] == sim.stats['bytes_verify']
        # storage-format parity on a skewed graph: the bucketed DeviceGraph
        # must be byte-identical to dense through the real all_to_all path
        # (sim/gather x format parity is covered by the fast suite)
        import dataclasses
        from repro.graph import powerlaw_graph
        gp = powerlaw_graph(160, 6, seed=5)
        pgp = partition(gp, 8, method='bfs')
        pat = Pattern.from_edges(QUERIES['q1'])
        oracle = canonicalize(enumerate_oracle(gp, pat), pat)
        ref_bytes = None
        for fmt in ['dense', 'bucketed']:
            cf = dataclasses.replace(cfg, storage_format=fmt)
            spmd = rads_enumerate(pgp, pat, cf, mode='spmd', mesh=mesh)
            ok &= canonicalize(spmd.embeddings, pat) == oracle
            b = (spmd.stats['bytes_fetch'], spmd.stats['bytes_verify'])
            ref_bytes = ref_bytes or b
            ok &= b == ref_bytes
        # multi-group workload: the async staged scheduler must pipeline
        # >= 2 waves through the real all_to_all spmd backend
        import dataclasses
        many = dataclasses.replace(cfg, region_group_budget=64,
                                   enable_sme=False)
        pat = Pattern.from_edges(QUERIES['q1'])
        oracle = canonicalize(enumerate_oracle(g, pat), pat)
        spmd = rads_enumerate(pg, pat, many, mode='spmd', mesh=mesh)
        ok &= canonicalize(spmd.embeddings, pat) == oracle
        inflight = spmd.stats['max_inflight_waves']
        # adjacency-cache parity through the sharded (shard_map) path:
        # cache-on == cache-off == oracle, the sim/spmd hit accounting is
        # identical (same host wave schedule), and the conservation law
        # bytes_fetch(on) + bytes_saved_cache == bytes_fetch(off) holds
        pat = Pattern.from_edges(QUERIES['q3'])
        oracle = canonicalize(enumerate_oracle(gp, pat), pat)
        ccfg = dataclasses.replace(cfg, enable_sme=False,
                                   region_group_budget=256,
                                   storage_format='bucketed')
        c_on = rads_enumerate(pgp, pat, ccfg, mode='spmd', mesh=mesh)
        c_off = rads_enumerate(
            pgp, pat, dataclasses.replace(ccfg, enable_cache=False),
            mode='spmd', mesh=mesh)
        c_sim = rads_enumerate(pgp, pat, ccfg, mode='sim')
        ok &= canonicalize(c_on.embeddings, pat) == oracle
        ok &= canonicalize(c_off.embeddings, pat) == oracle
        ok &= c_on.count == c_off.count == c_sim.count
        ok &= (c_on.stats['bytes_fetch'] + c_on.stats['bytes_saved_cache']
               == c_off.stats['bytes_fetch'])
        ok &= c_on.stats['cache_hits'] == c_sim.stats['cache_hits']
        ok &= c_on.stats['bytes_fetch'] == c_sim.stats['bytes_fetch']
        cache_hits = c_on.stats['cache_hits']
        print(json.dumps(dict(ok=bool(ok), inflight=int(inflight),
                              cache_hits=float(cache_hits))))
    """))
    assert res["ok"]
    assert res["inflight"] >= 2
    assert res["cache_hits"] > 0


@pytest.mark.slow
def test_spmd_wire_varint_matches_sim():
    """wire='varint' through the real all_to_all shard_map path: coded u8
    streams cross the collective, results match sim/oracle, and the actual
    stream-byte accounting is identical to the sim backend (deterministic
    codecs + identical wave schedule)."""
    res = run_sub(textwrap.dedent("""
        import dataclasses, json
        from repro.graph import partition, powerlaw_graph
        from repro.core import (Pattern, rads_enumerate, enumerate_oracle,
                                canonicalize)
        from repro.configs.rads import QUERIES, EngineConfig
        from repro.launch.mesh import make_engine_mesh
        mesh = make_engine_mesh(8)
        g = powerlaw_graph(160, 6, seed=5)
        pg = partition(g, 8, method='hash')
        cfg = EngineConfig(frontier_cap=1<<12, fetch_cap=256,
                           verify_cap=1024, region_group_budget=256,
                           enable_sme=False, wire_format='varint')
        ok = True
        for q in ['q1', 'q3']:
            pat = Pattern.from_edges(QUERIES[q])
            oracle = canonicalize(enumerate_oracle(g, pat), pat)
            spmd = rads_enumerate(pg, pat, cfg, mode='spmd', mesh=mesh)
            sim = rads_enumerate(pg, pat, cfg, mode='sim')
            raw = rads_enumerate(
                pg, pat, dataclasses.replace(cfg, wire_format='raw'),
                mode='spmd', mesh=mesh)
            ok &= canonicalize(spmd.embeddings, pat) == oracle
            ok &= canonicalize(sim.embeddings, pat) == oracle
            ok &= spmd.count == sim.count == raw.count
            ok &= (spmd.stats['bytes_wire_fetch']
                   == sim.stats['bytes_wire_fetch'])
            ok &= (spmd.stats['bytes_wire_verify']
                   == sim.stats['bytes_wire_verify'])
            ok &= (spmd.stats['bytes_wire_fetch']
                   <= spmd.stats['bytes_fetch'])
            ok &= (spmd.stats['bytes_wire_verify']
                   < raw.stats['bytes_wire_verify'])
        print(json.dumps(dict(ok=bool(ok))))
    """))
    assert res["ok"]


def test_launch_local_refuses_accelerator_backend(monkeypatch):
    """``launch_local`` is the CPU gloo harness: where JAX's backend is an
    accelerator it raises before starting any worker, instead of measuring
    CPU processes in place of the chips."""
    import jax

    from repro.launch.dist_worker import launch_local

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU gloo harness"):
        launch_local(2, ["--dataset", "dblp_bench"])


@pytest.mark.slow
@pytest.mark.parametrize("wire,cache", [("raw", False), ("raw", True),
                                        ("varint", False),
                                        ("varint", True)])
def test_dist_two_process_matches_sim(wire, cache):
    """The ``dist`` backend across two real OS processes (jax.distributed +
    gloo CPU collectives) is byte-identical to the in-process ``sim``
    backend on the same partitioned graph: counts, ``bytes_wire_*`` scalar
    totals, per-device attribution sums, and cache hit accounting.  Skips
    cleanly when the jaxlib build cannot bootstrap multi-process CPU."""
    import dataclasses

    from repro.configs.rads import QUERIES
    from repro.core import Pattern, rads_enumerate
    from repro.graph import load_dataset, partition
    from repro.launch.dist_worker import (build_argparser, dist_available,
                                          launch_local, worker_config)

    if not dist_available():
        pytest.skip("jaxlib lacks gloo CPU collectives")
    wargs = ["--dataset", "dblp_bench", "--query", "q1",
             "--partition", "hash", "--wire", wire,
             "--frontier-cap", str(1 << 12), "--fetch-cap", str(1 << 9),
             "--verify-cap", str(1 << 11), "--region-budget", str(1 << 11)]
    if not cache:
        wargs.append("--no-cache")
    workers = launch_local(2, wargs, timeout_s=900.0)
    if workers is None:
        pytest.skip("multi-process bootstrap unavailable at runtime")
    assert len(workers) == 2

    cfg = worker_config(build_argparser().parse_args(wargs))
    if cfg.pipeline_depth == "auto":
        cfg = dataclasses.replace(cfg, pipeline_depth=2)
    pg = partition(load_dataset("dblp_bench"), 2, method="hash")
    sim = rads_enumerate(pg, Pattern.from_edges(QUERIES["q1"]), cfg,
                         mode="sim", return_embeddings=False)
    assert sim.count > 0
    for w in workers:
        st = w["stats"]
        assert int(w["count"]) == sim.count
        for phase in ("fetch", "verify"):
            assert (float(st[f"bytes_wire_{phase}"])
                    == float(sim.stats[f"bytes_wire_{phase}"]))
            # per-device attribution is complete: rows sum to the total
            assert (float(sum(st[f"bytes_wire_{phase}_dev"]))
                    == float(sim.stats[f"bytes_wire_{phase}"]))
        assert float(st["bytes_fetch"]) == float(sim.stats["bytes_fetch"])
        assert float(st["cache_hits"]) == float(sim.stats["cache_hits"])
        if cache:
            assert (float(st["bytes_fetch"])
                    + float(st["bytes_saved_cache"])
                    == float(sim.stats["bytes_fetch"])
                    + float(sim.stats["bytes_saved_cache"]))


@pytest.mark.slow
def test_sharded_train_matches_single_device():
    res = run_sub(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.models.transformer import init_lm_params, lm_loss
        from repro.distributed.sharding import param_shardings
        from repro.launch.mesh import make_mesh
        cfg = get_reduced('qwen3-4b')
        params = init_lm_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
        lbls = jnp.roll(toks, -1, axis=1)
        loss_plain = float(lm_loss(params, cfg, toks, lbls))
        mesh = make_mesh((4, 2), ('data', 'model'))
        with mesh:
            psh = param_shardings(params, 'lm', mesh)
            pp = jax.tree.map(jax.device_put, params, psh)
            tsh = NamedSharding(mesh, P('data', None))
            lg = NamedSharding(mesh, P('data', None, 'model'))
            hd = NamedSharding(mesh, P('data', None, None))
            loss_sh = float(jax.jit(
                lambda p, t, l: lm_loss(p, cfg, t, l, logits_sharding=lg,
                                        hidden_sharding=hd))(
                pp, jax.device_put(toks, tsh), jax.device_put(lbls, tsh)))
        rel = abs(loss_plain - loss_sh) / max(abs(loss_plain), 1e-9)
        print(json.dumps(dict(rel=rel)))
    """))
    assert res["rel"] < 2e-2   # bf16 reduction-order tolerance


@pytest.mark.slow
def test_compressed_psum_close_to_exact():
    res = run_sub(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.distributed.compression import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('pod',))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        from jax.sharding import NamedSharding, PartitionSpec as P
        xs = jax.device_put(x, NamedSharding(mesh, P('pod', None)))
        # exact: every row becomes the column-sum
        want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), x.shape)
        got = np.asarray(compressed_psum(xs, 'pod', mesh))
        err = np.abs(got - want).max() / np.abs(want).max()
        print(json.dumps(dict(err=float(err))))
    """))
    assert res["err"] < 0.05


@pytest.mark.slow
def test_dryrun_entrypoint_smallest_cell():
    """The actual dryrun module runs end to end (512 devices) for one cell."""
    env = dict(os.environ, PYTHONPATH=SRC,
               DRYRUN_ARTIFACTS="/tmp/dryrun_test_artifacts")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gat-cora",
         "--shape", "molecule", "--mesh", "single"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "all dry-runs passed" in out.stdout
