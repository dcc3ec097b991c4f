"""Async wave scheduler: double-buffered pipelining, the overflow
split / capacity-escalation robustness loop, steal-from-longest queue
rebalancing, and the Pallas membership gate — all against the oracle."""
import dataclasses

import numpy as np
import pytest

from repro import compat
from repro.configs.rads import QUERIES, EngineConfig
from repro.core import (Pattern, PipelineScheduler, StageRunner, best_plan,
                        canonicalize, enumerate_oracle, rads_enumerate)
from repro.core.engine import build_plan_data
from repro.core.exchange import Exchange
from repro.graph import device_graph, erdos_graph, partition

# region_group_budget=64 => many small region groups per device — the
# multi-group workload the pipeline needs to show overlap.
CFG = EngineConfig(frontier_cap=1 << 13, fetch_cap=512, verify_cap=2048,
                   region_group_budget=64, enable_sme=False)


@pytest.fixture(scope="module")
def erdos():
    g = erdos_graph(150, 5.0, seed=3)
    return g, partition(g, 4, method="bfs")


def test_async_pipeline_two_inflight_matches_oracle(erdos):
    """The tentpole invariant: with pipeline_depth=2 and a multi-group
    workload, >= 2 waves are in flight and results stay oracle-exact."""
    g, pg = erdos
    pat = Pattern.from_edges(QUERIES["q1"])
    oracle = canonicalize(enumerate_oracle(g, pat), pat)
    res = rads_enumerate(pg, pat, CFG, mode="sim")
    assert res.count == len(oracle)
    assert canonicalize(res.embeddings, pat) == oracle
    assert res.stats["n_waves"] >= 4
    assert res.stats["max_inflight_waves"] >= 2
    assert res.stats["wave_s_total"] > 0.0
    assert res.stats["dist_pipeline_s"] > 0.0


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_sync_equals_async(erdos, qname):
    """pipeline_depth=1 (the old synchronous loop) and depth=2 must be
    byte-identical: counts, embeddings, and logical traffic accounting.

    The adjacency cache is disabled here: cache state is sequenced through
    fetches in *dispatch* order, so the wire traffic (never the results —
    see test_cache.py::test_sync_equals_async_with_cache) legitimately
    depends on the pipeline depth when the cache is on."""
    g, pg = erdos
    pat = Pattern.from_edges(QUERIES[qname])
    nocache = dataclasses.replace(CFG, enable_cache=False)
    sync = rads_enumerate(pg, pat,
                          dataclasses.replace(nocache, pipeline_depth=1),
                          mode="sim")
    anc = rads_enumerate(pg, pat, nocache, mode="sim")
    assert sync.count == anc.count
    assert canonicalize(sync.embeddings, pat) == canonicalize(
        anc.embeddings, pat)
    assert sync.stats["bytes_fetch"] == anc.stats["bytes_fetch"]
    assert sync.stats["bytes_verify"] == anc.stats["bytes_verify"]
    assert sync.stats["max_inflight_waves"] == 1


def test_gather_async_matches_oracle(erdos):
    g, pg = erdos
    pat = Pattern.from_edges(QUERIES["q2"])
    oracle = canonicalize(enumerate_oracle(g, pat), pat)
    res = rads_enumerate(pg, pat, CFG, mode="gather")
    assert canonicalize(res.embeddings, pat) == oracle


def test_robustness_split_and_escalation(erdos):
    """Deliberately tiny capacities must force >= 1 region-group split AND
    >= 1 capacity escalation — and the final result stays oracle-exact
    (§6: memory control is a robustness mechanism, not an error path)."""
    g, pg = erdos
    pat = Pattern.from_edges(QUERIES["q1"])
    tiny = EngineConfig(frontier_cap=8, fetch_cap=16, verify_cap=16,
                        region_group_budget=64)
    oracle = canonicalize(enumerate_oracle(g, pat), pat)
    res = rads_enumerate(pg, pat, tiny, mode="sim")
    assert canonicalize(res.embeddings, pat) == oracle
    assert res.stats["overflow_retries"] >= 1
    assert res.stats["cap_escalations"] >= 1
    assert res.stats["final_caps"]["frontier"] > 8


def test_steal_from_longest_queue():
    """Drive the scheduler directly with deliberately imbalanced per-device
    group queues: the drained devices must steal from the longest queue
    (checkR/shareR) and the union of wave counts must equal the oracle."""
    g = erdos_graph(120, 5.0, seed=9)
    pg = partition(g, 4, method="bfs")
    pat = Pattern.from_edges(QUERIES["q1"])
    oracle = canonicalize(enumerate_oracle(g, pat), pat)
    plan = best_plan(pat)
    pd = build_plan_data(plan)
    cfg = EngineConfig(frontier_cap=1 << 13, fetch_cap=512, verify_cap=2048)
    runner = StageRunner(device_graph(pg, "dense"), pd, cfg, Exchange("sim"))

    # every candidate seed exactly once, packed into groups of 8 that all
    # start on device 0 — devices 1..3 drain immediately and must steal
    seeds = np.flatnonzero(pg.deg.reshape(-1) >= pd.start_deg)
    groups = [seeds[i:i + 8].astype(np.int64)
              for i in range(0, len(seeds), 8)]
    queues = [list(groups), [], [], []]

    total = 0
    stats = dict(overflow_retries=0, cap_escalations=0, n_waves=0,
                 max_inflight_waves=0, steal_events=0, wave_s_total=0.0)

    def consume(rows, alive, counts, st, phase):
        nonlocal total
        total += int(np.asarray(counts).sum())

    sched = PipelineScheduler(runner, stats, consume)
    sched.run(queues, scap=16, local_only=False, phase="dist")
    assert total == len(oracle)
    assert stats["steal_events"] >= 1
    assert stats["max_inflight_waves"] >= 2


def test_steal_disabled_same_results(erdos):
    g, pg = erdos
    pat = Pattern.from_edges(QUERIES["q1"])
    a = rads_enumerate(pg, pat, CFG, mode="sim")
    b = rads_enumerate(pg, pat,
                       dataclasses.replace(CFG, steal_from_longest=False),
                       mode="sim")
    assert canonicalize(a.embeddings, pat) == canonicalize(b.embeddings, pat)


@pytest.mark.skipif(not compat.HAS_EXECUTABLE_SERIALIZATION,
                    reason="jax build cannot serialize executables")
def test_warm_run_zero_compiles(erdos, tmp_path):
    """With a populated persistent executable store, a brand-new
    StageRunner performs ZERO stage traces/compiles — the whole warm run
    is executable dispatch (the PR-7 latency-floor invariant), and the
    results stay byte-identical with the traced cold run."""
    from repro.runtime.compile_cache import StageExecCache

    g, pg = erdos
    pat = Pattern.from_edges(QUERIES["q1"])
    cfg = dataclasses.replace(CFG, compile_cache_dir=str(tmp_path / "ex"))
    cold = rads_enumerate(pg, pat, cfg, mode="sim")
    assert cold.stats["exec_cache_enabled"]
    assert cold.stats["compiles"] > 0
    assert cold.stats["exec_cache"]["stores"] == cold.stats["compiles"]
    StageExecCache.clear_memory_memo()       # force on-disk deserialization
    warm = rads_enumerate(pg, pat, cfg, mode="sim")
    assert warm.count == cold.count
    assert canonicalize(warm.embeddings, pat) == canonicalize(
        cold.embeddings, pat)
    assert warm.stats["compiles"] == 0
    assert warm.stats["compile_s"] == 0.0
    assert warm.stats["compile_cache_hits"] > 0
    assert warm.stats["exec_cache"]["misses"] == 0


def test_prewarm_escalation_rung_precompiles_next_caps():
    """``prewarm(escalation_rungs=1)`` resolves the stage ladder one
    capacity rung *above* the live caps; a subsequent ``escalate()`` then
    finds every stage already in the slot table (zero new compiles on the
    escalation path), and the old rung's slots survive for in-flight
    waves."""
    g = erdos_graph(80, 4.0, seed=2)
    pg = partition(g, 2, method="bfs")
    pat = Pattern.from_edges(QUERIES["q1"])
    pd = build_plan_data(best_plan(pat))
    cfg = EngineConfig(frontier_cap=1 << 8, fetch_cap=64, verify_cap=128,
                       region_group_budget=256)
    runner = StageRunner(device_graph(pg, "dense"), pd, cfg, Exchange("sim"))

    n0 = runner.prewarm(scap=16, local_only=False)
    assert n0 > 0
    base_key = (cfg.frontier_cap, cfg.fetch_cap, cfg.verify_cap)
    assert base_key in {k[1] for k in runner._slots if k[1]}

    n1 = runner.prewarm(scap=16, local_only=False, escalation_rungs=1)
    assert n1 > n0                  # base rung re-walked + one rung above
    esc = runner._escalated(cfg)
    esc_key = (esc.frontier_cap, esc.fetch_cap, esc.verify_cap)
    assert esc_key in {k[1] for k in runner._slots if k[1]}

    compiles_before = runner.compiles
    assert runner.escalate()
    # the slot table survives escalation: both rungs still resolvable
    keys = {k[1] for k in runner._slots if k[1]}
    assert base_key in keys and esc_key in keys
    # re-warming the escalated rung is pure slot hits — no new compiles
    assert runner.prewarm(scap=16, local_only=False) > 0
    assert runner.compiles == compiles_before
    """use_pallas_kernels routes the back-edge / verifyE membership tests
    through the Pallas kernel (interpret mode on CPU) — results must not
    change."""
    g = erdos_graph(60, 4.0, seed=7)
    pg = partition(g, 3, method="bfs")
    pat = Pattern.from_edges(QUERIES["q3"])
    oracle = canonicalize(enumerate_oracle(g, pat), pat)
    cfg = EngineConfig(frontier_cap=1 << 11, fetch_cap=256, verify_cap=512,
                       region_group_budget=1 << 10, use_pallas_kernels=True)
    res = rads_enumerate(pg, pat, cfg, mode="sim")
    assert res.count == len(oracle)
    assert canonicalize(res.embeddings, pat) == oracle


def test_join_prewarm_reraises_prewarm_failure(monkeypatch):
    """A stage the compiler refuses during background pre-warm fails the
    run at its cause: ``join_prewarm`` re-raises the first failure the
    pre-warm thread recorded instead of turning it into a warning."""
    g = erdos_graph(40, 3.0, seed=1)
    pg = partition(g, 2, method="bfs")
    pd = build_plan_data(best_plan(Pattern.from_edges(QUERIES["q1"])))
    runner = StageRunner(device_graph(pg, "dense"), pd, CFG,
                         Exchange("sim"))

    def refused(*a, **k):
        raise NotImplementedError("kernel refused by the compiler")

    monkeypatch.setattr(runner, "prewarm", refused)
    runner.prewarm_async(scap=16, local_only=False)
    with pytest.raises(NotImplementedError, match="refused"):
        runner.join_prewarm()
    runner.join_prewarm()           # reported once; nothing left to raise
