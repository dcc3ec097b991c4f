"""The harness on the CPU: discovery by name, the refusal without a TPU,
a rehearsal of a whole run at a tiny size, the faults and the control
that the comparison has to catch."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import control, harness

ROOT = harness.ROOT
TINY = {"kind": "kronecker", "scale": 7, "edgefactor": 16, "seed": 1}
TINY_MESH = {"kind": "delaunay", "k": 9, "seed": 1}
TINY_ENGINE = {"storage_format": "bucketed", "frontier_cap": 1024}


def _tiny(name="graph500.q1", generator=TINY):
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, generator=generator, engine=TINY_ENGINE)
    return cell


def _run(cell, traced=False, seconds=0.5, seed=2 ** 31 + 3):
    return harness.run_cell(cell, seed, seconds, traced,
                            jax.devices("cpu")[:1], 0.0,
                            log=open(os.devnull, "w"))


# --------------------------------------------------------------------------- #
# discovery
# --------------------------------------------------------------------------- #
def test_cells_find_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["generator"]["kind"] in ("kronecker", "delaunay")
        assert cell.traffic["pattern"] == w["traffic"]
        assert {m["name"] for m in cell.end_to_end} == {"query_s", "setup_s"}
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
    with pytest.raises(KeyError):
        harness.load_cell("no_such.cell")


def test_a_new_file_is_picked_up(tmp_path):
    """A new configuration, mix and metric are new files plus entries in
    BENCHMARK.json; the harness finds them with no edit."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(
        {"generator": TINY, "partition": {"method": "block"},
         "engine": TINY_ENGINE}))
    (tmp_path / "bench" / "traffic" / "q1b.json").write_text(json.dumps(
        {"pattern": "q1", "edges": [[0, 1], [1, 2], [0, 2]],
         "loop": "closed", "clients": 1}))
    (tmp_path / "bench" / "metrics" / "queries.py").write_text(
        "def read(run):\n    return float(len(run.queries))\n")
    bench["configs"].append({"name": "tiny", "file": "bench/configs/"
                             "tiny.json", "source": "x", "reduced": [],
                             "why": "x"})
    bench["workloads"].append({"name": "tiny.q1b", "config": "tiny",
                               "traffic": "q1b", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "queries", "unit": "queries",
                               "better": "higher", "source":
                               "program_counter", "layer": "driver",
                               "moves": "query_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny.q1b", root=str(tmp_path))
    assert cell.config["generator"] == TINY
    assert cell.traffic["edges"] == [[0, 1], [1, 2], [0, 2]]
    assert "queries" in [m["name"] for m in cell.per_layer]
    read = harness.metric_reader("queries", root=str(tmp_path))
    assert read(harness.Run(queries=[1, 2], window_s=1.0)) == 2.0


# --------------------------------------------------------------------------- #
# no TPU, no result
# --------------------------------------------------------------------------- #
def _bench_run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "graph500.q1",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _bench_run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _bench_run(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""


def test_tpu_devices_refuses_the_cpu():
    with pytest.raises(harness.NoAccelerator):
        harness.tpu_devices(1)


# --------------------------------------------------------------------------- #
# rehearsal: the whole run path at a tiny size
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("cell_name,generator", [
    ("graph500.q1", TINY), ("delaunay_n20.q2", TINY_MESH)])
def test_rehearsal_untraced(cell_name, generator):
    r = _run(_tiny(cell_name, generator))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"query_s", "setup_s"}
    assert r["metrics"]["query_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert r["checks"]["count_gap"] == {"value": 0, "limit": 0}


def test_rehearsal_traced():
    r = _run(_tiny(), traced=True)
    assert r["correct"]
    # the CPU trace has no device plane: the device readers find nothing
    assert r["metrics"]["waves_per_query"]["value"] >= 1
    assert 0 <= r["metrics"]["split_share"]["value"] < 1
    assert 0 < r["metrics"]["group_form_frac"]["value"] < 1
    assert "device_idle_frac" not in r["metrics"]
    assert "membership_frac" not in r["metrics"]


# --------------------------------------------------------------------------- #
# faults under the timed path, and the control
# --------------------------------------------------------------------------- #
def test_fault_answer_altered_where_produced(monkeypatch):
    """Every wave's count off by one where the finalize stage makes it."""
    from repro.core import scheduler

    real = scheduler.finalize_wave

    def altered(state, exec_hits=0.0):
        rows, alive, counts, complete, st = real(state, exec_hits)
        return rows, alive, counts.at[0].add(1), complete, st

    monkeypatch.setattr(scheduler, "finalize_wave", altered)
    r = _run(_tiny())
    assert not r["correct"] and r["failed"] == r["attempted"]
    assert r["checks"]["count_gap"]["value"] > 0


def test_fault_half_the_batch_left_out(monkeypatch):
    """Half of every wave's seeds masked out when the wave is admitted."""
    from repro.core import scheduler

    real = scheduler._pad_seeds

    def half(seeds_per_dev, ndev, scap, sentinel):
        out, mask = real(seeds_per_dev, ndev, scap, sentinel)
        for t, s in enumerate(seeds_per_dev):
            mask[t, len(s) // 2:] = False
        return out, mask

    monkeypatch.setattr(scheduler, "_pad_seeds", half)
    r = _run(_tiny())
    assert not r["correct"]
    assert r["checks"]["count_gap"]["value"] > 0


@pytest.mark.parametrize("cell_name,generator", [
    ("graph500.q1", TINY), ("delaunay_n20.q2", TINY_MESH)])
def test_control_is_not_correct(cell_name, generator):
    cell = _tiny(cell_name, generator)
    for seed in (1, 2, 2 ** 31 + 9):
        ref, ctl, checks = control.readings(cell, seed)
        assert ctl < ref
        assert checks["count_gap"]["value"] > checks["count_gap"]["limit"]
