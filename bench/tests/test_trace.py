"""The trace reduction, on a hand-made trace with known answers and on a
small trace recorded on a TPU v5e (``data/``)."""
import gzip
import json
import os

import pytest

from bench import trace
from bench.harness import Run
from bench.metrics import membership_frac
from bench.metrics.membership_frac import is_membership

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# window [0.5, 10.5] ms on the host; device 0 runs ops in [1, 3] ms,
# [2.5, 4] ms (overlapping, the kernel) and [6, 7] ms; the last op starts
# inside the window and ends after it; one op lies wholly outside
MS = 1_000_000_000  # picoseconds
SYNTHETIC = f'''
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {2 * MS} }}
    events {{ metadata_id: 2 offset_ps: {int(1.5 * MS)}
              duration_ps: {int(1.5 * MS)} }}
    events {{ metadata_id: 1 offset_ps: {5 * MS} duration_ps: {1 * MS} }}
    events {{ metadata_id: 3 offset_ps: {9 * MS} duration_ps: {5 * MS} }}
    events {{ metadata_id: 3 offset_ps: {20 * MS} duration_ps: {1 * MS} }}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events {{ metadata_id: 4 offset_ps: 0 duration_ps: {9 * MS} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = s32[8]{{0}} fusion(s32[8]{{0}} %p), kind=kLoop" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%jit_membership.2 = s32[8,128]{{1,0}} custom-call(s32[8,128]{{1,0}} %a), custom_call_target=\\"tpu_custom_call\\"" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "copy.3" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit__lambda" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 500000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {10 * MS} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench_window" }} }} }}
'''


def _synthetic():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(SYNTHETIC)


def test_union_clips_and_merges():
    assert trace.union([(5, 8), (1, 3), (2, 4), (9, 20)], 0, 10) == \
        [(1, 4), (5, 8), (9, 10)]
    assert trace.union([(0, 1)], 2, 3) == []


def test_reduce_synthetic():
    ms = 1e6
    spans = [("retire", 0.4 * ms, 0.9 * ms), ("wave", 0, 11 * ms),
             ("group_form", 4.1 * ms, 5.9 * ms),
             ("expand:u1", 7 * ms, 7.6 * ms)]
    red = trace.reduce(_synthetic(), "bench_window", spans)
    # busy = [1, 4] + [6, 7] + [10, 10.5] ms = 4.5 ms
    assert red.window_s == pytest.approx(10e-3)
    assert red.busy_s == pytest.approx(4.5e-3)
    assert red.idle_frac == pytest.approx(0.55)
    assert red.kernel_seconds(is_membership) == pytest.approx(1.5e-3)
    assert red.kernel_seconds(lambda t: "no_such_kernel" in t) == 0.0
    ops = {trace.short_name(k): v for k, v in red.op_seconds.items()}
    assert set(ops) == {"%fusion.1 fusion s32[8]",
                        "%jit_membership.2 custom-call s32[8,128]", "copy.3"}
    assert ops["copy.3"] == pytest.approx(0.5e-3)   # clipped to the window
    assert ops["%fusion.1 fusion s32[8]"] == pytest.approx(3e-3)
    gaps = red.gap_seconds()
    # gaps [0.5, 1] retire, [4, 6] group_form, [7, 10]: expand:u1 covers
    # 0.6 ms of it; the umbrella "wave" wins only where it is alone
    assert gaps == pytest.approx({"retire": 0.5e-3, "group_form": 2e-3,
                                  "expand:u1": 3e-3})
    assert sum(gaps.values()) + red.busy_s == pytest.approx(red.window_s)
    bd = red.breakdown()
    assert [k for k, _ in bd["device_ops"]] == [
        "%fusion.1 fusion s32[8]", "%jit_membership.2 custom-call s32[8,128]",
        "copy.3"]
    assert bd["idle_gaps"][0][0] == "expand:u1"


def test_membership_frac_reads_the_kernel_or_nothing():
    red = trace.reduce(_synthetic(), "bench_window")
    run = Run(queries=[], window_s=red.window_s, trace=red)
    assert membership_frac.read(run) == pytest.approx(0.15)
    red.op_seconds = {k: v for k, v in red.op_seconds.items()
                      if not is_membership(k)}
    assert membership_frac.read(run) is None     # left out of the line
    assert membership_frac.read(Run(queries=[], window_s=1.0)) is None


def test_attribution_falls_back():
    cause = trace.attributor([("wave", 0, 50000), ("query", 0, 90000),
                              ("retire", 60000, 65000)])
    assert cause(40000, 60000) == "query"    # query covers more than wave
    assert cause(55000, 70000) == "retire"   # narrow beats umbrella
    assert cause(70000, 75000) == "query"
    assert cause(200000, 300000) == trace.NO_SPAN
    assert cause(100, 600) == trace.SHORT_GAP


def test_short_name():
    hlo = ("%fusion.31 = s32[10649600]{0:T(1024)S(1)} fusion(s32[1,39635]"
           "{1,0:T(1,128)S(1)} %copy-done), kind=kCustom")
    assert trace.short_name(hlo) == "%fusion.31 fusion s32[10649600]"
    hlo = ("%while.22 = (s32[]{:T(128)}, s32[1,16384]{1,0:T(1,128)S(1)}) "
           "while((s32[]{:T(128)}) %tuple.111), condition=%c")
    assert trace.short_name(hlo) == "%while.22 while tuple"


def test_reduce_refuses_a_trace_without_the_window():
    with pytest.raises(ValueError):
        trace.reduce(_synthetic(), "no_such_window")


def test_recorded_tpu_trace():
    """A traced 1 s window of triangle queries on a 3,000-vertex preferential-attachment
    graph on one TPU v5e (``small.xplane.pb.gz``, with the recorder's
    spans and its clock read inside the window in ``small_spans.json``):
    every number the reduction gives is consistent, the membership kernel
    is found, and the recorder's spans land inside the window."""
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(DATA, "small.xplane.pb.gz")) as f:
        prof = ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "small_spans.json")) as f:
        rec = json.load(f)
    lo, hi = trace.host_annotation(prof, "bench_window")
    off = lo - rec["a0"] * 1e3
    spans = [(n, t * 1e3 + off, (t + d) * 1e3 + off)
             for n, _, t, d in rec["spans"]]
    assert all(lo - 1e6 <= a and b <= hi + 1e6 for _, a, b in spans)
    red = trace.reduce(prof, "bench_window", spans)
    assert red.n_devices == 1
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.kernel_seconds(is_membership) < red.busy_s
    assert sum(red.gap_seconds().values()) + red.busy_s == \
        pytest.approx(red.window_s, rel=1e-9)
    assert sum(red.op_seconds.values()) >= red.busy_s * (1 - 1e-9)
    bd = red.breakdown()
    assert bd["device_ops"][0][0].startswith("%fusion.")
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
