"""The trace reduction, on hand-made operations and spans."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository root on the path)
from bench import trace
from bench.metrics import _common


def summary(ops, spans=(), start=0, end=100, devices=1):
    return trace.Summary([trace.Op(*o) for o in ops],
                         [trace.Span(*s) for s in spans], start, end,
                         devices)


def test_union_of_overlapping_intervals():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40), (40, 45)]) == 35
    assert trace.union_ns([]) == 0


def test_busy_idle_and_scopes():
    ops = [(10, 30, "fusion.1", "jit(step)/lda.plan/scatter-add", 0),
           (25, 50, "lda_sample", "jit(step)/lda.sample/pallas_call", 0),
           (60, 70, "fusion.2", "jit(step)/lda.phi_delta/add", 0),
           (95, 120, "fusion.3", "jit(step)/lda.plan/top_k", 0)]
    s = summary(ops, [(0, 100, trace.WINDOW_SPAN), (50, 60, "train.iter"),
                      (70, 95, "host.pack")])
    # busy: [10, 50) + [60, 70) + [95, 100) clipped to the window
    assert s.busy_s == pytest.approx(55e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.scope_s("lda.plan") == pytest.approx(25e-9)
    assert s.scope_s("lda.sample") == pytest.approx(25e-9)
    assert s.scope_s("lda.sync") == 0
    # the scope must be a whole path component
    assert summary([(0, 10, "x", "jit/lda.planner/x", 0)]).scope_s(
        "lda.plan") == 0
    gaps = s.idle_gaps()
    assert gaps[0] == ["host.pack", 25e-9]
    assert gaps[1] == ["no host span", 10e-9]
    assert s.top_ops()[0][0] in ("lda_sample", "fusion.1")


def test_scope_time_averages_over_devices():
    ops = [(0, 40, "a", "lda.sample/k", 0), (0, 20, "a", "lda.sample/k", 1)]
    s = summary(ops, devices=2)
    assert s.scope_s("lda.sample") == pytest.approx(30e-9)
    assert s.busy_s == pytest.approx(30e-9)


def test_readers_return_nothing_when_nothing_to_read():
    s = summary([])

    class R:
        trace = s
        window = {"iterations": 2}

    assert _common.per_unit_ms(R, "lda.plan", 2) is None
    assert _common.idle_share(R) is None


DATA = Path(__file__).parent / "data"


def recorded(with_hlo: bool):
    """Three iterations of a small LDA step (V=3000, K=256, 24,895 tokens)
    on one v5e, traced by the harness with the program's named scopes, and
    the compiled program's HLO text."""
    hlo = [gzip.decompress((DATA / "tiny_train.hlo.txt.gz").read_bytes())
           .decode()] if with_hlo else []
    return trace.from_xspace((DATA / "tiny_train.xplane.pb").read_bytes(),
                             [0], hlo)


def test_recorded_tpu_trace():
    s = recorded(with_hlo=True)
    assert len(s.ops) == 315
    assert s.window_s == pytest.approx(0.065390178)
    assert s.busy_s == pytest.approx(0.06012691)
    # the scopes the per-layer metrics read
    assert s.scope_s("lda.sample") == pytest.approx(0.030788573)
    assert s.scope_s("lda.plan") == pytest.approx(0.019831317)
    assert s.scope_s("lda.phi_delta") == pytest.approx(0.009485331)
    assert s.scope_s("lda.sync") == pytest.approx(1.9074e-05)
    assert s.top_ops(1) == [["lda_sample.1", pytest.approx(0.030340248)]]
    total = sum(s.scope_s(x) for x in ("lda.plan", "lda.sample",
                                       "lda.phi_delta", "lda.sync"))
    assert total <= s.busy_s
    by_name = {o.name: o.text for o in s.ops}
    assert "/lda.sample/" in by_name["lda_sample.1"]
    # the scatter XLA expanded for the theta rebuild carries no op path of
    # its own; its consumers in the HLO put it under lda.plan
    assert "/lda.plan/" in by_name["fusion.1"]
    assert s.breakdown()["idle_gaps"][0][0] == "train.iteration"


def test_recorded_trace_without_hlo_leaves_the_scatter_unscoped():
    s = recorded(with_hlo=False)
    assert s.scope_s("lda.plan") == pytest.approx(9.0873e-05)
    assert s.scope_s("lda.sample") == pytest.approx(0.030776084)
    assert "lda." not in {o.name: o.text for o in s.ops}["fusion.1"]


def test_hlo_paths_follow_consumers_then_operands():
    text = """ENTRY %main (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  %a = s32[4]{0} add(%p, %p), metadata={op_name="jit(f)/lda.plan/add"}
  %s = (s32[4]{0}, s32[4]{0}) sort(%a, %p), dimensions={0}
  %g = s32[4]{0} get-tuple-element(%s), index=0
  %u = s32[4]{0} multiply(%g, %g), metadata={op_name="jit(f)/lda.sample/mul"}
  ROOT %t = s32[4]{0} negate(%a)
}"""
    paths = trace.hlo_paths([text])
    assert paths["s"] == paths["g"] == "jit(f)/lda.sample/mul"
    assert paths["t"] == "jit(f)/lda.plan/add"
    assert paths["p"] == "jit(f)/lda.plan/add"
