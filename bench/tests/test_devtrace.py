"""Trace reduction: busy union, idle share, device time per program and the
breakdown, on hand-made events and on a short trace recorded on one v5e
chip (``data/sweep_small.xplane.pb``: ``bench/run.py --workload
exp1-type4.market-sweep --seconds 3 --trace 1``)."""

import os

import pytest

import devtrace

DATA = os.path.join(os.path.dirname(__file__), "data", "sweep_small.xplane.pb")


def test_union_merges_overlaps_and_keeps_gaps():
    assert devtrace.union([]) == 0.0
    assert devtrace.union([(0, 1), (0.5, 2), (3, 4), (3.5, 3.6)]) == 3.0
    assert devtrace.union([(5, 6), (0, 1)]) == 2.0


def _planes():
    host = [(devtrace.WINDOW, 10.0, 20.0), (devtrace.UNIT, 10.0, 15.0),
            (devtrace.UNIT, 15.0, 20.0)]
    modules = [("jit_policy_cost_chain(123)", 9.0, 14.0),
               ("jit_views(9)", 14.5, 15.0), ("jit_gen(7)", 16.0, 16.5),
               ("jit_policy_cost_chain(123)", 17.0, 19.0)]
    ops = [("%policy_cost_chain.1 = f32[..] custom-call(..)", 9.0, 14.0),
           ("%fusion.2 = f32[..] fusion(..)", 14.5, 15.0),
           ("%fusion.2 = f32[..] fusion(..)", 16.0, 16.5),
           ("%policy_cost_chain.1 = f32[..] custom-call(..)", 17.0, 19.0)]
    return {"host": host, "devices": [{"modules": modules, "ops": ops}]}


def test_events_are_clipped_to_the_window():
    tr = devtrace.Trace(_planes())
    assert tr.window_s == 10.0
    assert tr.busy_s() == 4.0 + 0.5 + 0.5 + 2.0
    assert tr.program_s(["policy_cost_chain"]) == 6.0
    assert tr.program_s(["gen", "views"]) == 1.0
    assert tr.top_ops(1) == [["policy_cost_chain.1", 6.0]]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = devtrace.Trace(_planes())
    spans = [("evaluate_grid", 10.0, 20.0), ("plan", 14.0, 14.6),
             ("eval", 15.2, 16.2)]
    gaps = dict((k, v) for k, v in tr.idle_gaps(spans))
    # gaps: [14, 14.5] in plan, [15, 16] in eval, [16.5, 17] and
    # [19, 20] only in evaluate_grid
    assert gaps == pytest.approx({"plan": 0.5, "eval": 1.0,
                                  "evaluate_grid": 1.5})


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_chip_trace():
    tr = devtrace.Trace(devtrace.read_planes(DATA))
    assert 0.0 < tr.busy_s() <= tr.window_s
    kernel = tr.program_s(["policy_cost_chain"])
    assert 0.0 < kernel <= tr.window_s
    assert tr.program_s(["gen", "views"]) > 0.0
    assert len(tr.units) >= 1
    assert tr.top_ops(1)[0][0].startswith("policy_cost_chain")
