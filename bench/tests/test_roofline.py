"""The shape-only byte and operation counts of the eval roofline."""

import importlib.util
import math
import os

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_eval_bytes_on_the_sweep_cell_shapes():
    m = _reader("eval_roofline_pct.sweep")
    J, G, L, S, B, n = 2500, 25, 49, 4, 5, 12628
    rows = G * J
    want = rows * L * 13 + rows * 4 + B * S * (n + 1) * 8 + S * rows * 16
    assert m.eval_bytes(J, G, L, S, B, n) == want == 46_083_140
    assert m.eval_ops(J, G, L, S, n) == S * rows * L * (2 * 14 + 40)


def test_bytes_bound_the_least_time():
    m = _reader("eval_roofline_pct.sweep")
    shapes = {"J": 2500, "groups": 25, "L": 49, "S": 4, "bids": 5,
              "n_slots": 12628}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    t = m.least_seconds(shapes, peaks)
    assert math.isclose(t, 46_083_140 / 819e9)
