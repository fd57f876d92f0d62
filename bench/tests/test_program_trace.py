"""The reduction of the program's own names: interval arithmetic by hand,
and a constructed trace with nested program spans and ``op_name`` paths
whose answers are worked out by hand (``data/program_spans.xplane.pb``,
written by ``data/make_program_spans.py``)."""

import os

import numpy as np
import pytest

import program_trace as pt
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = os.path.join(DATA, "program_spans.xplane.pb")
NS = 1e-9


def test_span_names_drop_metadata():
    assert pt.span_name("service.pull#flush=3#") == "service.pull"
    assert pt.span_name("fl.dispatch") == "fl.dispatch"


@pytest.mark.parametrize("path,scope", [
    ("jit(run)/while/body/fl.update/dot_general", "fl.update"),
    ("jit(run)/fl.update/fl.aggregate/add", "fl.aggregate"),
    ("jit(run)/fl.update/transpose(jvp(fl.sgd))/mul", "fl.sgd"),
    ("jit(run)/while/body/mul", "other"),
    ("", "other"),
])
def test_innermost_scope_wins(path, scope):
    assert pt.scope_of(path) == scope


def test_idle_before_counts_gap_length_up_to_x():
    f = pt.idle_before(np.array([1.0, 5.0]), np.array([3.0, 6.0]))
    xs = [0.0, 1.0, 2.0, 3.0, 4.0, 5.5, 6.0, 10.0]
    assert list(f(np.array(xs))) == [0.0, 0.0, 1.0, 2.0, 2.0, 2.5, 3.0, 3.0]
    assert list(pt.idle_before(np.array([]), np.array([]))(
        np.array([1.0, 2.0]))) == [0.0, 0.0]


def test_self_intervals_leave_out_direct_children():
    spans = [("a", 0.0, 10.0), ("b", 1.0, 3.0), ("c", 2.0, 2.5),
             ("d", 4.0, 6.0), ("e", 12.0, 13.0)]
    got = {name: [(float(lo), float(hi)) for lo, hi in pieces]
           for name, pieces in pt.self_intervals(spans)}
    assert got == {"a": [(0.0, 1.0), (3.0, 4.0), (6.0, 10.0)],
                   "b": [(1.0, 2.0), (2.5, 3.0)], "c": [(2.0, 2.5)],
                   "d": [(4.0, 6.0)], "e": [(12.0, 13.0)]}


def test_constructed_trace_scopes():
    """Device ops in window [0, 2000), module ``jit_run`` running over
    [90, 1700): a ``while`` [100, 420) holding fusion.1 [100, 300) under
    ``fl.decision`` and fusion.2 [250, 400) under
    ``fl.update/fl.aggregate``; the Mosaic call decision_fused.3
    [600, 800) under ``fl.decision``; fusion.4 [1500, 1600) under
    ``fl.eval``. Module ``jit_other`` over [1850, 2100): copy.5
    [1900, 1950) under no scope, and its own fusion.1 [1950, 2100) under
    ``fl.eval``, clipped by the window."""
    red = pt.reduce_file(SPANS)
    assert red.devices == 1
    assert red.scope_s == pytest.approx({"fl.decision": 400 * NS,
                                         "fl.aggregate": 150 * NS,
                                         "fl.eval": 150 * NS,
                                         "other": 50 * NS})


def test_op_names_come_from_the_hlo_in_the_trace():
    assert pt.module_op_names(SPANS) == {
        "jit_run": {"p.0": "", "while.1": "jit(run)/while",
                    "fusion.4": "jit(run)/fl.eval/reduce_sum",
                    "fusion.1": "jit(run)/while/body/fl.decision/mul",
                    "fusion.2":
                        "jit(run)/while/body/fl.update/fl.aggregate/add",
                    "decision_fused.3":
                        "jit(run)/while/body/fl.decision/pallas_call"},
        "jit_other": {"copy.5": "jit(other)/copy",
                      "fusion.1": "jit(other)/fl.eval/mul"}}


def test_op_names_of_a_captured_trace(tmp_path):
    """The HLO that JAX's profiler writes into a real trace (CPU here):
    a jitted function's named scope reaches its instructions' op_name."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("fl.update"):
            return jnp.sin(x) * 2.0

    x = jnp.ones((8,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = pt.module_op_names(pt.newest_trace(str(tmp_path)))
    assert any(pt.scope_of(path) == "fl.update"
               for path in names.get("jit_f", {}).values())


def test_constructed_trace_program_spans():
    """Device idle in the window: [0, 100), [420, 600), [800, 1500),
    [1600, 1900). Host thread ``python``: service.submit [10, 40);
    service.flush [60, 1900) holding stage [70, 90), dispatch [90, 120),
    pulls [450, 550) and [550, 700) and unpack [700, 900); a pull of the
    next flush [1950, 2100) clipped by the window. Thread ``worker``:
    fl.dispatch [1000, 1400), and one that starts before the window and
    is left out."""
    red = pt.reduce_file(SPANS)
    want = {
        "service.submit": (1, 30, 30),
        # self [60, 70) + [120, 450) + [900, 1900); idle 10 + 30 + 900
        "service.flush": (1, 1340, 940),
        "service.stage": (1, 20, 20),
        "service.dispatch": (1, 30, 10),
        # idle 100 + 50 + 0
        "service.pull": (3, 300, 150),
        "service.unpack": (1, 200, 100),
        "fl.dispatch": (1, 400, 400),
    }
    assert set(red.program_spans) == set(want)
    for name, (count, self_ns, idle_ns) in want.items():
        got = red.program_spans[name]
        assert got["count"] == count, name
        assert got["self_s"] == pytest.approx(self_ns * NS), name
        assert got["idle_s"] == pytest.approx(idle_ns * NS), name


def test_program_spans_leave_the_harness_reduction_as_it_was():
    """``trace_reduce`` labels idle gaps by the harness's spans alone: the
    program's spans nested in them change no label."""
    red = tr.reduce_file(SPANS)
    assert red.window_s == pytest.approx(2000 * NS)
    assert red.busy_s == pytest.approx(720 * NS)
    assert red.op_s == pytest.approx({"fusion.1": 250 * NS,
                                      "fusion.2": 150 * NS,
                                      "decision_fused.3": 200 * NS,
                                      "fusion.4": 100 * NS,
                                      "copy.5": 50 * NS})
    assert red.idle_by_span == pytest.approx({"submit": 100 * NS,
                                              "flush": 1180 * NS})
    assert [k for k, _ in red.breakdown()["idle_gaps"]] == ["flush",
                                                            "submit"]


def test_trace_without_program_names():
    """The harness-only constructed trace (no HLO, no program span):
    every op under ``other``."""
    red = pt.reduce_file(os.path.join(DATA, "constructed.xplane.pb"))
    assert red.scope_s == pytest.approx({"other": 650 * NS})
    assert red.program_spans == {}
