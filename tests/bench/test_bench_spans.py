"""The reduction of the program's spans and scopes (``bench/spans.py``): on
known events, and on traces of the ``mc_study`` driver recorded here on
the CPU at a tiny size."""
import shutil
import tempfile

import jax
import jax.numpy as jnp
import pytest

from bench import harness, spans, study_split, xplane

STUDY_SPANS = ("sweep.stage", "sweep.dispatch", "sweep.fetch",
               "sweep.summarize")


@pytest.mark.parametrize("op_name,want", [
    ("jit(_renewal_mc_core)/vmap(vmap(renewal_fold))/mul", "renewal_fold"),
    ("jit(_renewal_mc_core)/renewal_sample/jit(_uniform)/while/body/add",
     "renewal_sample"),
    ("jit(f)/vmap(vmap(renewal_scan))/while/body/closed_call/sub",
     "renewal_scan"),
    ("transpose(jvp(renewal_scan))/mul", "renewal_scan"),
    ("jit(_renewal_mc_core)/vmap(vmap())/convert_element_type", None),
    ("jit(_renewal_scan)/mul", None),
    ("renewal_scans/mul", None),
])
def test_scope_of_takes_transform_wrappers_off(op_name, want):
    assert spans.scope_of(op_name) == want


def test_instruction_and_metadata_names():
    assert spans.instruction("%fusion.1 = pred[786432]{0} fusion(x)") == \
        "fusion.1"
    assert spans.instruction("while.6") == "while.6"
    text = ('%fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(f)/vmap(renewal_fold)/mul" '
            'source_file="a/b.py"}')
    assert spans.hlo_scopes(text) == {"fusion.3": "renewal_fold"}


def test_hlo_scopes_of_a_compiled_program():
    def f(x):
        with jax.named_scope("renewal_sample"):
            y = jnp.sin(x)
        with jax.named_scope("renewal_fold"):
            return jax.vmap(lambda r: r @ r)(y)
    text = jax.jit(f).lower(jnp.ones((4, 8))).compile().as_text()
    got = set(spans.hlo_scopes(text).values())
    assert {"renewal_sample", "renewal_fold"} <= got


def test_self_time_goes_to_the_innermost_operation():
    # a while loop around two body ops, one of which holds a third
    ops = [("while", 0, 100), ("a", 10, 20), ("b", 30, 60), ("c", 40, 50),
           ("d", 150, 170)]
    scope = {"while": "renewal_scan", "a": "renewal_scan",
             "b": "renewal_fold"}.get
    busy = xplane.merge((s, e) for _, s, e in ops)
    got = spans.self_times(busy, ops, 0, 160, scope)
    assert got == {"renewal_scan": 70.0, "renewal_fold": 20.0, None: 20.0}
    assert sum(got.values()) == xplane.coverage(busy)(0, 160)


def test_busy_time_no_operation_names_is_unscoped():
    # the CPU client's wait holds the busy time; the worker's ops name it
    busy = [(0, 100)]
    ops = [("add.1", 20, 50)]
    got = spans.self_times(busy, ops, 0, 100, {"add.1": "renewal_fold"}.get)
    assert got == {None: 70.0, "renewal_fold": 30.0}


HOST = [("bench.window", 0, 200), ("bench.study", 10, 190),
        ("sweep.study", 20, 180), ("sweep.stage", 20, 40),
        ("DevicePut", 25, 30), ("sweep.dispatch", 40, 50),
        ("sweep.fetch", 50, 120), ("sweep.summarize", 120, 170)]


def test_idle_time_in_a_study_split_by_the_span_that_held_the_host():
    got = spans.idle_split([(45, 110)], HOST, "bench.study")
    assert got["program"] == {"sweep.stage": 15.0, "sweep.dispatch": 5.0,
                              "sweep.fetch": 10.0, "sweep.summarize": 50.0,
                              "sweep.study": 10.0}
    assert got["jax"] == {"sweep.stage": 5.0}
    assert got["driver"] == 20.0           # [10, 20] and [180, 190]


def test_summarize_on_known_events():
    device = [[("fusion.1", 45, 80), ("fusion.2", 80, 110)]]
    scope = {"fusion.1": "renewal_scan"}.get
    got = spans.summarize(device, device, [HOST], scope)
    assert got["studies"] == 1
    assert got["busy_s"] == pytest.approx(65e-9)
    assert got["scopes_s"] == {"renewal_sample": 0.0,
                               "renewal_scan": pytest.approx(35e-9),
                               "renewal_fold": 0.0}
    assert got["unscoped_s"] == pytest.approx(30e-9)
    assert sum(got["program_idle_s"].values()) == pytest.approx(90e-9)
    assert got["driver_idle_s"] == pytest.approx(20e-9)


TINY = dict(n_runs=64, max_failures=8)


@pytest.fixture(scope="module", params=["mc.table4.exp", "mc.table4.rack"])
def traced(request):
    """One traced window of a cell's driver at a tiny size: the raw
    trace's reductions by ``xplane`` and ``spans``, and the host line."""
    driver = harness.make_driver(harness.load_cell(request.param),
                                 2 ** 31 + 29, **TINY)
    driver.warmup()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        harness.window(driver, 0.3, trace_dir)
        pd = xplane.load(trace_dir)
        line = next(ln for ln in xplane.host_lines(pd)
                    if any(ev[0] == xplane.WINDOW for ev in ln))
        yield dict(xplane=xplane.reduce(trace_dir),
                   spans=spans.reduce(trace_dir,
                                      study_split.study_hlo(driver)),
                   line=line)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def test_the_program_spans_nest_in_order_under_each_study(traced):
    line = traced["line"]
    studies = [ev for ev in line if ev[0] == "bench.study"]
    assert len(studies) >= 2
    for _, lo, hi in studies:
        inside = sorted((s, e, n) for n, s, e in line
                        if n.startswith(spans.PROGRAM_PREFIX) and lo <= s
                        and e <= hi)
        assert [n for *_, n in inside] == ["sweep.study", *STUDY_SPANS]
        (s0, e0, _), *steps = inside
        assert all(s0 <= s and e <= e0 for s, e, _ in steps)
        assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:]))


def test_every_scope_reads_and_the_scopes_add_up_to_the_busy_time(traced):
    got, busy_s = traced["spans"], traced["xplane"]["busy_s"]
    assert all(v > 0 for v in got["scopes_s"].values()), got["scopes_s"]
    total = sum(got["scopes_s"].values()) + got["unscoped_s"]
    assert total == pytest.approx(busy_s, rel=1e-2)


def test_the_idle_time_in_the_studies_splits_three_ways(traced):
    got, calls = traced["spans"], traced["xplane"]["calls"]
    assert got["studies"] == len(calls)
    idle = sum(span - busy for span, busy in calls)
    parts = (sum(got["program_idle_s"].values())
             + sum(got["jax_idle_s"].values()) + got["driver_idle_s"])
    assert parts == pytest.approx(idle, rel=1e-2)
    assert set(got["program_idle_s"]) <= {"sweep.study", *STUDY_SPANS}
    assert got["program_idle_s"]["sweep.summarize"] > 0


def test_the_device_reduction_keeps_its_keys_and_names_program_spans(traced):
    got = traced["xplane"]
    assert set(got) == {"window_s", "busy_s", "device_ops", "idle_gaps",
                        "calls"}
    labels = [label for label, _ in got["idle_gaps"]]
    assert any(label.startswith("study: sweep.") for label in labels)


def test_a_split_of_a_window_per_study():
    driver = harness.make_driver(harness.load_cell("mc.table4.exp"),
                                 2 ** 31 + 31, **TINY)
    driver.warmup()
    got = study_split.split(driver, 0.2)
    assert got["studies"] == got["calls"] > 0
    total = sum(got["scope_ms"].values()) + got["unscoped_ms"]
    assert total == pytest.approx(got["busy_ms"], rel=1e-6)
    assert got["program_idle_ms"]["sweep.summarize"] > 0
