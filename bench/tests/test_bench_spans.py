"""The program's host spans as the benchmark reads them: the names are
the program's, self time is a span's duration less the program spans
nested in it, a trace without them gives no metric, and a traced run of
each cell reports every span metric."""
import os
import time
import types

import jax
import pytest

import bench_tiny
from lib import manifest as mf
from lib import spans
from lib.readers import Context
from lib.trace import TRACED_SPAN, find_xplane, reduce

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb")
SPAN_METRICS = {
    "cw09b.ingest.bulk": ["flush.copy_ms_per_kdoc", "flush.segment_ms_per_kdoc",
                          "codec.encode_ms_per_kdoc",
                          "storage.write_ms_per_kdoc",
                          "storage.sync_ms_per_kdoc",
                          "flush.account_ms_per_kdoc"],
    "cw09b.batch.k1000": ["search.plan_ms_per_batch",
                          "prune.host_ms_per_batch",
                          "searcher.sync_ms_per_batch"],
}


def test_span_names_are_the_programs():
    from repro.spans import SPANS
    assert spans.SPAN_NAMES == tuple(SPANS)


def test_span_metrics_are_declared_for_their_cell():
    m = mf.load()
    for cell, names in SPAN_METRICS.items():
        for n in names:
            x, = [x for x in m["per_layer"] if x["name"] == n]
            assert x["source"] == "program_span" and x["workloads"] == [cell]


def test_self_time_is_duration_less_nested_program_spans(tmp_path):
    from repro.spans import span
    with jax.profiler.trace(str(tmp_path)):
        with span("search.merge"):          # before the traced span
            time.sleep(0.01)
        with jax.profiler.TraceAnnotation(TRACED_SPAN):
            with span("sched.step", step=1, batch=1):
                time.sleep(0.02)
                with span("search.plan"):
                    with jax.profiler.TraceAnnotation("not.a.program.span"):
                        time.sleep(0.03)
    t = spans.span_times(jax.profiler.ProfileData.from_file(
        find_xplane(str(tmp_path))))
    assert set(t) == {"sched.step", "search.plan"}
    assert t["search.plan"].self_s == t["search.plan"].total_s >= 0.03
    assert 0.02 <= t["sched.step"].self_s < 0.03
    assert t["sched.step"].total_s >= 0.05
    assert t["sched.step"].count == t["search.plan"].count == 1


def test_trace_without_program_spans_gives_no_metric():
    """The recorded v5e sample predates the program's spans, as a parent
    program's trace does: every span metric stays out of the line."""
    profile = jax.profiler.ProfileData.from_file(SAMPLE)
    assert spans.span_times(profile) == {}
    tracer = types.SimpleNamespace(state="done",  # noqa: F841 (of_run)
                                   dir=os.path.dirname(SAMPLE))
    ctx = Context(window_s=1.0, trace=reduce(profile),
                  counters={"traced_docs": 4096, "traced_batches": 3})
    for names in SPAN_METRICS.values():
        for n in names:
            assert mf.metric_reader(n)(ctx) is None, n


def test_traced_run_without_its_tracer_raises():
    """A reader called where no finished tracer is found (the harness
    changed) fails loudly instead of dropping every span metric."""
    ctx = Context(window_s=1.0,
                  trace=reduce(jax.profiler.ProfileData.from_file(SAMPLE)),
                  counters={"traced_docs": 4096})
    with pytest.raises(RuntimeError, match="tracer"):
        mf.metric_reader("flush.segment_ms_per_kdoc")(ctx)
    assert mf.metric_reader("flush.segment_ms_per_kdoc")(
        Context(window_s=1.0)) is None


@pytest.mark.parametrize("workload", sorted(SPAN_METRICS))
def test_traced_run_reports_every_span_metric(workload):
    out = bench_tiny.run_small(workload, trace=True)
    assert out["correct"] is True, out["checks"]
    for n in SPAN_METRICS[workload]:
        assert out["metrics"][n]["unit"] == "ms"
        assert out["metrics"][n]["value"] >= 0, n
