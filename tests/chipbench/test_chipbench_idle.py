"""The device's idle time put down to the innermost host span, read from the
trace: on a small trace recorded on the CPU with the engine's span
recorder (committed beside this file: a jitted matmul under ``moe.route``,
then ``expert.fetch`` 8 ms and 2 ms of ``decode.step``'s own time, the
route held 4 ms past the matmul, each step under ``bench.poll`` inside
``bench.window``, three times, 3 ms apart), and end to end in a traced
run of the tiny cell."""
import shutil
import sys
import tempfile
from types import SimpleNamespace

import pytest

from chipbench_tiny import CELL, DATA, REPO, bench_copy

sys.path.insert(0, str(REPO))
from benchmarks.chip import harness, idle, spec, trace  # noqa: E402
from repro.obs.spans import SPAN_NAMES  # noqa: E402

ENGINE = DATA / "cpu_engine_spans.xplane.pb"
WINDOW = DATA / "cpu_window.xplane.pb"
NEW = ("residency.h2d_exposed_share.prefill",
       "scheduler.route_idle_share.prefill")


def test_idle_by_span_of_a_recorded_trace():
    s = idle.split(str(ENGINE), SPAN_NAMES)
    assert s.n_engine_spans == 9
    assert set(s.by_span) == {"expert.fetch", "moe.route", "decode.step",
                              "bench.poll", idle.OUTSIDE}
    # every idle nanosecond is put down to exactly one span
    assert sum(s.by_span.values()) == pytest.approx(s.idle_s, abs=1e-9)
    assert s.by_span["expert.fetch"] == pytest.approx(0.02439136)
    assert s.by_span["moe.route"] == pytest.approx(0.013332804)
    assert s.by_span["decode.step"] == pytest.approx(0.00645234)
    assert s.by_span[idle.OUTSIDE] == pytest.approx(0.009359768)
    # the sleeps the recording made, three of each
    assert 3 * 0.008 <= s.by_span["expert.fetch"] < 3 * 0.0085
    assert 3 * 0.004 <= s.by_span["moe.route"]
    assert 3 * 0.002 <= s.by_span["decode.step"] < 3 * 0.0025
    assert s.share_pct(("moe.route",)) == pytest.approx(
        100 * s.by_span["moe.route"] / s.window_s)


@pytest.mark.parametrize("path", [ENGINE, WINDOW])
def test_window_and_busy_are_those_of_the_reduction(path):
    s, r = idle.split(str(path), SPAN_NAMES), trace.reduce(str(path))
    assert (s.window_s, s.busy_s) == (r.window_s, r.busy_s)


def test_innermost_span_wins_also_when_spans_end_out_of_order():
    host = [("decode.step", 0, 100), ("expert.fetch", 10, 150),
            ("moe.route", 10, 40), ("bench.poll", 0, 200)]
    got = idle._attribute([(0, 5), (20, 30), (90, 120), (160, 170),
                           (250, 260)], host)
    assert got == {"decode.step": 5, "moe.route": 10, "expert.fetch": 30,
                   "bench.poll": 10, idle.OUTSIDE: 10}


def _traced_dir(tmp_path, fixture):
    d = tmp_path / "chipbench-trace-x" / "plugins"
    d.mkdir(parents=True)
    shutil.copy(fixture, d / "host.xplane.pb")


def test_readers_find_the_runs_trace_and_need_engine_spans(tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    _traced_dir(tmp_path, ENGINE)
    ctx = SimpleNamespace(trace=trace.reduce(str(ENGINE)))
    read = spec.metric_reader(REPO, "scheduler.route_idle_share.prefill")
    assert read(ctx) == pytest.approx(
        100 * 0.013332804 / ctx.trace.window_s)
    # another run's trace, or one without engine spans: nothing to read
    other = SimpleNamespace(trace=trace.reduce(str(WINDOW)))
    assert read(other) is None
    shutil.rmtree(tmp_path / "chipbench-trace-x")
    _traced_dir(tmp_path, WINDOW)
    assert read(SimpleNamespace(trace=trace.reduce(str(WINDOW)))) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_a_traced_tiny_cell_puts_its_idle_time_down_to_spans(tmp_path,
                                                             monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    root = bench_copy(tmp_path)
    r = harness.run_cell(root, CELL, 2 ** 32 + 29, 1.5, True,
                         require_chip=False,
                         peaks=spec.peaks_of("TPU v5 lite"))
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(got)
    assert all(0 <= got[k] <= 100 for k in NEW)
    assert sum(got[k] for k in NEW) <= got["device.idle_share.prefill"]
    # the engine's spans cover the time the device waits on the host
    s = idle._last[1]
    assert s.n_engine_spans > 0
    assert sum(s.by_span.values()) == pytest.approx(s.idle_s, rel=1e-9)
    assert {"expert.fetch", "moe.route", "moe.combine"} <= set(s.by_span)
