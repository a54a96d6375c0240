"""``bench/span_reduce.py`` and the four readers of the program's spans,
on a small trace recorded on one v5e chip (``data/spans.xplane.pb``).

The recorded program: the small engine of the decode cell
(``bench/tests/tiny.py``) set up and warmed, then, inside the host span
``bench_window``, three requests (4 rows each; prompts of 128, 64 and
32 tokens; 2, 8 and 6 tokens out) served through
``bench/systems/engine.py`` (placement, then ``generate``) with the
Python tracer off.  The file keeps what the reducers read: the host
planes whole, and the chip's ``XLA Modules`` and ``XLA Ops`` events
with their names and times (their stats, the other device lines and the
HLO protos of ``/host:metadata`` were dropped to keep it small).  At
this size the chip is busy 0.6 ms of a 72.7 ms window: nearly every gap
is the host's.  The expected numbers below were read off the trace's
events by hand."""

import os
import shutil

import pytest

from bench import run as R
from bench import span_reduce as S
from bench.readers import Context
from bench.trace_reduce import reduce_trace

HERE = os.path.join(os.path.dirname(__file__), "data")
DATA = os.path.join(HERE, "spans.xplane.pb")
SMALL = os.path.join(HERE, "small.xplane.pb")
READERS = ("place_ms.prefill", "engine_idle_ms.prefill",
           "engine_idle_ms.decode", "step_host_ms.decode")


@pytest.fixture(scope="module")
def red():
    return S.reduce_spans(DATA)


def _as_run_trace(tmp_path, monkeypatch, src):
    """``src`` where ``bench/run.py`` keeps the trace of its window, and
    the reduced trace its readers are given."""
    d = tmp_path / "bench_trace_x" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(src, d / "vm.xplane.pb")
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    return Context(reduce_trace(src, ("generate",)), {}, {}, "TPU v5 lite")


def _read(ctx):
    return {m: R.load_reader(m)(ctx) for m in READERS}


def test_span_counts(red):
    # 3 requests of 2 + 8 + 6 tokens: a sampling per token, a decode
    # step per token after the first
    assert {k: n for k, (n, _) in red.spans.items()} == {
        "placement.estimate": 3, "engine.generate": 3,
        "engine.prefill": 3, "engine.sample": 16,
        "engine.decode_step": 13, "engine.wait": 3}
    assert red.requests == 3
    # placement.estimate: 192,860 + 132,291 + 196,390 ns
    assert red.seconds("placement.estimate") == pytest.approx(
        521_541e-9, rel=1e-9)


def test_idle_covers_the_window(red):
    t = reduce_trace(DATA, ("generate",))
    assert (red.window_s, red.busy_s) == (t.window_s, t.busy_s)
    # bench_window: 42,109,265 ns to 114,778,977 ns
    assert red.window_s == pytest.approx(72_669_712e-9, rel=1e-9)
    assert sum(red.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert set(red.idle_by_span) == {
        S.OUTSIDE, S.IN_PROGRAM, "placement.estimate", "engine.generate",
        "engine.prefill", "engine.sample", "engine.decode_step",
        "engine.wait"}


def test_a_gap_read_by_hand(red):
    # the chip idles from 88,423,042 ns (the end of request 2's last
    # operation) to 90,066,230 ns (request 3's prefill); the middle,
    # 89,244,636 ns, lies in request 3's placement.estimate span
    # (89,058,680 to 89,255,070 ns), inside the benchmark's generate
    # span and outside any program: the only gap under that key
    assert red.idle_by_span["placement.estimate"] == pytest.approx(
        1_643_188e-9, rel=1e-9)
    # the two gaps outside the program: between requests 1 and 2
    # (57,465,898 to 58,782,340 ns; its middle, 58,124,119 ns, after
    # request 2's placement.estimate ends at 58,112,914 ns and before
    # its engine.generate starts at 58,147,453 ns) and after request 3
    # (114,035,766 ns to the window's end)
    assert red.idle_by_span[S.OUTSIDE] == pytest.approx(
        (1_316_442 + 743_211) * 1e-9, rel=1e-9)


def test_prefill_dispatch_offsets():
    # each engine.prefill span's start to its prefill program's start
    assert S.dispatch_offsets(DATA) == pytest.approx(
        [725_680e-9, 631_931e-9, 769_193e-9], rel=1e-6)


def test_readers_read_the_runs_spans(tmp_path, monkeypatch):
    ctx = _as_run_trace(tmp_path, monkeypatch, DATA)
    got = _read(ctx)
    # 521,541 ns of placement over 3 requests
    assert got["place_ms.prefill"] == pytest.approx(0.173847, rel=1e-5)
    # idle under engine.*: prefill 24,823,266 + generate 2,442,103 +
    # sample 4,433,940 + decode_step 30,816,222 + wait 5,848,557 ns
    engine = 68_364_088e-9 / 3 * 1e3
    assert got["engine_idle_ms.prefill"] == pytest.approx(engine, rel=1e-9)
    assert got["engine_idle_ms.decode"] == pytest.approx(engine, rel=1e-9)
    # 13 decode steps, 32,861,207 ns of host time
    assert got["step_host_ms.decode"] == pytest.approx(
        32.861207 / 13, rel=1e-9)


def test_readers_silent_without_program_spans(tmp_path, monkeypatch):
    # a trace of a program that opens none of the spans, as the parent
    # of the spans' change
    ctx = _as_run_trace(tmp_path, monkeypatch, SMALL)
    assert S.of_run(ctx).spans == {}
    assert _read(ctx) == dict.fromkeys(READERS)


def test_readers_silent_without_the_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    ctx = Context(reduce_trace(SMALL, ("generate",)), {}, {}, "TPU v5 lite")
    assert S.of_run(ctx) is None
    assert _read(ctx) == dict.fromkeys(READERS)


def test_another_window_is_not_read(tmp_path, monkeypatch):
    # the newest trace under the temporary directory is of another run
    ctx = _as_run_trace(tmp_path, monkeypatch, SMALL)
    ctx.trace = reduce_trace(DATA, ("generate",))
    assert S.of_run(ctx) is None
