"""The serving engine's ``engine.*`` spans read from its tracer, placed on
the window's clock, device idle time split among them on synthetic
intervals, and the three readers built on them; then a traced run at test
size on the CPU."""
import types

import pytest

from benchcells import run_tiny, tiny_cell
from harness import cells, engine_spans, xplane

CHAT = "serve.smollm-135m.chat"


def test_innermost_pieces_of_nested_spans():
    # a serve [0, 20) holding two steps, each with phases back to back
    pieces = engine_spans.innermost([
        (0, 20, "serve"), (1, 9, "step"), (1, 2, "admit"), (2, 4, "prepare"),
        (4, 8, "sync"), (12, 18, "step"), (13, 17, "sync")])
    assert pieces == [
        (0, 1, "serve"), (1, 2, "admit"), (2, 4, "prepare"), (4, 8, "sync"),
        (8, 9, "step"), (9, 12, "serve"), (12, 13, "step"), (13, 17, "sync"),
        (17, 18, "step"), (18, 20, "serve")]


def test_cover_splits_gaps_among_pieces():
    pieces = [(0, 2, "a"), (2, 5, "b"), (7, 9, "a")]
    # a gap across a and b, one in no piece, one inside the last a
    assert engine_spans.cover([(1, 3), (5, 7), (7.5, 8)], pieces) == {
        "a": 1.5, "b": 1}
    assert engine_spans.cover([], pieces) == {}


def _x(name, t0_s, t1_s, **args):
    """A tracer complete event; the tracer's clock runs 100 s ahead of the
    window's here."""
    ev = {"name": name, "ph": "X", "ts": (t0_s + 100.0) * 1e6,
          "dur": (t1_s - t0_s) * 1e6}
    return {**ev, "args": args} if args else ev


def _events(engine=True):
    ev = [{"name": "admission", "ph": "i", "ts": 101e6, "args": {"req": 0}},
          _x("step", 2.0, 5.0, phase="chunk_prefill")]
    if engine:
        # the serve is called at the window's start, its loop 0.5 s later
        ev += [_x("engine.admit", 1.0, 2.0, req=0),
               _x("engine.dispatch", 2.0, 3.0), _x("engine.sync", 3.0, 5.0),
               _x("engine.step", 1.0, 5.0, phase="chunk_prefill", live=3),
               _x("engine.step", 6.0, 7.0, phase="decode", live=2),
               _x("engine.wait_arrival", 8.0, 9.5),
               _x("engine.serve", 0.5, 10.0, setup_us=0.5e6)]
    return ev


def _run(devices=1, engine=True, n_slots=4):
    summary = xplane.Summary(
        window_s=10.0, busy_s=6.5, devices=devices, op_self_s={},
        op_count={}, idle_by_span={"serve": 3.5},
        gaps=[(0.25, 1.5, "serve"), (2.5, 3.5, "serve"), (9.0, 10.0, "serve")])
    tracer = types.SimpleNamespace(events=_events(engine))
    return types.SimpleNamespace(
        kind="serve", trace=summary,
        cell=types.SimpleNamespace(traffic={"n_slots": n_slots}),
        serve={"obs": types.SimpleNamespace(tracer=tracer)})


def test_spans_on_the_window_clock():
    found = engine_spans.spans(_run())
    assert [n for n, *_ in found][-1] == "engine.serve"
    starts = {n: (a, b) for n, a, b, _ in found}
    assert starts["engine.serve"] == pytest.approx((0.5, 10.0))
    assert starts["engine.admit"] == pytest.approx((1.0, 2.0))
    assert found[0][3] == {"req": 0}
    assert engine_spans.spans(_run(engine=False)) is None
    untraced = _run()
    untraced.serve = {"obs": None}
    assert engine_spans.spans(untraced) is None


def test_idle_by_engine_span():
    assert engine_spans.idle_by_span(_run()) == pytest.approx({
        "engine.serve": 1.0, "engine.admit": 0.5, "engine.dispatch": 0.5,
        "engine.sync": 0.5, "engine.wait_arrival": 0.5})
    # no device plane (the CPU), or a program without engine spans: nothing
    assert engine_spans.idle_by_span(_run(devices=0)) is None
    assert engine_spans.idle_by_span(_run(engine=False)) is None


@pytest.mark.parametrize("name, want", [
    ("scheduler_idle.serve", 100.0 * 1.0 / 10.0),
    ("prefill_step_ms", 4000.0),
    ("slot_occupancy.serve", 100.0 * 2.5 / 4),
])
def test_engine_span_readers(name, want):
    read = cells.metric_reader(name)
    assert read(_run()) == pytest.approx(want)
    # a parent program, whose tracer holds no engine spans, reads nothing
    assert read(_run(engine=False)) is None


def test_traced_tiny_chat_reports_engine_span_metrics():
    out = run_tiny(tiny_cell(CHAT), seconds=0.5, trace=1)
    assert out["correct"]
    assert out["metrics"]["prefill_step_ms"]["value"] > 0
    assert 0 < out["metrics"]["slot_occupancy.serve"]["value"] <= 100
    # the CPU has no TPU plane to read idle time from
    assert "scheduler_idle.serve" not in out["metrics"]
