"""The trace reduction, on a trace recorded on a TPU v5e and on intervals
made up for the cases one chip cannot record (collectives)."""
import pathlib

import pytest

from bench import trace as T

DATA = pathlib.Path(__file__).parent / "data" / "tpu_v5e_sketch.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return T.load(str(DATA))


def brute_busy(ops, window, step=1000.0):
    """Busy time by sampling every ``step`` ns: a slow, obviously right
    union of intervals."""
    w0, w1 = window
    n = int((w1 - w0) // step)
    hit = 0
    for k in range(n):
        t = w0 + (k + 0.5) * step
        if any(o.start <= t < o.end for o in ops):
            hit += 1
    return hit * step


def test_recorded_trace_has_the_device_ops_and_host_spans(recorded):
    assert list(recorded.ops) == [0]
    names = [o.name for o in recorded.ops[0]]
    assert sum("tpu_custom_call" in n for n in names) == 3
    assert [s.name for s in recorded.spans].count("bench.call") == 3
    w0, w1 = recorded.window()
    assert w1 - w0 == pytest.approx(49231739.0)


def test_busy_union_matches_sampling(recorded):
    win = recorded.window()
    got = T.busy_ns(recorded, win, 0)
    want = brute_busy(recorded.ops[0], win)
    assert got == pytest.approx(want, abs=2 * 1000.0 * 13)
    assert T.mean_busy_ns(recorded, win, [0]) == got


def test_idle_gaps_fill_the_rest_of_the_window(recorded):
    win = recorded.window()
    gaps = T.idle_gaps(recorded, win, 0)
    idle = sum(e - s for s, e in gaps)
    assert idle + T.busy_ns(recorded, win, 0) == pytest.approx(win[1] - win[0])
    labelled = T.idle_by_host(recorded, win, 0)
    assert sum(s for _, s in labelled) == pytest.approx(idle * 1e-9)
    assert {n for n, _ in labelled} <= {"bench.call", "bench.other",
                                        "host.other"}


def test_op_seconds_groups_by_name(recorded):
    win = recorded.window()
    ops = T.op_seconds(recorded, win, [0])
    top, secs = ops[0]
    assert top == "sketch_matmul.1 f32[4096,256] custom-call"
    want = sum(min(o.end, win[1]) - max(o.start, win[0])
               for o in recorded.ops[0] if o.name.startswith("%sketch"))
    assert secs == pytest.approx(want * 1e-9)
    assert {n.split(" ")[-1] for n, _ in ops} == {
        "custom-call", "copy-start", "copy-done", "fusion"}


def test_short_names():
    assert T.short_name("%copy-done = f32[8]{0:T(128)} copy-done((f32[8], "
                        "u32[]) %copy-start)") == "copy-done f32[8] copy-done"
    assert T.short_name("%pad = u32[4]{0:T(128)S(1)} pad(u32[2]{0} %k)") \
        == "pad u32[4] pad"
    assert T.short_name("jit_step(123)") == "jit_step(123)"


def test_merge_subtract_and_clip():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.clip([(0, 5), (8, 12)], (2, 10)) == [(2, 5), (8, 10)]
    assert T.length([(0, 2), (1, 4)]) == 4


def test_exposed_collectives_exclude_overlapped_compute():
    ops = {0: [T.Op(0, 100, "fusion.1"),
               T.Op(80, 130, "%reduce-scatter.2 = f32[64,256] ..."),
               T.Op(140, 150, "all-gather-start.3")],
           1: [T.Op(0, 40, "fusion.1"),
               T.Op(10, 60, "reduce-scatter.2")]}
    tr = T.Trace(ops, [T.Span(0, 200, "bench.window")])
    win = tr.window()
    assert T.exposed_collective_ns(tr, win, 0) == 30 + 10
    assert T.exposed_collective_ns(tr, win, 1) == 20
    assert T.busy_ns(tr, win, 0) == 140
    assert T.mean_busy_ns(tr, win, [0, 1]) == (140 + 60) / 2


def test_window_span_is_required():
    tr = T.Trace({0: []}, [])
    with pytest.raises(ValueError):
        tr.window()


def test_idle_labels_prefer_the_span_covering_most():
    tr = T.Trace({0: [T.Op(0, 10, "a"), T.Op(50, 60, "b")]},
                 [T.Span(0, 100, "bench.window"),
                  T.Span(5, 30, "bench.submit"),
                  T.Span(28, 52, "bench.update_ragged")])
    got = dict(T.idle_by_host(tr, tr.window(), 0))
    assert got["bench.update_ragged"] == pytest.approx(40e-9)
    assert got["host.other"] == pytest.approx(40e-9)
