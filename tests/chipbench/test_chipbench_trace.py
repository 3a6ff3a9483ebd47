"""``chipbench/trace.py`` on a small recorded trace: a text-proto cut from a
BERT-Large trace taken on the v5e (5 executions of the step, the 12 longest
leaf operations of each and a few flash kernels, 20 ``Async XLA Ops`` spans,
the loop's host annotations, and the device plane a second time as
``/device:TPU:1``). The expected numbers were summed by hand from the kept
events when the fixture was cut."""

import os

import pytest
from jax.profiler import ProfileData

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "fixture_bert_trace.textproto")) as f:
        return trace.reduce(ProfileData.from_text_proto(f.read()))


def test_window_is_whole_steps_on_the_device_clock(reduced):
    assert reduced.steps == 4                 # the clipped first one dropped
    assert reduced.step_ns == [82296981.0, 82288656.0, 82282841.0, 82293633.0]
    assert reduced.window_s == pytest.approx(0.329187988, rel=1e-9)


def test_busy_is_one_plane_one_line_and_a_union(reduced):
    assert reduced.n_device_planes == 2       # seen, and not summed
    assert reduced.busy_s == pytest.approx(0.042788109, rel=1e-9)
    assert 0 < reduced.busy_s <= reduced.window_s
    # the async spans kept alone sum to 0.0316 s and must not be in it


def test_mosaic_kernels_are_found_by_their_custom_call_target(reduced):
    got = reduced.op_seconds_per_step(trace.is_mosaic) * reduced.steps
    assert got == pytest.approx(0.006637367, rel=1e-9)
    assert reduced.exposed_seconds_per_step(trace.is_collective) == 0


def test_breakdown_names_ops_and_attributes_gaps_to_the_host(reduced):
    ops = reduced.top_ops(10)
    assert len(ops) == 10 and ops[0][0] == "multiply_reduce_fusion.123 f32[1024]"
    gaps = dict(reduced.idle_gaps(10))
    assert set(gaps) == {"host: loss fetch", "host: step call",
                         "host: next(feed)",
                         "host: outside the loop's annotations"}
    assert sum(gaps.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s, rel=1e-9)


def _plane(ops):
    names = sorted({n for n, _, _ in ops} | {"jit_step(1)"})
    ids = {n: i + 1 for i, n in enumerate(names)}
    ev = lambda n, s, d: (f"events {{ metadata_id: {ids[n]} offset_ps: "
                          f"{s * 1000} duration_ps: {d * 1000} }}\n")
    text = 'planes { id: 1 name: "/device:TPU:0"\n lines { id: 1 name: ' \
        '"XLA Modules"\n' + "".join(ev("jit_step(1)", s, 100)
                                    for s in (0, 100, 200)) + "}\n"
    text += ' lines { id: 2 name: "XLA Ops"\n' + "".join(
        ev(*o) for o in ops) + "}\n"
    for n, i in ids.items():
        text += f' event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
    return text + "}\n"


def test_exposed_collective_time_is_what_no_other_leaf_op_covers():
    step = lambda t: [
        ("%fusion.1 = f32[8] fusion(f32[8] %p)", t, 40),
        ("%all-reduce-start.2 = f32[8] all-reduce-start(f32[8] %x)", t + 40, 2),
        ("%fusion.3 = f32[8] fusion(f32[8] %p)", t + 42, 30),
        ("%all-reduce-done.2 = f32[8] all-reduce-done(f32[8] %y)", t + 72, 20),
        ("%fusion.4 = f32[8] fusion(f32[8] %p)", t + 80, 10),  # covers 80..90
    ]
    r = trace.reduce(ProfileData.from_text_proto(
        _plane(step(0) + step(100) + step(200))))
    assert r.steps == 2 and r.window == (100.0, 300.0)
    # per step: 2 ns of start + 20 of done, less the 10 the last fusion covers
    assert r.exposed_seconds_per_step(trace.is_collective) * 1e9 \
        == pytest.approx(12)
    assert r.busy_s * 1e9 == pytest.approx(2 * 92)


def test_interval_algebra():
    assert trace.merge([(5, 7), (0, 3), (2, 4), (7, 9), (1, 1)]) == \
        [(0, 4), (5, 9)]
    assert trace.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22)]) == \
        [(0, 2), (3, 8), (22, 30)]
    assert trace.intersect([(0, 10), (20, 30)], [(5, 25)]) == \
        [(5, 10), (20, 25)]
    assert trace.total([(0, 4), (5, 9)]) == 8


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(
            'planes { id: 1 name: "/host:CPU" }'))
