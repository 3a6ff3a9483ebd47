"""``chipbench/phases.py`` on a small recorded trace with the stats kept: a
text-proto cut from this PR's BERT-Large trace over four chips of the v5e
(4 executions of the step; of each execution the 3 longest leaf operations of
every phase; each event's metadata with its ``tf_op`` stat, where the scope
lives), and on scopes spelled as that trace spells them."""

import os

import pytest
from jax.profiler import ProfileData

from chipbench import phases, trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(HERE, "fixture_phases_trace.textproto")) as f:
        return f.read()


@pytest.fixture(scope="module")
def profile(text):
    return phases.parse(ProfileData.text_proto_to_serialized_xspace(text))


def test_the_wire_reader_sees_what_profiledata_sees_plus_the_scope(text,
                                                                   profile):
    theirs = [p for p in ProfileData.from_text_proto(text).planes
              if p.name == "/device:TPU:0"][0]
    mine = profile.planes[0]
    assert [l.name for l in mine.lines] == [l.name for l in theirs.lines]
    for a, b in zip(mine.lines, theirs.lines):
        assert [(e.name, e.start_ns, e.end_ns) for e in a.events] == \
            [(e.name, e.start_ns, e.end_ns) for e in b.events]
    scopes = {e.scope for e in trace._line(mine, "XLA Ops").events}
    assert any("jvp(hvd_forward)" in s for s in scopes)
    # ProfileData shows an event's own stats only: the scope is not there
    assert not any("hvd_" in str(v) for l in theirs.lines for e in l.events
                   for _, v in e.stats)


def test_phases_sum_to_the_busy_time(text, profile):
    got = phases.by_phase(profile)
    busy = got.pop("busy")
    reduced = trace.reduce(ProfileData.from_text_proto(text))
    assert busy == pytest.approx(reduced.busy_s / reduced.steps, rel=1e-12)
    assert sum(got.values()) == pytest.approx(busy, rel=1e-9)
    assert set(got) == {p for p, _ in phases.PHASES} | {phases.OTHER}
    assert got["forward"] > 0 and got["backward"] > 0 \
        and got["optimizer"] > 0 and got["other"] > 0


@pytest.mark.parametrize("scope,phase", [
    ("jit(step)/jvp(hvd_forward)/Bert/layer_0/attn/dot_general:", "forward"),
    ("jit(step)/jvp(hvd_forward)/Bert/layer_0/jit(_fa_call)/hvd_flash_fwd/"
     "pallas_call:", "forward"),
    ("jit(step)/transpose(jvp(hvd_forward))/Bert/jit(_take)/scatter-add:",
     "backward"),
    # the backward of a remat region: JAX repeats the forward's stack inside
    ("jit(step)/transpose(jvp(hvd_forward))/Bert/jvp(hvd_forward)/Bert/"
     "checkpoint/layer_1/ffn_norm/div:", "backward"),
    ("jit(step)/transpose(jvp(hvd_forward))/Bert/jvp(hvd_forward)/Bert/"
     "checkpoint/rematted_computation/layer_1/tanh:", "remat"),
    ("jit(step)/hvd_optimizer/add:", "optimizer"),
    ("jit(step)/shmap_body/hvd_exchange_pack/concatenate:", "exchange_pack"),
    ("jit(step)/shmap_body/hvd_exchange_reduce/psum:", "exchange_reduce"),
    ("jit(step)/shmap_body/hvd_exchange_unpack/convert_element_type:",
     "exchange_unpack"),
    # precedence: a scope that holds two markers takes the earlier phase
    ("jit(step)/hvd_optimizer/transpose(jvp(hvd_forward))/mul:", "optimizer"),
    ("jit(step)/hvd_exchange_unpack/hvd_optimizer/mul:", "exchange_unpack"),
    ("jit(step)/hvd_exchange_pack/jvp(hvd_forward)/mul:", "exchange_pack"),
    ("", "other"), ("jit(step)/copy:", "other"),
])
def test_a_scope_s_phase_and_the_precedence(scope, phase):
    assert phases.phase_of(scope) == phase


def _stat_plane(events):
    """Three executions of 100 ns; ``events`` as ``(scope, start, ns)``."""
    text = 'planes { id: 1 name: "/device:TPU:0"\n lines { id: 1 name: ' \
        '"XLA Modules"\n' + "".join(
            f"events {{ metadata_id: 1 offset_ps: {s * 1000} duration_ps: "
            f"100000 }}\n" for s in (0, 100, 200)) + "}\n"
    text += ' lines { id: 2 name: "XLA Ops"\n' + "".join(
        f"events {{ metadata_id: {i + 2} offset_ps: {s * 1000} duration_ps: "
        f"{d * 1000} }}\n" for i, (_, s, d) in enumerate(events)) + "}\n"
    text += ' event_metadata { key: 1 value { id: 1 name: "jit_step(1)" } }\n'
    for i, (scope, _, _) in enumerate(events):
        stat = f' stats {{ metadata_id: 7 str_value: "{scope}" }}' \
            if scope else ""
        text += (f' event_metadata {{ key: {i + 2} value {{ id: {i + 2} name: '
                 f'"%fusion.{i} = f32[8] fusion("{stat} }} }}\n')
    return text + ' stat_metadata { key: 7 value { id: 7 name: "tf_op" } }\n}\n'


def test_by_phase_is_a_union_over_the_reducer_s_window():
    step = lambda t: [
        ("jit(step)/jvp(hvd_forward)/dot_general:", t, 30),
        ("jit(step)/transpose(jvp(hvd_forward))/dot_general:", t + 30, 40),
        ("jit(step)/transpose(jvp(hvd_forward))/mul:", t + 60, 10),  # covered
        ("jit(step)/hvd_exchange_reduce/psum:", t + 70, 12),
        ("jit(step)/hvd_optimizer/add:", t + 82, 8),
        ("", t + 90, 5),
    ]
    text = _stat_plane(step(0) + step(100) + step(200))
    got = phases.by_phase(phases.parse(
        ProfileData.text_proto_to_serialized_xspace(text)))
    ns = {k: v * 1e9 for k, v in got.items()}
    assert ns == pytest.approx({
        "forward": 30, "backward": 40, "exchange_reduce": 12, "optimizer": 8,
        "other": 5, "remat": 0, "exchange_pack": 0, "exchange_unpack": 0,
        "busy": 95})


def test_a_ref_value_stat_is_looked_up():
    text = _stat_plane([("", 100, 50), ("", 200, 50)]).replace(
        ' name: "%fusion.0 = f32[8] fusion("',
        ' name: "%fusion.0 = f32[8] fusion(" stats { metadata_id: 7 '
        'ref_value: 9 }').replace(
        ' stat_metadata { key: 7', ' stat_metadata { key: 9 value { id: 9 '
        'name: "jit(step)/hvd_optimizer/add:" } }\n stat_metadata { key: 7')
    got = phases.by_phase(phases.parse(
        ProfileData.text_proto_to_serialized_xspace(text)))
    assert got["optimizer"] * 1e9 == pytest.approx(50 / 2)


def test_main_prints_the_table(tmp_path, text, capsys):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert phases.main([str(path)]) == 0
    import json
    out = json.loads(capsys.readouterr().out)
    assert out["sum_over_busy"] == pytest.approx(1.0)
    assert 0 < out["other_share"] < 1
    assert set(out["ms_a_step"]) == {p for p, _ in phases.PHASES} | {"other"}


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        phases.parse(ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/host:CPU" }'))
