"""The split of a traced window by stage scope, and the metrics that read it."""

import json
import types

import pytest

import benchpath  # noqa: F401
from bench import devtrace, harness, scopes

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
CLIENT = "jit(chunk)/while/body/closed_call/vmap(safl.client)/while/body/dot"
SKETCH = "jit(chunk)/while/body/closed_call/safl.sketch/vmap()/gather"
DERIVE_IN_SKETCH = ("jit(chunk)/while/body/closed_call/safl.derive/"
                    "vmap(jit(_rademacher))/safl.sketch/mul")
BM = json.loads((benchpath.ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = {"client_span_ms": "safl.client",
                "derive_span_ms": "safl.derive",
                "sketch_span_ms": "safl.sketch",
                "desk_span_ms": "safl.desk",
                "server_opt_span_ms": "safl.server_opt"}


def _ops(plane, *evs):
    return [[plane, devtrace.OPS, f"op.{i}", s, d, op]
            for i, (s, d, op) in enumerate(evs)]


def _synthetic():
    """Two chips over a 100 us window; times in ns."""
    return ([[HOST, "python", devtrace.WINDOW, 0, 100_000, ""],
             [HOST, "python", "run_scan.fetch", 55_000, 10_000, ""],
             [HOST, "python", "np.asarray", 58_000, 2_000, ""],
             [D0, devtrace.MODULES, "jit_chunk(1)", 0, 100_000, ""]]
            # device 0: a client while loop 0-50 us enclosing two of its
            # body ops, an op 70-80 us whose inherited path names no stage,
            # a sketch op cut at 100 us
            + _ops(D0, (0, 50_000, "jit(chunk)/safl.client/while"),
                   (5_000, 10_000, CLIENT), (20_000, 10_000, CLIENT),
                   (70_000, 10_000, "~jit(chunk)/while/body/add"),
                   (90_000, 20_000, SKETCH))
            # device 1: sketch 0-40 us with a derive op inside it, a sketch
            # op that started before the window
            + _ops(D1, (-10_000, 30_000, SKETCH), (20_000, 20_000, SKETCH),
                   (25_000, 5_000, DERIVE_IN_SKETCH)))


def test_innermost_stage_of_a_path():
    assert scopes.stage_of(CLIENT) == "safl.client"
    assert scopes.stage_of("transpose(jvp(safl.client))/dot") == "safl.client"
    assert scopes.stage_of(DERIVE_IN_SKETCH) == "safl.sketch"
    assert scopes.stage_of("jit(chunk)/while/body/DeviceBigramSampler."
                           "sample/add") == scopes.UNSCOPED
    assert scopes.stage_of("") == scopes.UNSCOPED


def test_synthetic_split_matches_hand_counts():
    r = scopes.reduce(_synthetic())
    fused = devtrace.reduce([e[:5] for e in _synthetic()])
    assert r["devices"] == 2 and r["window_s"] == pytest.approx(100e-6)
    # the while loop and the body ops it encloses count once: 50 us
    assert r["scopes"]["safl.client"] == pytest.approx(50e-6 / 2)
    assert r["scopes"][scopes.UNSCOPED] == pytest.approx(10e-6 / 2)
    assert r["inherited_s"] == 0
    # device 0: 90-100 us (clipped); device 1: 0-20 and 20-40 us less the
    # 25-30 us op, whose innermost scope is the sketch too
    assert r["scopes"]["safl.sketch"] == pytest.approx((10e-6 + 40e-6) / 2)
    assert "safl.derive" not in r["scopes"]
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])
    assert r["busy_s"] == pytest.approx(fused["busy_s"])
    # idle: device 0 50-70 us (middle 60 us: inside run_scan.fetch, the
    # runtime's np.asarray is not a program span) and 80-90 us (none);
    # device 1 40-100 us (middle 70 us: none)
    idle = r["idle_by_span"]
    assert idle["run_scan.fetch"] == pytest.approx(20e-6 / 2)
    assert idle["no host span"] == pytest.approx((10e-6 + 60e-6) / 2)
    assert "np.asarray" not in idle


def test_an_op_outliving_the_one_that_encloses_it():
    """Events that overlap without nesting: the later start wins while it
    runs, and the rest of the earlier one counts after it ends."""
    ivs = [(0, 30, "a"), (10, 50, "b"), (20, 25, "c")]
    assert scopes._exclusive(ivs) == {"a": 10, "b": 35, "c": 5}


def _ctx(window_s, rounds=2):
    return types.SimpleNamespace(fused={"window_s": window_s},
                                 rounds=rounds)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_has_an_entry_and_reads_its_scope(name, monkeypatch):
    m = {x["name"]: x for x in BM["per_layer"]}[name]
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        "ms/round", "lower", "device_trace", "round_s")
    assert m["workloads"] == ["bert100m-nob2.fed5.local4"]
    split = {"window_s": 1.0,
             "scopes": {SPAN_METRICS[name]: 0.5, scopes.UNSCOPED: 0.1}}
    monkeypatch.setattr(scopes, "_window_split", lambda ctx: split)
    assert harness.metric_reader(name).read(_ctx(1.0)) == pytest.approx(250)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_is_none_without_its_scope(name, monkeypatch, tmp_path):
    reader = harness.metric_reader(name)
    # a program without scopes: every op unscoped
    split = {"window_s": 1.0, "scopes": {scopes.UNSCOPED: 0.9}}
    monkeypatch.setattr(scopes, "_window_split", lambda ctx: split)
    assert reader.read(_ctx(1.0)) is None
    # no trace at all
    monkeypatch.undo()
    monkeypatch.setattr(harness, "OUT", tmp_path)
    assert reader.read(_ctx(1.0)) is None


def test_window_trace_is_this_runs(monkeypatch, tmp_path):
    """The readers take the newest window trace, and only where its window
    is the one the harness reduced."""
    trace = tmp_path / "cell.7" / "window" / "plugins" / "x.xplane.pb"
    trace.parent.mkdir(parents=True)
    trace.write_bytes(b"")
    monkeypatch.setattr(harness, "OUT", tmp_path)
    monkeypatch.setattr(scopes, "load", lambda path: _synthetic())
    ctx = _ctx(100e-6)
    assert scopes.ms_per_round(ctx, "safl.client") == \
        pytest.approx(1e3 * 25e-6 / 2)
    # kept for the other readers: busy 70 and 40 us on the two devices
    assert ctx.scope_split["busy_s"] == pytest.approx(55e-6)
    assert scopes.ms_per_round(_ctx(99e-6), "safl.client") is None


# a cut of a window traced on a TPU v5e in bert100m-nob2.fed5.local4, with
# each op's path as ``scopes.load`` gave it: the end of one round (the
# server step), the gap while the host fetches its loss, and the next
# round's first 13 ms (derive, sample, the sketch's index arrays, the
# start of the clients' loops); its bench_window is the cut's own 16 ms
RECORDED = benchpath.ROOT / "tests/bench/data/tpu_round_scopes.json"


def _by_hand(events):
    """Each elementary interval of the window to the op that started last
    among those running over all of it: a count independent of
    ``scopes._exclusive``."""
    (w0, w1), = [(s, s + d) for p, l, n, s, d, op in events
                 if n == devtrace.WINDOW]
    ops = [(max(s, w0), min(s + d, w1), scopes.stage_of(op))
           for p, l, n, s, d, op in events if l == devtrace.OPS
           and min(s + d, w1) > max(s, w0)]
    cuts = sorted({x for s, e, _ in ops for x in (s, e)})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        running = [o for o in ops if o[0] <= a and o[1] >= b]
        if running:
            scope = max(running, key=lambda o: (o[0], -o[1]))[2]
            out[scope] = out.get(scope, 0) + (b - a) * 1e-9
    return out


def test_recorded_tpu_cut_matches_a_count_by_hand():
    events = json.loads(RECORDED.read_text())
    r = scopes.reduce(events)
    hand = _by_hand(events)
    assert r["scopes"] == pytest.approx(hand, rel=1e-9)
    assert sum(r["scopes"].values()) == pytest.approx(
        devtrace.reduce([e[:5] for e in events])["busy_s"])
    # ops lie inside longer ones (the clients' loops), so summing their
    # durations would count time twice
    ops = [e for e in events if e[1] == devtrace.OPS]
    inside = [e for e in ops if any(
        o is not e and o[3] <= e[3] and o[3] + o[4] >= e[3] + e[4]
        for o in ops)]
    assert len(inside) > 10
    assert {"safl.client", "safl.derive", "safl.sketch", "safl.desk",
            "safl.server_opt", "driver.sample"} <= set(r["scopes"])
    assert r["scopes"].get(scopes.UNSCOPED, 0) < 1e-3 * r["busy_s"]
    # paths XLA left off its own instructions were inherited in the HLO
    assert 0 < r["inherited_s"] < r["busy_s"]
    # the one long gap, between the rounds, falls in the host's fetch
    (name, gap), = [(k, v) for k, v in r["idle_by_span"].items()
                    if v > 1e-4]
    assert name == "run_scan.fetch"
    assert gap == pytest.approx(r["window_s"] - r["busy_s"], rel=0.01)


def _varint(x):
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def _msg(*fields):
    """Protobuf bytes of ``(number, value)`` fields: an int as a varint, a
    list of ints packed, bytes or str length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
            continue
        if isinstance(v, list):
            v = b"".join(_varint(x) for x in v)
        v = v.encode() if isinstance(v, str) else v
        out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _instr(i, name, opcode, path="", args=(), calls=()):
    """An HloInstructionProto; an id of 0 is left out, as proto3 does."""
    meta = [(7, _msg((2, path)))] if path else []
    ids = [(35, i)] if i else []
    return _msg((1, name), (2, opcode), *meta, *ids, (36, list(args)),
                (38, list(calls)))


def test_hlo_paths_inherit_where_xla_left_none():
    main = _msg((1, "main"), (5, 1), (6, 2), *[(2, x) for x in (
        _instr(1, "param.0", "parameter"),
        _instr(2, "gather.1", "gather", "jit(f)/safl.sketch/gather", [3]),
        _instr(3, "while.1", "while", "", [1], [2]),
        _instr(4, "fusion.1", "fusion", "", [1], [3]),
        _instr(5, "copy.1", "copy", "", [4, 0]),
        _instr(0, "constant.1", "constant"))])
    body = _msg((1, "body"), (5, 2), (6, 21),
                (2, _instr(21, "dynamic-update-slice.1",
                           "dynamic-update-slice")))
    fused = _msg((1, "fused"), (5, 3), (6, 31),
                 (2, _instr(31, "multiply.1", "multiply",
                            "jit(f)/safl.desk/mul")))
    proto = _msg((1, _msg(*[(3, c) for c in (main, body, fused)])))
    assert scopes._hlo_paths(proto) == {
        "gather.1": "jit(f)/safl.sketch/gather",          # its own
        "while.1": "~jit(f)/safl.sketch/gather",          # its consumer
        "dynamic-update-slice.1": "~jit(f)/safl.sketch/gather",  # caller
        "fusion.1": "~jit(f)/safl.desk/mul",              # its root
        "copy.1": "~jit(f)/safl.desk/mul",                # its operand
        "param.0": "~jit(f)/safl.sketch/gather",          # its consumer
        "constant.1": "~jit(f)/safl.desk/mul",            # id 0, consumer
        "multiply.1": "jit(f)/safl.desk/mul"}


def _xspace():
    """A trace in the profiler's text form: device ops whose op_name sits
    on their event metadata (as a string or a reference) or only in the
    program's HLO, which the metadata plane holds."""
    hlo = _msg((1, _msg((3, _msg(
        (1, "main"), (5, 1), (6, 4),
        (2, _instr(3, "multiply.3", "multiply", "jit(chunk)/safl.desk/mul")),
        (2, _instr(4, "copy.4", "copy", "", [3])))))))
    escaped = "".join(f"\\{b:03o}" for b in hlo)
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 7 offset_ps: 1000000 duration_ps: 5000000 }}
    events {{ metadata_id: 8 offset_ps: 6000000 duration_ps: 1000000 }}
    events {{ metadata_id: 9 offset_ps: 7000000 duration_ps: 1000000 }}
    events {{ metadata_id: 10 offset_ps: 8000000 duration_ps: 1000000 }} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 11 offset_ps: 0 duration_ps: 10000000 }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "%fusion.1 = f32[8]"
    stats {{ metadata_id: 1 str_value: "jit(chunk)/safl.sketch/gather" }} }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "%while.2 = s32[]"
    stats {{ metadata_id: 3 str_value: "other" }}
    stats {{ metadata_id: 1 ref_value: 2 }} }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "%multiply.3 = f32[8]" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "%copy.4 = f32[8]" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_chunk(1)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2
    name: "jit(chunk)/safl.client/while" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "hlo_category" }} }}
}}
planes {{
  id: 2 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_chunk(1)"
    stats {{ metadata_id: 1 bytes_value: "{escaped}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
planes {{
  id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench_window" }} }}
}}
"""


def test_load_reads_the_op_name_from_event_metadata_or_hlo(tmp_path):
    import jax
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(_xspace()))
    events = scopes.load(str(path))
    ops = {e[2]: e[5] for e in events if e[1] == devtrace.OPS}
    assert ops == {"%fusion.1 = f32[8]": "jit(chunk)/safl.sketch/gather",
                   "%while.2 = s32[]": "jit(chunk)/safl.client/while",
                   "%multiply.3 = f32[8]": "jit(chunk)/safl.desk/mul",
                   "%copy.4 = f32[8]": "~jit(chunk)/safl.desk/mul"}
    assert [e[:5] for e in events] == devtrace.load(str(path))
    r = scopes.reduce(events)
    assert r["scopes"] == pytest.approx(
        {"safl.sketch": 5e-6, "safl.client": 1e-6, "safl.desk": 2e-6})
    assert r["inherited_s"] == pytest.approx(1e-6)
