"""Device time of the round's stage scopes, and idle time by the driver's spans.

The program names its stages with ``jax.named_scope`` (``safl.client``,
``safl.derive``, ``safl.sketch``, ``safl.mean``, ``safl.desk``,
``safl.server_opt``, ``driver.sample``) and its per-chunk host phases with
profiler spans (``run_scan.dispatch``, ``run_scan.fetch``,
``run_scan.on_chunk``).  XLA keeps each op's scope path in its ``op_name``
metadata; on a TPU the profiler carries it as the ``tf_op`` stat of the
op's event metadata, and the trace also holds the HLO of each program.
``load(path)`` keeps the path as a sixth field of each event record;
``reduce`` splits the busy time of one traced window by stage.

Rules of the split, per device, over the op events clipped to the window:

* an op belongs to the innermost stage scope in its path (the last one
  named), or to ``unscoped`` where its path names none;
* a fusion belongs to the scope of its root, as XLA's metadata gives it: a
  producer fused into its consumer (a sign draw fused into the gather that
  reads it) is paid in the consumer's scope;
* an op whose instruction XLA made without metadata (a relayout loop and
  the copies in it; the profiler gives no ``tf_op`` to ``while`` ops)
  inherits a path in the program's HLO (``_hlo_paths``), marked ``~``, and
  ``inherited_s`` counts the time so placed;
* at each instant the device's time goes to the innermost op event then
  running, the one that started last: a ``while`` event encloses its
  body's ops, so the loop's own time is what its body leaves uncovered,
  and nothing is counted twice.  The stages' seconds sum to the union of
  all op intervals, which is ``devtrace.reduce``'s ``busy_s``.

Seconds are averaged over the devices that ran anything in the window.
Names are spelled out here, not imported from the program, so a trace of a
program that lacks them reduces to ``unscoped`` alone.
"""

from __future__ import annotations

import bisect
import collections
import json
import re
import sys

from bench import devtrace

STAGES = ("safl.client", "safl.derive", "safl.sketch", "safl.mean",
          "safl.desk", "safl.server_opt", "driver.sample")
HOST_SPANS = ("run_scan.dispatch", "run_scan.fetch", "run_scan.on_chunk")
UNSCOPED = "unscoped"
OP_NAME_STAT = "tf_op"
HLO_PLANE, HLO_STAT = "/host:metadata", "Hlo Proto"
_TOKEN = re.compile(r"[A-Za-z_][\w.]*")


def stage_of(op_name: str) -> str:
    """The innermost stage scope named in an ``op_name`` path.

    Transformations wrap names (``transpose(jvp(safl.client))``), so the
    path is read as a sequence of names rather than split on ``/``."""
    for tok in reversed(_TOKEN.findall(op_name or "")):
        if tok in STAGES:
            return tok
    return UNSCOPED


# ---------------------------------------------------------------------------
# loading: device op events with their op_name
# ---------------------------------------------------------------------------

def load(path: str) -> list[list]:
    """``devtrace.load``'s records with a sixth field, the op_name path that
    places a device op (``""`` where none does, and on host events).

    The path is the op's own: the ``tf_op`` stat of its event metadata,
    else the ``op_name`` of its instruction in the HLO of the program that
    ran it (which the trace holds).  Where the op has none, because XLA
    made the instruction (a relayout loop, the copies in it), the path is
    the one it inherits in that HLO, marked with a leading ``~``
    (``_hlo_paths``)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    with open(path, "rb") as f:
        meta = _event_metadata_stats(f.read())
    hlo = {name: _hlo_paths(st[HLO_STAT]) for name, st in
           meta.get(HLO_PLANE, {}).items() if HLO_STAT in st}
    out = []
    for plane in data.planes:
        dev = bool(devtrace.DEVICE.match(plane.name))
        if not (dev or plane.name.startswith("/host:")):
            continue
        own = meta.get(plane.name, {})
        modules = []
        for line in plane.lines:
            if dev and line.name == devtrace.MODULES:
                modules = sorted((int(ev.start_ns), int(ev.end_ns), ev.name)
                                 for ev in line.events)
        for line in plane.lines:
            if dev and line.name not in (devtrace.OPS, devtrace.MODULES):
                continue
            ops = dev and line.name == devtrace.OPS
            for ev in line.events:
                start, name = int(ev.start_ns), ""
                if ops:
                    name = own.get(ev.name, {}).get(OP_NAME_STAT, "")
                    if not name:
                        i = bisect.bisect_right(modules, (start, 1 << 62)) - 1
                        if i >= 0 and start < modules[i][1]:
                            name = hlo.get(modules[i][2], {}).get(
                                ev.name.split(" ", 1)[0].lstrip("%"), "")
                out.append([plane.name, line.name, ev.name, start,
                            int(ev.duration_ns), str(name)])
    return out


def _hlo_paths(proto: bytes) -> dict[str, str]:
    """``{instruction name: op_name path}`` of one serialized ``HloProto``.

    An instruction with no op_name inherits one, marked ``~``, from the
    first of these that has one, until nothing changes: a fusion from its
    root; an instruction of a called computation (a loop body) from its
    caller; else from its first consumer, XLA having made it for that
    consumer (a relayout loop feeding a gather); else from its first
    operand (xla/service/hlo.proto: HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2, .id
    5, .root_id 6; HloInstructionProto.name 1, .opcode 2, .metadata 7, .id
    35, .operand_ids 36, .called_computation_ids 38; OpMetadata.op_name
    2)."""
    module = next((v for k, v in _fields(memoryview(proto)) if k == 1), b"")
    ins, comp_of, roots = {}, {}, {}
    for k, comp in _fields(module):
        if k != 3:
            continue
        cid = root = 0                 # proto3 leaves a 0 out
        ids = []
        for f, v in _fields(comp):
            if f == 5:
                cid = v
            elif f == 6:
                root = v
            elif f == 2:
                rec = {"id": 0, "name": "", "op": "", "path": "",
                       "args": [], "calls": []}
                for g, w in _fields(v):
                    if g == 1:
                        rec["name"] = bytes(w).decode()
                    elif g == 2:
                        rec["op"] = bytes(w).decode()
                    elif g == 7:
                        rec["path"] = bytes(dict(_fields(w)).get(
                            2, b"")).decode()
                    elif g == 35:
                        rec["id"] = w
                    elif g in (36, 38):
                        rec["args" if g == 36 else "calls"] += _ints(w)
                ins[rec["id"]] = rec
                ids.append(rec["id"])
        roots[cid] = root
        comp_of.update((i, cid) for i in ids)
    callers, users = {}, collections.defaultdict(list)
    for i, r in sorted(ins.items()):
        for c in r["calls"]:
            callers.setdefault(c, i)
        for a in r["args"]:
            users[a].append(i)
    path = {i: r["path"] for i, r in ins.items() if r["path"]}
    changed = True
    while changed:
        changed = False
        for i, r in sorted(ins.items()):
            if i in path:
                continue
            fused = [roots.get(c) for c in r["calls"]] \
                if r["op"] == "fusion" else []
            for j in fused + [callers.get(comp_of[i])] + users[i] \
                    + r["args"]:
                if j in path:
                    path[i] = "~" + path[j].lstrip("~")
                    changed = True
                    break
    return {ins[i]["name"]: p for i, p in path.items()}


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _ints(v) -> list[int]:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _fields(b):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, bytes for a length-delimited field, None for a fixed one."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _event_metadata_stats(space: bytes) -> dict:
    """``{plane: {event name: {stat: value}}}`` of the ``tf_op`` stats on
    the device planes' event metadata and the HLO protos on the metadata
    plane, which ``ProfileData`` does not expose (tsl/profiler/protobuf/
    xplane.proto: XSpace.planes 1; XPlane.name 2, .event_metadata 4,
    .stat_metadata 5; map entries key 1, value 2; XEventMetadata.name 2,
    .stats 5; XStat.metadata_id 1, .str_value 5, .bytes_value 6,
    .ref_value 7; XStatMetadata.id 1, .name 2).  A ``ref_value`` names the
    stat metadata whose name is the string."""
    out = {}
    for num, plane in _fields(memoryview(space)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if not (devtrace.DEVICE.match(name) or name == HLO_PLANE):
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:
                m = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[m.get(1, 0)] = bytes(m.get(2, b"")).decode()
        wanted = {i for i, s in stat_names.items()
                  if s in (OP_NAME_STAT, HLO_STAT)}
        events = {}
        for k, entry in fields:
            if k != 4 or not wanted:
                continue
            ev_name, stats = "", {}
            for f, v in _fields(dict(_fields(entry)).get(2, b"")):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        stats[stat_names[st[1]]] = (
                            bytes(st[5]).decode() if 5 in st else
                            bytes(st[6]) if 6 in st else
                            stat_names.get(st.get(7), ""))
            if stats:
                events[ev_name] = stats
        out[name] = events
    return out


# ---------------------------------------------------------------------------
# the split
# ---------------------------------------------------------------------------

def _exclusive(intervals) -> collections.Counter:
    """Nanoseconds by scope of one device's ``(start, end, scope)`` op
    intervals, each instant given to the op that started last among those
    running (the longer first where two start together)."""
    out = collections.Counter()
    stack: list[tuple[int, str]] = []       # (end, scope), by start
    t = None

    def advance(to):
        nonlocal t
        while stack and t < to:
            end, scope = stack[-1]
            if end > t:
                step = min(end, to)
                out[scope] += step - t
                t = step
            if end <= t:
                stack.pop()
        t = to

    for s, e, scope in sorted(intervals, key=lambda x: (x[0], -x[1])):
        if t is None:
            t = s
        advance(s)
        stack.append((e, scope))
    if stack:
        advance(max(e for e, _ in stack))
    return out


def reduce(events, window=None) -> dict:
    """Seconds of each stage scope, ``unscoped`` apart, the seconds a stage
    holds by an inherited path (``inherited_s``), and idle seconds by the
    innermost driver span open at each gap (``devtrace._attribute`` over
    the program's own spans)."""
    w0, w1 = window if window is not None else devtrace.window_of(
        e[:5] for e in events)
    per_dev = collections.defaultdict(list)
    for p, l, n, s, d, op in events:
        if l != devtrace.OPS or not devtrace.DEVICE.match(p):
            continue
        a, b = devtrace._clip(s, s + d, w0, w1)
        if b > a:
            per_dev[p].append((a, b, (stage_of(op), op.startswith("~"))))
    if not per_dev:
        raise ValueError("no device op ran inside the traced window")
    scopes, inherited, busy, gaps = collections.Counter(), 0, 0, []
    for ivs in per_dev.values():
        for (scope, inh), ns in _exclusive(ivs).items():
            scopes[scope] += ns
            inherited += ns if inh and scope != UNSCOPED else 0
        merged = devtrace._union([(a, b) for a, b, _ in ivs])
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = [(s, s + d, n) for p, l, n, s, d, *_ in events
            if not devtrace.DEVICE.match(p) and n in HOST_SPANS]
    idle = devtrace._attribute(gaps, host)
    scale = 1e-9 / len(per_dev)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy * scale,
            "devices": len(per_dev),
            "scopes": {k: v * scale for k, v in scopes.items()},
            "inherited_s": inherited * scale,
            "idle_by_span": {k: v / len(per_dev) for k, v in idle.items()}}


# ---------------------------------------------------------------------------
# what the metric readers call
# ---------------------------------------------------------------------------

def _window_split(ctx):
    """The split of this run's window trace (``ctx.trace_file``, the file
    the harness reduced into ``ctx.fused``), kept on ``ctx`` for the other
    readers; None where there is no trace or its window is not the one
    ``ctx.fused`` was reduced from."""
    if not hasattr(ctx, "scope_split"):
        ctx.scope_split = None
        if ctx.trace_file:
            try:
                split = reduce(load(ctx.trace_file))
            except ValueError:          # no device op in the window
                split = None
            if split and split["window_s"] == ctx.fused["window_s"]:
                ctx.scope_split = split
                _log(split, ctx.rounds)
    return ctx.scope_split


def _log(split: dict, rounds: int) -> None:
    """Coverage: device s/round of every scope next to busy, and the share
    of busy that a stage holds by the op's own path; idle s/round by the
    program's spans."""
    per = lambda d: {k: v / rounds for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])}
    busy = split["busy_s"]
    own = busy - split["scopes"].get(UNSCOPED, 0.0) - split["inherited_s"]
    print("bench: scopes (device s/round): " + json.dumps(
        {"busy": busy / rounds, **per(split["scopes"]),
         "inherited": split["inherited_s"] / rounds,
         "own_path_share": own / busy}), file=sys.stderr, flush=True)
    print("bench: idle by program span (s/round): " + json.dumps(
        per(split["idle_by_span"])), file=sys.stderr, flush=True)


def ms_per_round(ctx, scope: str):
    """Device milliseconds a round spends in ``scope``; None where the
    window's trace holds no op of it."""
    split = _window_split(ctx)
    if split is None or not split["scopes"].get(scope):
        return None
    return 1e3 * split["scopes"][scope] / ctx.rounds
