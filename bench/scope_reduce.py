"""Map each XLA op of a profiler trace to the ``op_name`` path its HLO
instruction carries, and sum device self time by named stage.

The device plane of an ``.xplane.pb`` keeps, in its event metadata, a
``tf_op`` stat per op: ``<op_name>:<op type>``, where ``op_name`` is
the metadata of the HLO instruction, such as
``jit(device_anns)/while/body/round_route/jit(take_along_axis)/gather``.
A ``jax.named_scope`` in the program adds its name to that path and to
nothing else, so the path names the stage an op belongs to.
``jax.profiler.ProfileData`` does not expose metadata stats, so this
module reads them straight from the protobuf wire format, with no
dependency: only the device planes' ``event_metadata`` and
``stat_metadata`` maps are decoded; their lines of events are skipped.

Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``:
XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
.stat_metadata = 5 (map entries: key = 1, value = 2);
XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
XStat.metadata_id = 1, .str_value = 5, .ref_value = 7.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterator, List, Sequence, Tuple

import trace_reduce

OP_PATH_STAT = "tf_op"

# the search's named stages (``jax.named_scope``s of the program's
# ``core/device_search``), as the op paths carry them
STAGES = ("search_entry", "round_pick", "round_fetch", "round_results",
          "round_expand", "round_route", "round_candidates")


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varints, the
    bytes for length-delimited fields, None for fixed-width ones."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
            yield field, val
        elif wire == 2:
            n, pos = _varint(buf, pos)
            yield field, buf[pos:pos + n]
            pos += n
        elif wire == 1:
            pos += 8
            yield field, None
        elif wire == 5:
            pos += 4
            yield field, None
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _map_value(entry: bytes) -> bytes:
    for f, v in _fields(entry):
        if f == 2:
            return v
    return b""


def _plane_paths(plane: bytes) -> Tuple[str, Dict[str, str]]:
    name = ""
    events: List[bytes] = []
    stat_names: Dict[int, str] = {}
    for f, v in _fields(plane):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 4:
            events.append(_map_value(v))
        elif f == 5:
            sid, sname = 0, ""
            for g, w in _fields(_map_value(v)):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = w.decode("utf-8", "replace")
            stat_names[sid] = sname
    path_ids = {i for i, s in stat_names.items() if s == OP_PATH_STAT}
    paths: Dict[str, str] = {}
    if not trace_reduce.DEVICE_PLANE.match(name) or not path_ids:
        return name, paths
    for ev in events:
        ev_name, path = "", None
        for f, v in _fields(ev):
            if f == 2:
                ev_name = v.decode("utf-8", "replace")
            elif f == 5:
                stat = dict(_fields(v))
                if stat.get(1) in path_ids:
                    if 5 in stat:
                        path = stat[5].decode("utf-8", "replace")
                    elif 7 in stat:
                        path = stat_names.get(stat[7])
        if path:
            paths.setdefault(ev_name, path.rpartition(":")[0] or path)
    return name, paths


def load_op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """Per device plane (by name): event name -> ``op_name`` path of
    every op whose metadata carries one."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field == 1:
            name, paths = _plane_paths(plane)
            if paths:
                out[name] = paths
    return out


def stage_ns(summary: trace_reduce.TraceSummary,
             paths: Dict[str, Dict[str, str]],
             stages: Sequence[str]) -> List[Dict[str, float]]:
    """Per device of ``summary``: the window's op self time (ns) by
    stage, each op counted under the first component of its path that
    is one of ``stages``; ops under no stage are left out."""
    wanted = set(stages)
    out = []
    for dev in summary.devices:
        names = paths.get(dev.name, {})
        tot: Dict[str, float] = collections.defaultdict(float)
        for op, t in dev.self_ns.items():
            stage = next((c for c in names.get(op, "").split("/")
                          if c in wanted), None)
            if stage is not None:
                tot[stage] += t
        out.append(dict(tot))
    return out


def stage_ms(run, stages: Sequence[str], per_round: bool = True):
    """The largest over devices of the window's device self time (ms)
    under ``stages``, each op counted under the first of ``STAGES`` on
    its path, per block-search round that device ran (``per_round``)
    or per batch; None where no op is under them."""
    if run.trace is None or not run.op_paths or not run.batches:
        return None
    out = []
    by_device = stage_ns(run.trace, run.op_paths, STAGES)
    for d, by_stage in enumerate(by_device):
        if not any(s in by_stage for s in stages):
            continue
        count = (sum(b.device_rounds[d] for b in run.batches
                     if d < len(b.device_rounds))
                 if per_round else len(run.batches))
        if count:
            out.append(sum(by_stage.get(s, 0.0) for s in stages)
                       / 1e6 / count)
    return max(out) if out else None
