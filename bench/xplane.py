"""Read what ``jax.profiler.ProfileData`` leaves out of an ``.xplane.pb``:
the stats kept on each plane's event metadata.

A TPU trace keeps an op's scope path there, and not on the op's events:
the stat ``tf_op`` of the op's metadata holds its HLO ``op_name``
(``jit(traced)/vmap(di_link)/mul:``).  The metadata's name is the op's
HLO text, the same string ``ProfileData`` gives as the event's name, so an
event finds its stats by name.  This is a reader of the protobuf wire
format for the few fields it needs (``tsl/profiler/protobuf/xplane.proto``):

    XSpace.planes = 1
    XPlane: name = 2, event_metadata = 4 (map int64 -> XEventMetadata),
            stat_metadata = 5 (map int64 -> XStatMetadata)
    XEventMetadata: name = 2, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, str_value = 5, ref_value = 7
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple


def _varint(b: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def fields(b: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varints, a
    memoryview for length-delimited fields; fixed-width ones are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield num, v
        elif wire == 2:
            size, i = _varint(b, i)
            yield num, b[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")


def _map_entry(b: memoryview) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, v in fields(b):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _str(b) -> str:
    return bytes(b).decode("utf-8", errors="replace")


def metadata_stat(raw: bytes, stat: str) -> Dict[str, Dict[str, str]]:
    """``{plane name: {event metadata name: the string stat ``stat``}}``
    for every plane, leaving out metadata without that stat."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in fields(memoryview(raw)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in fields(plane):
            if pnum == 2:
                name = _str(v)
            elif pnum == 4:
                events.append(_map_entry(v)[1])
            elif pnum == 5:
                sid, meta = _map_entry(v)
                stat_names[sid] = next((_str(x) for n, x in fields(meta) if n == 2), "")
        ids = {sid for sid, sname in stat_names.items() if sname == stat}
        found: Dict[str, str] = {}
        if ids:
            for ev in events:
                ev_name, value = "", None
                for enum, v in fields(ev):
                    if enum == 2:
                        ev_name = _str(v)
                    elif enum == 5:
                        value = _stat_value(v, ids, stat_names) if value is None else value
                if value is not None:
                    found[ev_name] = value
        out[name] = found
    return out


def _stat_value(b: memoryview, ids, stat_names) -> object:
    """The string value of one XStat if its metadata id is in ``ids``."""
    sid, value = None, None
    for num, v in fields(b):
        if num == 1:
            sid = v
        elif num == 5:
            value = _str(v)
        elif num == 7:
            value = stat_names.get(v, "")
    return value if sid in ids else None
