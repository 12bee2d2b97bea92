"""Read the op names (``tf_op``) that a TPU trace keeps beside its ops.

``jax.profiler.ProfileData`` gives each device event its HLO text but not
the event's metadata, where the profiler stores the op's name path (the
``jax.named_scope`` stack, such as ``jit(joint)/encode/...``).  This reads
just that from the ``.xplane.pb`` file with a small protobuf wire decoder,
so it needs nothing beyond the standard library.

Fields (``tsl/profiler/protobuf/xplane.proto``): XSpace.planes = 1;
XPlane.name = 2, event_metadata = 4 (map: key 1, value 2),
stat_metadata = 5 (map: key 1, value 2); XEventMetadata.name = 2,
stats = 5; XStat.metadata_id = 1, str_value = 5, ref_value = 7;
XStatMetadata.name = 2.
"""

from __future__ import annotations

OP_NAME_STAT = "tf_op"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of each field: an int, or the bytes of a
    length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _map_value(entry: bytes):
    key = value = None
    for f, v in _fields(entry):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_names(path: str, plane_prefix: str = "/device:") -> dict:
    """``{op's HLO text: its op name path}`` for the ops of the planes
    whose name starts with ``plane_prefix``."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(_map_value(v)[1])
            elif f == 5:
                sid, md = _map_value(v)
                stat_names[sid] = next(
                    (x.decode() for g, x in _fields(md or b"") if g == 2), "")
        if not name.startswith(plane_prefix):
            continue
        for em in events:
            ev_name, op = "", None
            for f, v in _fields(em or b""):
                if f == 2:
                    ev_name = v.decode()
                elif f == 5:
                    sid, text, ref = None, None, None
                    for g, x in _fields(v):
                        if g == 1:
                            sid = x
                        elif g == 5:
                            text = x.decode()
                        elif g == 7:
                            ref = x
                    if stat_names.get(sid) == OP_NAME_STAT:
                        op = text if text is not None else stat_names.get(ref)
            if op:
                out[ev_name] = op
    return out
