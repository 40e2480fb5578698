"""A minimal reader of a profiler capture (.xplane.pb, the XSpace protobuf
of tsl/profiler/protobuf/xplane.proto), in plain Python.

`jax.profiler.ProfileData` gives an event's own stats but not the stats of
its *metadata*, and on a TPU that is where the op name of a device
operation lives (`tf_op`, ending in the program's `jax.named_scope`
stack; looked at by hand, PR 24). Only the fields the reductions need are
decoded; everything else is skipped by its wire type. Checked against
`ProfileData` on the recorded captures (tests/test_hostspans.py).
"""

from __future__ import annotations

import struct


def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for
    varints and fixed-width fields, a memoryview for length-delimited."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            val = buf[pos:pos + size]
            pos += size
        elif wire == 1:
            val = struct.unpack_from("<Q", buf, pos)[0]
            pos += 8
        elif wire == 5:
            val = struct.unpack_from("<I", buf, pos)[0]
            pos += 4
        else:
            raise ValueError(f"xspace: wire type {wire} at byte {pos}")
        yield num, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """XStat -> (name, value). A ref_value names another stat's metadata."""
    name, value = None, None
    for num, wire, val in fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", val))[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _signed(val)
        elif num == 5:
            value = _text(val)
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            value = stat_names.get(val, str(val))
    return name, value


def _map_entry(buf):
    key, value = 0, b""
    for num, _, val in fields(buf):
        if num == 1:
            key = _signed(val)
        elif num == 2:
            value = val
    return key, value


def read(path: str) -> list:
    """[{name, stats, lines: [{name, events: [(name, start_ns, dur_ns,
    event stats, metadata stats)]}]}] of every plane of the file. Times as
    `ProfileData` gives them: line timestamp + offset, in ns."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _, plane_buf in fields(space):
        if num != 1:
            continue
        name, lines, event_md, stat_md, plane_stats = "", [], {}, {}, []
        for pnum, _, val in fields(plane_buf):
            if pnum == 2:
                name = _text(val)
            elif pnum == 3:
                lines.append(val)
            elif pnum == 4:
                key, md = _map_entry(val)
                event_md[key] = md
            elif pnum == 5:
                key, md = _map_entry(val)
                stat_md[key] = md
            elif pnum == 6:
                plane_stats.append(val)
        stat_names = {}
        for key, md in stat_md.items():
            for snum, _, val in fields(md):
                if snum == 2:
                    stat_names[key] = _text(val)
        metadata = {}
        for key, md in event_md.items():
            md_name, md_stats = "", {}
            for mnum, _, val in fields(md):
                if mnum == 2:
                    md_name = _text(val)
                elif mnum == 5:
                    sname, sval = _stat(val, stat_names)
                    md_stats[sname] = sval
            metadata[key] = (md_name, md_stats)
        out_lines = []
        for line_buf in lines:
            line_name, t0_ns, events = "", 0, []
            for lnum, _, val in fields(line_buf):
                if lnum == 2:
                    line_name = _text(val)
                elif lnum == 3:
                    t0_ns = _signed(val)
                elif lnum == 4:
                    events.append(val)
            decoded = []
            for ev in events:
                md_id, offset_ps, dur_ps, stats = 0, 0, 0, {}
                for enum, _, val in fields(ev):
                    if enum == 1:
                        md_id = _signed(val)
                    elif enum == 2:
                        offset_ps = _signed(val)
                    elif enum == 3:
                        dur_ps = _signed(val)
                    elif enum == 4:
                        sname, sval = _stat(val, stat_names)
                        stats[sname] = sval
                md_name, md_stats = metadata.get(md_id, ("", {}))
                # Whole nanoseconds, as ProfileData gives them.
                decoded.append((md_name, float(t0_ns + offset_ps // 1000),
                                float(dur_ps // 1000), stats, md_stats))
            out_lines.append({"name": line_name, "events": decoded})
        planes.append({"name": name, "lines": out_lines,
                       "stats": dict(_stat(s, stat_names)
                                     for s in plane_stats)})
    return planes
