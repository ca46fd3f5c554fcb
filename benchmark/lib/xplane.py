"""A reader of the profiler's ``.xplane.pb`` that needs nothing but the
standard library: the wire format of the few messages of
``xplane.proto`` (tsl/profiler/protobuf) that the reduction reads.

``jax.profiler.ProfileData`` gives events and their own stats, but not
the stats of an event's *metadata*, which is where the TPU's profiler
keeps what an operation is: its category, the ``op_name`` it was traced
under (the program's ``named_scope``), its HLO text.
"""
import struct


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message.  Length-delimited
    values come as memoryview slices, the others as ints."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError("wire type %d" % wt)
        yield num, wt, v


def _signed(x):
    return x - (1 << 64) if x >= 1 << 63 else x


def _stat(buf, stat_names):
    """(name, value) of one XStat; a ref_value is looked up."""
    name, value = None, None
    for num, wt, v in fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = val = None
    for num, wt, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


class Plane:
    """One XPlane: ``lines`` is [(line name, [(metadata id, start ns,
    duration ns)])], ``meta`` {metadata id: {"name": ..., stat: ...}}."""

    def __init__(self, buf):
        self.name = ""
        raw_lines, raw_meta, stat_names = [], [], {}
        for num, wt, v in fields(buf):
            if num == 2:
                self.name = bytes(v).decode()
            elif num == 3:
                raw_lines.append(v)
            elif num == 4:
                raw_meta.append(_map_entry(v)[1])
            elif num == 5:
                sid, sm = _map_entry(v)
                for n2, _, v2 in fields(sm):
                    if n2 == 2:
                        stat_names[sid] = bytes(v2).decode()
        self.meta = {}
        for m in raw_meta:
            mid, row = None, {}
            for num, wt, v in fields(m):
                if num == 1:
                    mid = v
                elif num == 2:
                    row["name"] = bytes(v).decode("utf-8", "replace")
                elif num == 4:
                    row["display_name"] = bytes(v).decode("utf-8", "replace")
                elif num == 5:
                    k, val = _stat(v, stat_names)
                    row[k] = val
            self.meta[mid] = row
        self.lines = []
        for ln in raw_lines:
            name, t0, events = "", 0, []
            for num, wt, v in fields(ln):
                if num == 2:
                    name = bytes(v).decode()
                elif num == 3:
                    t0 = v
                elif num == 4:
                    events.append(v)
            rows = []
            for ev in events:
                mid = off = dur = 0
                for num, wt, v in fields(ev):
                    if num == 1:
                        mid = v
                    elif num == 2:
                        off = v
                    elif num == 3:
                        dur = v
                rows.append((mid, t0 + off / 1000.0, dur / 1000.0))
            self.lines.append((name, rows))


def planes(path):
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [Plane(v) for num, wt, v in fields(buf) if num == 1]
