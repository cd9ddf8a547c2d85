"""Cut an ``*.xplane.pb`` down to a small recorded trace for the tests:

    python3 -m cellbench.tools.trim_trace IN.xplane.pb OUT.xplane.pb FROM_S TO_S

keeps the TPU device planes' ``Steps`` / ``XLA Modules`` / ``XLA Ops`` lines
and the host plane's threads that carry ``cellbench:`` spans (those spans
only), each cut to the events that start between ``FROM_S`` and ``TO_S``
seconds after the first device operation, and drops metadata nothing left
refers to. Works on the protobuf wire format directly (XSpace/XPlane/
XLine/XEvent field numbers of tsl/profiler/protobuf/xplane.proto); no
generated bindings are installed here.
"""

from __future__ import annotations

import re
import sys

KEEP_DEVICE_LINES = {"Steps", "XLA Modules", "XLA Ops"}
DEVICE = re.compile(r"^/device:TPU:\d+$")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _enc_varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def fields(buf):
    """``[(field number, wire type, value)]``; value is an int (varint,
    fixed) or bytes (length-delimited)."""
    i, out = 0, []
    while i < len(buf):
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            val, i = bytes(buf[i:i + n]), i + n
        elif wt == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wt == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wt}")
        out.append((num, wt, val))
    return out


def encode(items):
    out = bytearray()
    for num, wt, val in items:
        out += _enc_varint(num << 3 | wt)
        if wt == 0:
            out += _enc_varint(val)
        elif wt == 2:
            out += _enc_varint(len(val)) + val
        else:
            out += val
    return bytes(out)


def _get(items, num, default=None):
    for n, _, v in items:
        if n == num:
            return v
    return default


def _event_names(plane_items):
    names = {}
    for n, _, v in plane_items:
        if n == 4:  # map entry: 1 key, 2 XEventMetadata{1 id, 2 name}
            entry = fields(v)
            meta = fields(_get(entry, 2, b""))
            names[_get(entry, 1, 0)] = _get(meta, 2, b"").decode()
    return names


def _line_events(line_items):
    base_ps = _get(line_items, 3, 0) * 1000
    for n, _, v in line_items:
        if n == 4:
            ev = fields(v)
            yield v, _get(ev, 1, 0), base_ps + _get(ev, 2, 0)


def trim(data: bytes, from_s: float, to_s: float) -> bytes:
    planes = [fields(v) for n, _, v in fields(data) if n == 1]
    first = None
    for plane in planes:
        if DEVICE.match(_get(plane, 2, b"").decode()):
            for n, _, v in plane:
                if n == 3 and _get(fields(v), 2, b"").decode() == "XLA Ops":
                    for _, _, t in _line_events(fields(v)):
                        first = t if first is None else min(first, t)
    if first is None:
        raise ValueError("no XLA Ops line on a TPU device plane")
    lo, hi = first + int(from_s * 1e12), first + int(to_s * 1e12)
    out = []
    for plane in planes:
        pname = _get(plane, 2, b"").decode()
        device = bool(DEVICE.match(pname))
        if not device and pname != "/host:CPU":
            continue
        names = _event_names(plane)
        used, kept_lines = set(), []
        for n, wt, v in plane:
            if n != 3:
                continue
            line = fields(v)
            lname = _get(line, 2, b"").decode()
            if device and lname not in KEEP_DEVICE_LINES:
                continue
            keep = [(raw, mid) for raw, mid, t in _line_events(line)
                    if lo <= t < hi and (device or names.get(mid, "")
                                         .startswith("cellbench:"))]
            if not keep:
                continue
            used |= {mid for _, mid in keep}
            kept_lines.append(encode(
                [f for f in line if f[0] != 4]
                + [(4, 2, raw) for raw, _ in keep]))
        if not kept_lines:
            continue
        new = []
        for n, wt, v in plane:
            if n == 3:
                continue
            if n == 4 and _get(fields(v), 1, 0) not in used:
                continue
            new.append((n, wt, v))
        new += [(3, 2, ln) for ln in kept_lines]
        out.append((1, 2, encode(new)))
    return encode(out)


if __name__ == "__main__":
    src, dst, a, b = sys.argv[1:5]
    with open(src, "rb") as f:
        small = trim(f.read(), float(a), float(b))
    with open(dst, "wb") as f:
        f.write(small)
    print(f"{dst}: {len(small)} bytes")
