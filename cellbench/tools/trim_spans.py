"""``trim_trace`` for a trace that holds the program's own spans:

    python3 -m cellbench.tools.trim_spans IN.xplane.pb OUT.xplane.pb FROM_S TO_S [PREFIX ...]

keeps what ``cellbench.tools.trim_trace`` keeps, but of the host plane the
spans whose names start with any ``PREFIX`` (default ``cellbench:`` and
``dmlc_tpu:``), on every thread that carries one: the program's stage spans
come from its producer and pool threads too.
"""

from __future__ import annotations

import sys

from cellbench.tools.trim_trace import (DEVICE, KEEP_DEVICE_LINES, _event_names,
                                        _get, _line_events, encode, fields)

PREFIXES = ("cellbench:", "dmlc_tpu:")


def trim(data: bytes, from_s: float, to_s: float,
         prefixes=PREFIXES) -> bytes:
    planes = [fields(v) for n, _, v in fields(data) if n == 1]
    starts = [t for plane in planes
              if DEVICE.match(_get(plane, 2, b"").decode())
              for n, _, v in plane
              if n == 3 and _get(fields(v), 2, b"").decode() == "XLA Ops"
              for _, _, t in _line_events(fields(v))]
    if not starts:
        raise ValueError("no XLA Ops line on a TPU device plane")
    lo, hi = min(starts) + int(from_s * 1e12), min(starts) + int(to_s * 1e12)
    out = []
    for plane in planes:
        pname = _get(plane, 2, b"").decode()
        device = bool(DEVICE.match(pname))
        if not device and pname != "/host:CPU":
            continue
        names = _event_names(plane)
        used, kept_lines = set(), []
        for n, _, v in plane:
            if n != 3:
                continue
            line = fields(v)
            if device and _get(line, 2, b"").decode() not in KEEP_DEVICE_LINES:
                continue
            keep = [(raw, mid) for raw, mid, t in _line_events(line)
                    if lo <= t < hi and (device or names.get(mid, "")
                                         .startswith(tuple(prefixes)))]
            if keep:
                used |= {mid for _, mid in keep}
                kept_lines.append(encode([f for f in line if f[0] != 4]
                                         + [(4, 2, raw) for raw, _ in keep]))
        if kept_lines:
            out.append((1, 2, encode(
                [f for f in plane if f[0] != 3
                 and not (f[0] == 4 and _get(fields(f[2]), 1, 0) not in used)]
                + [(3, 2, ln) for ln in kept_lines])))
    return encode(out)


if __name__ == "__main__":
    src, dst, a, b = sys.argv[1:5]
    with open(src, "rb") as f:
        small = trim(f.read(), float(a), float(b),
                     tuple(sys.argv[5:]) or PREFIXES)
    with open(dst, "wb") as f:
        f.write(small)
    print(f"{dst}: {len(small)} bytes")
