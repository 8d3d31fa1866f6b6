#!/usr/bin/env python3
"""Cut a kept ``.xplane.pb`` down to a small recorded trace for these tests:

    python3 benchmark/tests/cut_trace.py in.xplane.pb[.gz] out.xplane.pb.gz \
        <lo_ns> <hi_ns> [--hlo]

Kept: the device planes' ``XLA Ops`` and ``XLA Modules`` lines and the host
plane's thread lines, each cut to the events inside [lo, hi] on the trace's
clock (a host event that crosses an edge is clipped to it, so
``bench.trace_window`` becomes the new window; a device event that crosses
one is dropped, so choose edges where the device is idle), and the names
of the events that are left. Gone: per-event stats, every other line and
plane. With ``--hlo`` the ``/host:metadata`` plane stays, each program's
HLO proto stripped to what ``program_trace.instruction_scopes`` reads: the
instructions' names, ``op_name``, ids, operands and called computations.
"""

from __future__ import annotations

import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.program_trace import _fields, _map_value  # noqa: E402

DEVICE_LINES = ("XLA Ops", "XLA Modules")


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _put(no: int, val) -> bytes:
    if isinstance(val, int):
        return _varint(no << 3) + _varint(val)
    val = bytes(val)
    return _varint(no << 3 | 2) + _varint(len(val)) + val


def _keep(msg, numbers) -> bytes:
    return b"".join(_put(no, val) for no, val in _fields(msg)
                    if no in numbers)


def _cut_line(line, lo, hi, clip, used):
    """The line with its events inside [lo, hi], or None if none is."""
    head, events, t0 = b"", [], 0
    for no, val in _fields(line):
        if no == 4:
            events.append(val)
        elif no in (1, 2, 3, 10, 11):
            head += _put(no, val)
            if no == 3:
                t0 = val
    kept = b""
    for ev in events:
        f = dict(_fields(ev))
        s = t0 * 1000 + f.get(2, 0)          # picoseconds
        e = s + f.get(3, 0)
        if e <= lo * 1000 or s >= hi * 1000:
            continue
        if s < lo * 1000 or e > hi * 1000:
            if not clip:
                continue
            s, e = max(s, lo * 1000), min(e, hi * 1000)
        used.add(f[1])
        kept += _put(4, _put(1, f[1]) + _put(2, s - t0 * 1000)
                     + _put(3, e - s))
    return head + kept if kept else None


def _strip_hlo(proto) -> bytes:
    out = b""
    for no, module in _fields(proto):
        if no != 1:
            continue
        mod = b""
        for mno, val in _fields(module):
            if mno == 1:
                mod += _put(1, val)
            elif mno == 3:
                comp = b""
                for cno, cval in _fields(val):
                    if cno in (1, 5):
                        comp += _put(cno, cval)
                    elif cno == 2:
                        ins = b""
                        for ino, ival in _fields(cval):
                            if ino in (1, 2, 35, 36, 38):
                                ins += _put(ino, ival)
                            elif ino == 7:
                                ins += _put(7, _keep(ival, (2,)))
                        comp += _put(2, ins)
                mod += _put(3, comp)
        out += _put(1, mod)
    return out


def _plane_name(plane) -> str:
    for no, val in _fields(plane):
        if no == 2:
            return bytes(val).decode()
    return ""


def _cut_plane(plane, name: str, lo: int, hi: int, names: set) -> bytes:
    """A device or host plane cut to [lo, hi]; ``names`` collects the
    names of the events that are left."""
    device = name.startswith("/device:")
    used, body, metas = set(), _put(2, name.encode()), []
    for no, val in _fields(plane):
        if no == 1:
            body += _put(1, val)
        elif no == 4:
            metas.append(dict(_fields(_map_value(val))))
        elif no == 3:
            lname = _plane_name(val)      # a line's name is its field 2 too
            if device and lname not in DEVICE_LINES:
                continue
            line = _cut_line(val, lo, hi, not device, used)
            if line is not None:
                body += _put(3, line)
    for meta in metas:
        if meta.get(1) in used:
            ev_name = meta.get(2, b"")
            body += _put(4, _put(1, meta[1]) + _put(
                2, _put(1, meta[1]) + _put(2, ev_name)))
            names.add(bytes(ev_name).decode())
    return body


def _metadata_plane(plane, programs: set) -> bytes:
    """``/host:metadata`` with the stripped HLO proto of ``programs``."""
    body = _put(2, b"/host:metadata")
    for no, val in _fields(plane):
        if no == 5:
            body += _put(5, val)
        elif no == 4:
            meta = dict(_fields(_map_value(val)))
            if bytes(meta[2]).decode() not in programs:
                continue
            stat = dict(_fields(meta[5]))
            stat[6] = _strip_hlo(stat[6])
            entry = _put(1, meta[1]) + _put(2, meta[2]) + _put(
                5, b"".join(_put(k, v) for k, v in stat.items()))
            body += _put(4, _put(1, meta[1]) + _put(2, entry))
    return body


def cut(data: bytes, lo: int, hi: int, hlo: bool) -> bytes:
    planes = [(val, _plane_name(val))
              for no, val in _fields(memoryview(data)) if no == 1]
    out, names = b"", set()
    for plane, name in planes:
        if name.startswith("/device:TPU:") or name == "/host:CPU":
            out += _put(1, _cut_plane(plane, name, lo, hi, names))
    if hlo:     # the programs that ran in the window are among the names
        for plane, name in planes:
            if name == "/host:metadata":
                out += _put(1, _metadata_plane(plane, names))
    return out


def main(argv) -> int:
    hlo = "--hlo" in argv
    src, dst, lo, hi = [a for a in argv if a != "--hlo"]
    opener = gzip.open if src.endswith(".gz") else open
    with opener(src, "rb") as f:
        data = f.read()
    small = cut(data, int(lo), int(hi), hlo)
    with gzip.GzipFile(dst, "wb", mtime=0) as f:
        f.write(small)
    print(f"{len(data)} -> {len(small)} bytes, {os.path.getsize(dst)} "
          f"gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
