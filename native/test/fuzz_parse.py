"""Crash-safety fuzz for the native parse C ABI.

Feeds mutated (byte-flip / delete / insert) variants of valid libsvm /
csv / libfm / RecordIO seeds into every native parse entry point and
asserts the process survives — parse errors are expected and fine; a
SIGSEGV/abort is the failure this hunts. The text scanners and the
RecordIO frame walker read length fields and delimiters straight from
untrusted bytes, which is exactly the surface a mutation fuzz stresses
(the reference's parsers carry the same risk class but no fuzz harness;
its sanitizer CI runs only fixed corpora, scripts/travis).

Runs in-process (a crash kills the run — run it via `make fuzz`, which
wraps it in a subprocess and checks the exit code). Iterations via
DMLC_FUZZ_ITERS (default 2000, ~15 s on the dev host; r5 validation ran
8000 per group clean).
"""

from __future__ import annotations


import os
import random
import struct
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

from dmlc_tpu import native  # noqa: E402

ITERS = int(os.environ.get("DMLC_FUZZ_ITERS", "2000"))


def mutate(rng: random.Random, b: bytes) -> bytes:
    out = bytearray(b * rng.randint(1, 3))
    for _ in range(rng.randint(1, 16)):
        if not out:
            break
        op = rng.randint(0, 2)
        i = rng.randrange(len(out))
        if op == 0:
            out[i] = rng.randrange(256)
        elif op == 1:
            del out[i]
        else:
            out.insert(i, rng.randrange(256))
    return bytes(out)


def main() -> int:
    lib = native._load()  # signatures come from _declare (one ABI source)
    if lib is None:
        print("native core unavailable; nothing to fuzz")
        return 0
    rng = random.Random(int(os.environ.get("DMLC_FUZZ_SEED", "1234")))
    magic = struct.pack("<I", 0xCED7230A)
    seeds = [
        b"1 0:1.5 3:2.5\n0 1:0.5\n1 qid:3 2:3.0 4:4.5\n",
        b"1,2.5,3\n4,5.5,6\n",
        b"1,16777217,2147483647\n0,-2147483648,7\n",
        b"1,68fd1e64,,-1\n0,,,\r\n",
        b"1 0:10:1 1:20:1\n0 2:30:0.5\n",
        b"# comment\n1:2 label\n",
        magic + struct.pack("<I", 8) + b"payload1",
        magic + struct.pack("<I", (1 << 29) | 12) + b"x" * 12,  # multipart
    ]
    for it in range(ITERS):
        data = mutate(rng, rng.choice(seeds))
        try:
            native.parse_libsvm(data, nthread=2)
        except Exception:  # noqa: BLE001 - parse errors are the happy path
            pass
        for cells in ("float32", "int32", "int64"):
            try:
                native.parse_csv(data, dtype=cells)
            except Exception:  # noqa: BLE001
                pass
        for cells in ("int32", "int64"):
            try:
                native.parse_csv_hashed(
                    data, rng.choice([1, 97, 2 ** 31 - 1]), dtype=cells,
                    label_column=rng.randint(-1, 3),
                    weight_column=rng.randint(-1, 3))
            except Exception:  # noqa: BLE001
                pass
        try:
            native.parse_libfm(data, nthread=2)
        except Exception:  # noqa: BLE001
            pass
        try:
            native.parse_libsvm_dense(data, 8, nthread=2)
        except Exception:  # noqa: BLE001
            pass
        try:
            native.recordio_extract(data)
        except Exception:  # noqa: BLE001
            pass
        r = lib.dmlc_parse_csv_split(data, len(data), 2, b",",
                                     rng.randint(-1, 6), rng.randint(-1, 6))
        if r:
            lib.dmlc_free_csv_split(r)
        for fmt, nc in ((3, 1000), (0, 50)):
            r = lib.dmlc_parse_coo(data, len(data), 2, 0, fmt, nc,
                                   rng.choice([0, 4]), rng.choice([0, 8]),
                                   rng.randint(0, 1), rng.randint(0, 1))
            if r:
                lib.dmlc_free_coo(r)
    print(f"fuzz_parse: {ITERS} iterations x 9 entry points, no crash")
    return 0


if __name__ == "__main__":
    sys.exit(main())
