"""Cloud-FS read at volume (BASELINE stretch).

Serves the config-1 corpus through a LOOPBACK S3-compatible server
(disk-backed, Range-capable — zero egress) and measures:

  - the raw S3 read-stream rate (signed range-GETs through
    ``open_stream``, the analog of the reference's CURL ReadStream,
    /root/reference/src/io/s3_filesys.cc:422-650), and
  - the full remote parse pipeline: ``create_parser`` over the s3:// URI
    routes NativeFeedParser — Python range-reads feed the C++ chunk
    parser push-mode — which is what a TPU-VM pulling training data from
    object storage actually runs.

The emitted metric is the remote pipeline MB/s; vs_baseline is the local
single-threaded parse of the same bytes (the suite-wide CPU reference),
so the ratio reads "what does remoteness cost end-to-end". The part-loop
invariant (4 byte-range partitions, no loss/duplication) doubles as the
range-GET-restart validation under volume.

Note the asterisk on absolute numbers: server, client, and parser share
this host's ONE core, so the loopback rate understates what a real
NIC-attached object store sustains; the leg exists to validate the
client under GB volume and record the pipeline's remote-path overhead.
"""

from __future__ import annotations

import http.server
import os
import threading
import urllib.parse

from _common import CACHE_DIR, TARGET_MB, emit, log, synth_text, timed_stats

NUM_COL = 28
_ROWS_PER_BLOCK = 2000
_block_cache: dict = {}


def _line(i: int) -> str:
    """bench.py's HIGGS-like shape, generated 2000 rows per rng
    construction (synth_text consumes rows sequentially, so the one-block
    cache always hits) — a per-row default_rng would pay SeedSequence
    setup ~3.7M times at GB scale."""
    import numpy as np

    b = i // _ROWS_PER_BLOCK
    rows = _block_cache.get(b)
    if rows is None:
        _block_cache.clear()
        rng = np.random.default_rng(b)
        vals = rng.standard_normal((_ROWS_PER_BLOCK, NUM_COL))
        rows = [
            f"{(b * _ROWS_PER_BLOCK + r) % 2} "
            + " ".join(f"{j}:{vals[r, j]:.6f}" for j in range(NUM_COL))
            + "\n"
            for r in range(_ROWS_PER_BLOCK)
        ]
        _block_cache[b] = rows
    return rows[i % _ROWS_PER_BLOCK]


class _DiskS3Handler(http.server.BaseHTTPRequestHandler):
    """Minimal S3 surface over one disk file: HEAD (size), list-type=2,
    GET with Range — served straight from disk in 4 MB writes so a GB
    object never sits in memory."""

    path_on_disk = ""
    key = "corpus.libsvm"
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):  # quiet
        pass

    def _size(self) -> int:
        return os.path.getsize(self.path_on_disk)

    def do_HEAD(self):
        if self.key not in self.path:
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Length", str(self._size()))
        self.end_headers()

    def do_GET(self):
        parsed = urllib.parse.urlparse(self.path)
        query = dict(urllib.parse.parse_qsl(parsed.query,
                                            keep_blank_values=True))
        if query.get("list-type") == "2":
            body = (
                '<?xml version="1.0"?><ListBucketResult>'
                f"<Contents><Key>{self.key}</Key>"
                f"<Size>{self._size()}</Size></Contents>"
                "</ListBucketResult>").encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if self.key not in parsed.path:
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        size = self._size()
        lo, hi = 0, size - 1
        rng = self.headers.get("Range")
        if rng:
            spec = rng.split("=")[1]
            a, b = spec.split("-")
            lo = int(a)
            hi = int(b) if b else size - 1
            if lo >= size:
                self.send_response(416)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            hi = min(hi, size - 1)
            self.send_response(206)
            self.send_header("Content-Range", f"bytes {lo}-{hi}/{size}")
        else:
            self.send_response(200)
        length = hi - lo + 1
        self.send_header("Content-Length", str(length))
        self.end_headers()
        with open(self.path_on_disk, "rb") as f:
            f.seek(lo)
            left = length
            while left > 0:
                chunk = f.read(min(4 << 20, left))
                if not chunk:
                    break
                try:
                    self.wfile.write(chunk)
                except (BrokenPipeError, ConnectionResetError):
                    return  # client restarted the range — normal
                left -= len(chunk)


def run() -> None:
    path = synth_text(os.path.join(CACHE_DIR, "higgs_like.libsvm"), _line)
    size_mb = os.path.getsize(path) / 2**20
    _DiskS3Handler.path_on_disk = path

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _DiskS3Handler)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    os.environ["S3_ENDPOINT"] = f"http://127.0.0.1:{port}"
    os.environ["S3_ACCESS_KEY_ID"] = "benchkey"
    os.environ["S3_SECRET_ACCESS_KEY"] = "benchsecret"
    uri = f"s3://bench/{_DiskS3Handler.key}"

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.io import open_stream

    try:
        # raw signed range-GET stream (ReadStream analog), 4 MB reads
        def raw_read():
            n = 0
            with open_stream(uri) as f:
                while True:
                    buf = f.read(4 << 20)
                    if not buf:
                        break
                    n += len(buf)
            assert n == os.path.getsize(path), (n, os.path.getsize(path))

        raw_best, raw_med, _ = timed_stats(raw_read, reps=3)
        log(f"raw s3 read-stream: {size_mb / raw_best:.1f} MB/s best, "
            f"{size_mb / raw_med:.1f} median")

        # part-loop invariant under volume: 4 byte-range partitions through
        # the remote pipeline == 1 local pass (range-GET restart per part)
        def count_rows(u, nparts, threaded):
            rows = 0
            for part in range(nparts):
                p = create_parser(u, part, nparts, "libsvm",
                                  threaded=threaded)
                rows += sum(len(b) for b in p)
                p.close()
            return rows

        n_remote = count_rows(uri, 4, True)
        log(f"4-part remote read OK ({n_remote} rows)")

        # the remote pipeline (NativeFeedParser push-mode); row counts must
        # agree across every remote pass
        def remote_parse():
            p = create_parser(uri, 0, 1, "libsvm", threaded=True)
            rows = sum(len(b) for b in p)
            p.close()
            assert rows == n_remote, (rows, n_remote)

        t_best, t_med, times = timed_stats(remote_parse, reps=3)
        log(f"remote parse pipeline: {size_mb / t_best:.1f} MB/s best, "
            f"{size_mb / t_med:.1f} median")

        # suite-wide CPU reference: local single-threaded parse. Its row
        # count doubles as the remote-vs-local half of the part-loop
        # invariant — no extra counting pass (the timed work includes the
        # count either way).
        local_rows = []

        def local_parse():
            p = create_parser(path, 0, 1, "libsvm", threaded=False)
            local_rows.append(sum(len(b) for b in p))
            p.close()

        base_best, base_med, _ = timed_stats(local_parse, reps=3)
        log(f"local single-thread parse: {size_mb / base_best:.1f} MB/s")
        assert all(n == n_remote for n in local_rows), (local_rows, n_remote)
        log(f"part-loop invariant OK ({n_remote} rows, 4 remote byte-range "
            f"parts == 1 local pass)")

        emit("cloud_read_mb_per_sec", size_mb / t_best, "MB/s",
             size_mb / base_best,
             median=size_mb / t_med,
             median_vs_baseline=base_med / t_med,
             spread=[round(size_mb / max(times), 2),
                     round(size_mb / min(times), 2)],
             raw_stream_mb_per_sec=round(size_mb / raw_best, 2),
             reps=3)
    finally:
        server.shutdown()
        server.server_close()


if __name__ == "__main__":
    run()
