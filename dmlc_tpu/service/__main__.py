"""The data service's components as OS processes.

    python3 -m dmlc_tpu.service dispatcher --uri URI --num-parts N [--parser JSON]
    python3 -m dmlc_tpu.service worker DISPATCHER_ADDRESS [--frame-store-bytes N]

One :class:`~dmlc_tpu.service.dispatcher.Dispatcher` or one
:class:`~dmlc_tpu.service.worker.ParseWorker` per process, built from the
arguments their constructors take (docs/service.md "Deploying"). When the
component listens, the process prints one line to its standard output,

    ready dispatcher 127.0.0.1:40123
    ready worker 127.0.0.1:40125

(the address clients connect to; a worker's is also its id at the
dispatcher), and then serves until SIGTERM or SIGINT: it closes its
listener and every connection and exits 0. A trainer connects with
``ServiceParser(<dispatcher address>)``.

Neither component imports ``jax``: a parse worker beside a trainer must
never load the accelerator's runtime, which one process at a time may
hold. Logging goes to the standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
from typing import List, Optional


def _arguments(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="python3 -m dmlc_tpu.service",
        description="Run one component of the RowBlock data service.")
    sub = ap.add_subparsers(dest="component", required=True)
    d = sub.add_parser("dispatcher", help="the control plane: owns the "
                       "dataset and grants its parts to workers")
    d.add_argument("--uri", required=True, help="the corpus")
    d.add_argument("--num-parts", type=int, required=True,
                   help="InputSplit partitions the corpus is cut into")
    d.add_argument("--parser", default="{}", metavar="JSON",
                   help="create_parser() keywords every worker parses "
                        'with, e.g. \'{"format": "libfm"}\'')
    d.add_argument("--host", default="127.0.0.1")
    d.add_argument("--port", type=int, default=0,
                   help="0 (the default) lets the system choose")
    w = sub.add_parser("worker", help="one parse worker")
    w.add_argument("dispatcher", help="the dispatcher's host:port")
    w.add_argument("--host", default="127.0.0.1",
                   help="the interface the data listener binds")
    w.add_argument("--frame-store-bytes", type=int, default=None,
                   metavar="N", help="bound the frame store: take no new "
                   "part while it holds N bytes, evict served parts to "
                   "get back under it (default: keep every part)")
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    args = _arguments(argv)
    # the kernel hands a signal to any thread that does not block it
    # (numpy's pool, started when the package was imported, among them),
    # and Python runs a handler only once the main thread wakes: so the
    # main thread waits on the wake-up pipe, which the C-level handler
    # writes to whichever thread took the signal
    woken, wake = os.pipe()
    os.set_blocking(wake, False)
    signal.set_wakeup_fd(wake)
    for signum in (signal.SIGTERM, signal.SIGINT):
        signal.signal(signum, lambda *_: None)
    if args.component == "dispatcher":
        from dmlc_tpu.service.dispatcher import Dispatcher

        parser = json.loads(args.parser)
        if not isinstance(parser, dict):
            raise SystemExit("--parser takes a JSON object")
        component = Dispatcher(args.uri, args.num_parts, parser=parser,
                               host=args.host, port=args.port)
    else:
        from dmlc_tpu.service.worker import ParseWorker

        component = ParseWorker(args.dispatcher, host=args.host,
                                frame_store_bytes=args.frame_store_bytes)
    try:
        print(f"ready {args.component} {component.host}:{component.port}",
              flush=True)
        os.read(woken, 1)
    finally:
        component.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
