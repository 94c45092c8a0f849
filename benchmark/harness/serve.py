"""The ONE child of a run: the program's own server entry, plus a control
thread for what only the process that holds the chip can do.

    python benchmark/harness/serve.py --control-port P -- server --bind ...

The main thread calls ``pilosa_tpu.cli.main([...])``: the program's own
event loop, router, scheduler and engines, nothing of the benchmark's in
their way. The control thread answers one JSON line per connection on
127.0.0.1:P:

    {"cmd": "trace_start", "dir": D}   jax.profiler.start_trace(D)
    {"cmd": "trace_stop"}              jax.profiler.stop_trace()
    {"cmd": "memory"}                  peak_bytes_in_use of each local device

The program has no profiler hook and reports device memory only while a
stack is resident; both belong inside it (PERF.md, the tracing list).
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _handle(req: dict) -> dict:
    import jax  # the server has imported it long before the first request

    cmd = req.get("cmd")
    if cmd == "trace_start":
        # the device's timeline is what is read; Python frames and most
        # host events only make the trace large and the host slow
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(req["dir"], profiler_options=opts)
        return {"ok": True, "monotonic": time.monotonic()}
    if cmd == "trace_stop":
        at = time.monotonic()
        jax.profiler.stop_trace()
        return {"ok": True, "monotonic": at, "export_s": time.monotonic() - at}
    if cmd == "memory":
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
        return {"ok": True, "peak_bytes_in_use": peaks}
    return {"ok": False, "error": f"unknown command {cmd!r}"}


def _control(listener: socket.socket) -> None:
    while True:
        conn, _ = listener.accept()
        with conn:
            try:
                line = conn.makefile("rb").readline()
                reply = _handle(json.loads(line))
            except Exception as e:  # the boundary: report, keep serving
                reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            conn.sendall(json.dumps(reply).encode() + b"\n")


def main() -> int:
    argv = sys.argv[1:]
    if len(argv) < 4 or argv[0] != "--control-port" or argv[2] != "--":
        print(__doc__, file=sys.stderr)
        return 2
    listener = socket.socket()
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", int(argv[1])))
    listener.listen(4)
    threading.Thread(target=_control, args=(listener,), daemon=True).start()
    sys.path.insert(0, ROOT)
    from pilosa_tpu import cli

    return cli.main(argv[3:]) or 0


if __name__ == "__main__":
    sys.exit(main())
