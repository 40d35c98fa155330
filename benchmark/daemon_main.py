#!/usr/bin/env python3
"""Launcher of the daemon child for runs that must reach into the program
from outside: ``daemon_main.py [options] -- <seaweedfs_tpu arguments>``.

- ``--trace-dir D --control-port P``: wraps the calls into each layer in
  ``jax.profiler.TraceAnnotation`` spans and serves ``POST /start`` and
  ``POST /stop`` on 127.0.0.1:P, which start and stop the profiler in this
  process — only the process that holds the chip can trace it. No file of
  the program changes; spans inside the program are the ``tracing`` issue's.
- ``--control wrong-codec``: flips one bit of one coefficient of every
  matrix handed to the device matmul. The comparison that decides
  ``correct`` has to fail under it.
- ``--rehearsal``: the CPU rehearsal; the codec runs the Pallas kernel in
  interpret mode, so the launch accounting is the chip's.

Then it runs ``python -m seaweedfs_tpu <arguments>`` in this process.
"""

from __future__ import annotations

import argparse
import functools
import json
import runpy
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

# (module, class or None, attribute, span name)
SPANS = (
    ("seaweedfs_tpu.storage.store", "Store", "ec_encode_volume", "ec_encode_volume"),
    ("seaweedfs_tpu.storage.store", "Store", "_recover_interval", "_recover_interval"),
    ("seaweedfs_tpu.ec.encoder", None, "write_ec_files", "write_ec_files"),
    ("seaweedfs_tpu.ec.encoder", None, "rebuild_ec_files", "rebuild_ec_files"),
    ("seaweedfs_tpu.ec.codec", "Codec", "reconstruct", "reconstruct"),
    ("seaweedfs_tpu.ec.codec", "TpuCodec", "matmul", "matmul"),
    ("seaweedfs_tpu.ec.codec", "TpuCodec", "matmul_device", "matmul_device"),
    ("seaweedfs_tpu.ec.sharded", "MeshCodec", "matmul", "matmul"),
    ("seaweedfs_tpu.ec.sharded", "MeshCodec", "matmul_device", "matmul_device"),
    ("seaweedfs_tpu.server.volume_server", "VolumeServer", "_h_get", "handler"),
)


def _owner(module: str, cls: str | None):
    import importlib

    mod = importlib.import_module(module)
    return getattr(mod, cls) if cls else mod


def wrap_spans() -> None:
    from jax.profiler import TraceAnnotation

    for module, cls, attr, name in SPANS:
        owner = _owner(module, cls)
        inner = getattr(owner, attr)

        def spanned(*args, _inner=inner, _name=name, **kwargs):
            extra = {}
            if _name == "matmul_device":
                # the kernel's shape: rows x k matrix, k x n bytes
                matrix, data = args[1], args[2]
                extra = {"rows": int(matrix.shape[0]), "k": int(matrix.shape[1]),
                         "n": int(data.shape[1])}
            elif _name == "_recover_interval":
                extra = {"size": int(args[4])}
            with TraceAnnotation(_name, **extra):
                return _inner(*args, **kwargs)

        setattr(owner, attr, functools.wraps(inner)(spanned))


def break_codec() -> None:
    """One wrong coefficient in every device matmul."""
    for module, cls in (("seaweedfs_tpu.ec.codec", "TpuCodec"),
                        ("seaweedfs_tpu.ec.sharded", "MeshCodec")):
        owner = _owner(module, cls)
        inner = owner.matmul_device

        def wrong(self, matrix, data_dev, _inner=inner):
            altered = matrix.copy()
            altered[0, 0] ^= 1
            return _inner(self, altered, data_dev)

        owner.matmul_device = functools.wraps(inner)(wrong)


def interpret_kernels() -> None:
    """The rehearsal's codec: the Pallas kernel, interpreted on the CPU."""
    store = _owner("seaweedfs_tpu.storage.store", None)
    codec = _owner("seaweedfs_tpu.ec.codec", None)
    inner = store.get_codec

    def get_codec(backend=None, *args, **kwargs):
        if backend:
            return inner(backend, *args, **kwargs)
        return codec.TpuCodec(*args, use_pallas=True, pallas_interpret=True,
                              **kwargs)

    store.get_codec = get_codec


def serve_profiler(port: int, trace_dir: str) -> None:
    state = {"on": False}

    class Control(BaseHTTPRequestHandler):
        def do_POST(self):
            import jax

            try:
                if self.path == "/start" and not state["on"]:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 2
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    state["on"] = True
                elif self.path == "/stop" and state["on"]:
                    jax.profiler.stop_trace()
                    state["on"] = False
                body, code = {"tracing": state["on"]}, 200
            except Exception as e:  # reported to the harness, which fails
                body, code = {"error": repr(e)}, 500
            raw = json.dumps(body).encode()
            self.send_response(code)
            self.send_header("Content-Length", str(len(raw)))
            self.end_headers()
            self.wfile.write(raw)

        def log_message(self, *args):
            pass

    server = ThreadingHTTPServer(("127.0.0.1", port), Control)
    threading.Thread(target=server.serve_forever, daemon=True).start()


def main() -> None:
    split = sys.argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--control-port", type=int, default=0)
    ap.add_argument("--control", default="", choices=["", "wrong-codec"])
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(sys.argv[1:split])
    if args.rehearsal:
        interpret_kernels()
    if args.control == "wrong-codec":
        break_codec()
    if args.trace_dir:
        wrap_spans()
        serve_profiler(args.control_port, args.trace_dir)
    sys.argv = ["seaweedfs_tpu", *sys.argv[split + 1:]]
    runpy.run_module("seaweedfs_tpu", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
