"""HTTP front for the search service: JSON POST → one live driver.

Mounts :func:`repro.launch.serve_search.handle_request` — the same
dict-in/dict-out protocol the stdin front speaks — behind a stdlib
``ThreadingHTTPServer``, completing the transport story sketched in that
module's docstring ("a real deployment would mount handle_request behind
HTTP").  No new dependency: ``http.server`` ships with CPython.

  POST /            {"op": "submit", "tenant": "a", "plan": {...}}
  POST /            {"op": "stats"} | {"op": "drain"}
  GET  /stats       convenience alias for {"op": "stats"}

One JSON body per request, one JSON response (HTTP 200 even for
``{"ok": false}`` protocol errors — transport status is reserved for
transport problems: 400 malformed JSON, 404 unknown path, 405 bad
method).  Shutdown drains: admitted work is never lost.

  PYTHONPATH=src python -m repro.launch.serve_http --port 8080 &
  curl -d '{"op": "submit", "tenant": "a", "class": 0, \
            "plan": {"result_limit": 5, "execution": \
                     {"queries_axis": true}}}' localhost:8080
"""
from __future__ import annotations

import json
import sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve_search import (
    build_parser,
    build_service,
    handle_request,
)
from repro.serve.service import SearchService


def make_server(
    service: SearchService, host: str = "127.0.0.1", port: int = 0
) -> ThreadingHTTPServer:
    """Bind a threaded HTTP server over ``service`` (``port=0`` picks a
    free port — read it back from ``server.server_address``).  The caller
    owns the service lifecycle: ``service.start()`` before serving,
    ``drain()``/``stop()`` after ``server.shutdown()``."""

    class Handler(BaseHTTPRequestHandler):
        def _reply(self, code: int, payload: dict) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self) -> None:  # noqa: N802 (stdlib naming)
            length = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(length)
            try:
                obj = json.loads(raw.decode() or "null")
            except (json.JSONDecodeError, UnicodeDecodeError) as e:
                self._reply(400, {"ok": False, "error": f"bad JSON: {e}"})
                return
            if not isinstance(obj, dict):
                self._reply(
                    400, {"ok": False,
                          "error": "request body must be a JSON object"})
                return
            self._reply(200, handle_request(service, obj))

        def do_GET(self) -> None:  # noqa: N802 (stdlib naming)
            if self.path.rstrip("/") in ("", "/stats"):
                self._reply(200, handle_request(service, {"op": "stats"}))
            else:
                self._reply(
                    404, {"ok": False,
                          "error": f"unknown path {self.path!r}"})

        def log_message(self, fmt, *args) -> None:
            pass   # quiet: the service prints its own summary on stderr

    return ThreadingHTTPServer((host, port), Handler)


def main() -> None:
    # serve_search's full CLI surface (dataset/budget/cache/index/...)
    # plus the bind address — one parser, one source of truth
    ap = build_parser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    args = ap.parse_args()
    enable_compile_cache()

    service = build_service(args)
    server = make_server(service, host=args.host, port=args.port)
    host, port = server.server_address[:2]
    print(f"service: http://{host}:{port} (POST JSON ops; GET /stats)",
          file=sys.stderr)
    service.start()
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        server.server_close()
        if service.busy():
            service.drain()   # shutdown implies drain, like the stdin EOF
        service.stop()
    print("service: clean drain", file=sys.stderr)


if __name__ == "__main__":
    main()
