"""The interactive state-space Explorer web service.

Reference: src/checker/explorer.rs.  ``CheckerBuilder.serve`` wraps the
builder with a recent-path sampling visitor, spawns an **on-demand**
checker, and serves:

- ``GET /`` (and ``/app.js``, ``/app.css``) — the single-page UI;
- ``GET /.status`` — ``StatusView`` JSON: done, model type name, counts,
  properties with encoded discovery paths, a recently-visited path
  (src/checker/explorer.rs:171-190);
- ``GET /.metrics`` — the checker's live ``metrics()`` snapshot (this
  package's addition; the reference has no metrics surface): counts for
  every engine, plus wave cadence / table occupancy / device-call time
  for the TPU engines and, for a traced tiered run, the roofline trace
  summary (docs/OBSERVABILITY.md);
- ``GET /.states/{fp1}/{fp2}/...`` — the successor ``StateView`` list for
  the state reached by re-executing the fingerprint path (404 on a bad
  path), each visit nudging the background checker via
  ``check_fingerprint`` so it follows the user
  (src/checker/explorer.rs:224-320);
- ``POST /.runtocompletion`` — switch the on-demand checker to exhaustive
  mode (src/checker/explorer.rs:192-202).
"""

from __future__ import annotations

import json
import pathlib
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, List, Optional

from ..core.path import NondeterminismError, Path
from ..core.visitor import CheckerVisitor

_UI_DIR = pathlib.Path(__file__).resolve().parent / "ui"


class _Snapshot(CheckerVisitor):
    """Samples one recently-visited path every ``period`` seconds.

    Reference: src/checker/explorer.rs:61-98.
    """

    def __init__(self, period: float = 4.0):
        self._lock = threading.Lock()
        self._take = True
        self.path_repr: Optional[str] = None
        t = threading.Thread(
            target=self._rearm, args=(period,), daemon=True, name="snapshot"
        )
        t.start()

    def _rearm(self, period: float) -> None:
        while True:
            time.sleep(period)
            with self._lock:
                self._take = True

    def visit(self, model, path: Path) -> None:
        with self._lock:
            if not self._take:
                return
            self._take = False
            self.path_repr = repr(path.into_actions())


def _properties_view(checker) -> List[List[Any]]:
    """[[expectation, name, encoded discovery path or None], ...]
    (src/checker/explorer.rs:205-222)."""
    model = checker.model()
    out = []
    for p in model.properties():
        disc = checker.try_discovery(p.name)
        out.append(
            [
                p.expectation.name.capitalize(),
                p.name,
                disc.encode(model) if disc is not None else None,
            ]
        )
    return out


def _status_view(checker, snapshot: _Snapshot) -> dict:
    out = {
        "done": checker.is_done(),
        "model": type(checker.model()).__name__,
        "state_count": checker.state_count(),
        "unique_state_count": checker.unique_state_count(),
        "max_depth": checker.max_depth(),
        "properties": _properties_view(checker),
        "recent_path": snapshot.path_repr,
    }
    # Live vitals beside the counts (the same mid-run-safe subset the
    # checking service embeds in a running job's snapshot —
    # obs/metrics.vitals_view): one /.status poll answers "is this run
    # moving, and how fast" without a second /.metrics request.
    from ..obs.metrics import vitals_view

    vitals = vitals_view(checker)
    if vitals is not None:
        out["vitals"] = vitals
    return out


def _state_views(checker, fp_path: str) -> List[dict]:
    """src/checker/explorer.rs:224-320; raises ValueError on bad input."""
    model = checker.model()
    fps_str = fp_path.rstrip("/")
    parts = [p for p in fps_str.split("/") if p != ""]
    fps = []
    for part in parts:
        try:
            fps.append(int(part))
        except ValueError:
            raise ValueError(f"Unable to parse fingerprints {fps_str}")

    results = []
    # The property view is per-checker, not per-successor; discovery paths
    # are reconstructed by re-execution, so compute it once per request.
    properties = _properties_view(checker)
    if not fps:
        for state in model.init_states():
            fp = model.fingerprint(state)
            checker.check_fingerprint(fp)
            try:
                svg = model.as_svg(Path.from_fingerprints(model, [fp]))
            except NondeterminismError:
                svg = None
            results.append(
                {
                    "action": None,
                    "outcome": None,
                    "state": repr(state),
                    "fingerprint": str(fp),
                    "properties": properties,
                    "svg": svg,
                }
            )
        return results

    last_state = Path.final_state(model, fps)
    if last_state is None:
        raise ValueError(f"Unable to find state following fingerprints {fps_str}")
    actions: List[Any] = []
    model.actions(last_state, actions)
    for action in actions:
        outcome = model.format_step(last_state, action)
        state = model.next_state(last_state, action)
        if state is None:
            # "Action ignored" is still returned for debugging
            # (src/checker/explorer.rs:299-306).
            results.append(
                {
                    "action": model.format_action(action),
                    "outcome": None,
                    "state": None,
                    "properties": properties,
                    "svg": None,
                }
            )
            continue
        fp = model.fingerprint(state)
        checker.check_fingerprint(fp)
        try:
            svg = model.as_svg(Path.from_fingerprints(model, fps + [fp]))
        except NondeterminismError:
            svg = None
        results.append(
            {
                "action": model.format_action(action),
                "outcome": outcome,
                "state": repr(state),
                "fingerprint": str(fp),
                "properties": properties,
                "svg": svg,
            }
        )
    return results


def serve(builder, address, block: bool = True, engine: str = "on_demand",
          **engine_kwargs):
    """Serve the Explorer; returns the underlying checker.

    ``address``: ``(host, port)``.  ``block=True`` (reference behavior,
    src/checker/explorer.rs:163-165) serves forever on the calling thread;
    ``block=False`` serves on a background thread and returns immediately
    (the checker gains ``explorer_server`` and ``explorer_address``
    attributes for shutdown and port discovery).

    ``engine``: ``"on_demand"`` (reference behavior — the checker expands
    only what the user browses, ``check_fingerprint`` following each
    click) or ``"tpu"`` — an exhaustive TPU wavefront run proceeds in the
    background while the UI browses its live counts; state views are
    host-re-executed either way, and discovery paths appear in the status
    once the device run completes.  Extra kwargs go to the spawn call.
    """
    snapshot = _Snapshot()
    if engine == "on_demand":
        checker = builder.visitor(snapshot).spawn_on_demand(**engine_kwargs)
    elif engine == "tpu":
        # Deliberately NO snapshot visitor: a visitor reads every
        # expanded state back and decodes it on the host after each
        # fused call (docs/OBSERVABILITY.md), which would slow the
        # exhaustive background run the UI is watching.  The recent-path
        # pane stays empty; live counts come from /.status and /.metrics.
        checker = builder.spawn_tpu(**engine_kwargs)
    else:
        raise ValueError(f"unknown explorer engine {engine!r}")
    return serve_checker(checker, address, block=block, snapshot=snapshot)


def serve_checker(checker, address, block: bool = True, snapshot=None):
    """Serve the Explorer UI over an EXISTING checker — the attach path
    the checking service uses to open a browser on a completed job's
    checker (serve/server.py ``POST /jobs/<id>/explore``) without
    re-running the check.  ``snapshot`` is the recent-path sampling
    visitor when the caller wired one into the spawn; state views are
    host-re-executed against the checker's model exactly as in
    :func:`serve`."""
    snapshot = snapshot or _Snapshot()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # quiet
            pass

        def _send(self, code: int, body: bytes, ctype: str) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send_json(self, obj) -> None:
            self._send(200, json.dumps(obj).encode(), "application/json")

        def do_GET(self) -> None:
            url, _, querystr = self.path.partition("?")
            if url == "/":
                url = "/index.htm"
            if url in ("/index.htm", "/app.js", "/app.css"):
                f = _UI_DIR / url[1:]
                ctype = {
                    ".htm": "text/html",
                    ".js": "text/javascript",
                    ".css": "text/css",
                }[f.suffix]
                self._send(200, f.read_bytes(), ctype)
            elif url == "/.status":
                try:
                    self._send_json(_status_view(checker, snapshot))
                except Exception as e:  # surface, don't reset the connection
                    self._send(500, str(e).encode(), "text/plain")
            elif url == "/.metrics":
                # The live observability surface beside /.status: the
                # checker's metrics() snapshot (counts for every engine;
                # the device engines add wave cadence, table occupancy,
                # device-call totals, the always-on vitals histograms,
                # and — traced tiered runs — the roofline summary).  JSON by
                # default; ``?format=prometheus`` (or a scraper's
                # Accept header) selects the standard text exposition
                # (obs/prometheus.py).  Names: docs/OBSERVABILITY.md.
                from urllib.parse import parse_qsl

                from ..obs.prometheus import (
                    CONTENT_TYPE, render_prometheus, wants_prometheus,
                )

                try:
                    query = dict(parse_qsl(querystr))
                    m = checker.metrics()
                    if wants_prometheus(
                        query, self.headers.get("Accept")
                    ):
                        self._send(
                            200, render_prometheus(m).encode(),
                            CONTENT_TYPE,
                        )
                    else:
                        self._send_json(m)
                except Exception as e:
                    self._send(500, str(e).encode(), "text/plain")
            elif url.startswith("/.states"):
                try:
                    self._send_json(_state_views(checker, url[len("/.states"):]))
                except ValueError as e:
                    self._send(404, str(e).encode(), "text/plain")
            else:
                self._send(404, b"", "text/plain")

        def do_POST(self) -> None:
            if self.path == "/.runtocompletion":
                checker.run_to_completion()
                self._send(200, b"", "text/plain")
            else:
                self._send(404, b"", "text/plain")

    server = ThreadingHTTPServer(tuple(address), Handler)
    checker.explorer_server = server
    checker.explorer_address = server.server_address
    if block:
        server.serve_forever()
    else:
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
    return checker
