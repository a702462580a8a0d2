"""Hermetic threaded HTTP range server with fault injection.

Serves one byte payload on a 127.0.0.1 ephemeral port with single-range
GET/HEAD, ETag + Last-Modified validators, and ``If-Range`` semantics
(mismatched validator -> 200 full body, per RFC 9110). A thread-safe
``FaultPlan`` injects the failure modes a real object store exhibits:

  * ``inject_503(n)``     — next n requests answer 503 (retryable)
  * ``inject_short(n)``   — next n range bodies are cut in half mid-wire
                            (Content-Length promises more; connection drops)
  * ``drop_ranges``       — ignore Range headers entirely (200 full body)
  * ``latency``           — per-request sleep, modelling a far origin
  * ``flip_etag()``       — swap payload/ETag at runtime (object replaced)

Used by the FileReader contract suite and the remote-backend tests.
Loopback only — no external network — so tier-1 stays offline-safe.
"""

from __future__ import annotations

import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple


class FaultPlan:
    """Mutable, thread-safe schedule of injected faults."""

    def __init__(self):
        self._lock = threading.Lock()
        self.fail_503 = 0
        self.short_bodies = 0
        self.misaligned = 0
        self.drop_ranges = False
        self.strip_etag = False  # model an intermediary stripping ETag
        self.latency = 0.0

    def inject_503(self, n: int = 1) -> None:
        with self._lock:
            self.fail_503 += n

    def inject_short(self, n: int = 1) -> None:
        with self._lock:
            self.short_bodies += n

    def inject_misaligned(self, n: int = 1) -> None:
        """Next n range responses answer for a shifted start offset (a
        misbehaving cache serving a differently-aligned partial object)."""
        with self._lock:
            self.misaligned += n

    def _take(self, attr: str) -> bool:
        with self._lock:
            n = getattr(self, attr)
            if n > 0:
                setattr(self, attr, n - 1)
                return True
            return False

    def take_503(self) -> bool:
        return self._take("fail_503")

    def take_short(self) -> bool:
        return self._take("short_bodies")

    def take_misaligned(self) -> bool:
        return self._take("misaligned")


class RangeHTTPServer:
    """One-payload HTTP server: ``with RangeHTTPServer(blob) as srv: srv.url``."""

    def __init__(
        self,
        payload: bytes,
        *,
        etag: Optional[str] = '"rs-1"',
        send_validators: bool = True,
        latency: float = 0.0,
    ):
        self._lock = threading.Lock()
        self._payload = bytes(payload)
        # send_validators=False models gateways that return neither ETag nor
        # Last-Modified (clients must fall back to content digests).
        self._etag = etag if send_validators else None
        self._last_modified = (
            "Mon, 27 Jul 2026 00:00:00 GMT" if send_validators else None
        )
        self.faults = FaultPlan()
        self.faults.latency = latency
        self.request_count = 0
        self.range_requests = 0
        self.head_requests = 0

        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"  # keep-alive: exercise conn reuse

            def log_message(self, *args):  # noqa: D102 - silence stderr
                pass

            def _snapshot(self) -> Tuple[bytes, Optional[str], Optional[str]]:
                with outer._lock:
                    outer.request_count += 1
                    return outer._payload, outer._etag, outer._last_modified

            def _common_headers(self, etag: Optional[str], lm: Optional[str]) -> None:
                if etag is not None and not outer.faults.strip_etag:
                    self.send_header("ETag", etag)
                if lm is not None:
                    self.send_header("Last-Modified", lm)
                self.send_header("Accept-Ranges", "bytes")

            def _send_503(self) -> None:
                body = b"injected server error"
                self.send_response(503)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_HEAD(self):  # noqa: N802 - http.server API
                payload, etag, lm = self._snapshot()
                with outer._lock:
                    outer.head_requests += 1
                if outer.faults.latency:
                    time.sleep(outer.faults.latency)
                if outer.faults.take_503():
                    self._send_503()
                    return
                self.send_response(200)
                self._common_headers(etag, lm)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()

            def do_GET(self):  # noqa: N802 - http.server API
                payload, etag, lm = self._snapshot()
                if outer.faults.latency:
                    time.sleep(outer.faults.latency)
                if outer.faults.take_503():
                    self._send_503()
                    return

                rng = _parse_range(self.headers.get("Range"), len(payload))
                if_range = self.headers.get("If-Range")
                use_range = (
                    rng is not None
                    and not outer.faults.drop_ranges
                    # RFC 9110 If-Range: serve the range only if the
                    # validator still matches, else the full current body.
                    and not (if_range is not None and if_range != etag)
                )
                if rng is not None:
                    with outer._lock:
                        outer.range_requests += 1
                if use_range:
                    a, b = rng
                    if outer.faults.take_misaligned() and a > 0:
                        a, b = a - 1, b - 1  # answer for a shifted window
                    if a >= len(payload):
                        self.send_response(416)
                        self._common_headers(etag, lm)
                        self.send_header("Content-Range", "bytes */%d" % len(payload))
                        self.send_header("Content-Length", "0")
                        self.end_headers()
                        return
                    body = payload[a : b + 1]
                    self.send_response(206)
                    self._common_headers(etag, lm)
                    self.send_header(
                        "Content-Range", "bytes %d-%d/%d" % (a, a + len(body) - 1, len(payload))
                    )
                else:
                    body = payload
                    self.send_response(200)
                    self._common_headers(etag, lm)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                if outer.faults.take_short():
                    # Promise the full body, deliver half, drop the
                    # connection: the client sees IncompleteRead.
                    self.wfile.write(body[: len(body) // 2])
                    self.close_connection = True
                    return
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    # -- runtime control ----------------------------------------------------

    @property
    def url(self) -> str:
        return "http://127.0.0.1:%d/payload.gz" % self._httpd.server_address[1]

    @property
    def etag(self) -> str:
        with self._lock:
            return self._etag

    def set_payload(self, payload: bytes, etag: Optional[str]) -> None:
        """Replace the object (new content, new validator — or none)."""
        with self._lock:
            self._payload = bytes(payload)
            self._etag = etag
            if etag is not None or self._last_modified is not None:
                self._last_modified = "Tue, 28 Jul 2026 00:00:00 GMT"

    def flip_etag(self, etag: str = '"rs-2"') -> None:
        """Change the validator without changing content (metadata rewrite)."""
        with self._lock:
            self._etag = etag
            self._last_modified = "Tue, 28 Jul 2026 00:00:00 GMT"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)

    def __enter__(self) -> "RangeHTTPServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


_RANGE_RE = re.compile(r"^bytes=(\d+)-(\d*)$")


def _parse_range(value: Optional[str], total: int) -> Optional[Tuple[int, int]]:
    """'bytes=a-b' / 'bytes=a-' -> (a, b_inclusive); None when absent/odd."""
    if not value:
        return None
    m = _RANGE_RE.match(value.strip())
    if not m:
        return None
    a = int(m.group(1))
    b = int(m.group(2)) if m.group(2) else total - 1
    return a, min(b, total - 1)
