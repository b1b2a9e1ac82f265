"""A small JSON client for the agent's HTTP API: one keep-alive
connection per thread, standard library only (the submitter imports
nothing of the program)."""
from __future__ import annotations

import http.client
import json
import threading


class ApiError(Exception):
    def __init__(self, status: int, body: str):
        super().__init__(f"HTTP {status}: {body[:200]}")
        self.status = status


class Api:
    def __init__(self, port: int, timeout: float = 120.0):
        self.port = port
        self.timeout = timeout
        self._tls = threading.local()

    def _conn(self) -> http.client.HTTPConnection:
        c = getattr(self._tls, "conn", None)
        if c is None:
            c = http.client.HTTPConnection("127.0.0.1", self.port,
                                           timeout=self.timeout)
            self._tls.conn = c
        return c

    def call(self, method: str, path: str, body=None):
        """Returns (decoded JSON, X-Nomad-Index or 0)."""
        data = None if body is None else json.dumps(body).encode()
        headers = {"Content-Type": "application/json"} if data else {}
        for attempt in (0, 1):
            c = self._conn()
            try:
                c.request(method, path, body=data, headers=headers)
                r = c.getresponse()
                raw = r.read()
                break
            except (http.client.HTTPException, ConnectionError, OSError):
                # a dropped keep-alive connection: reconnect once
                c.close()
                self._tls.conn = None
                if attempt:
                    raise
        if r.status >= 400:
            raise ApiError(r.status, raw.decode(errors="replace"))
        return (json.loads(raw) if raw else None,
                int(r.getheader("X-Nomad-Index") or 0))

    def get(self, path: str):
        return self.call("GET", path)[0]
