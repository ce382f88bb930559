"""Bring-up waits shared by the multi-process serving-plane drills."""

from __future__ import annotations

import http.client
import json
import os
import time


def wait_port_record(port_dir, tag, proc, timeout=180.0) -> int:
    """The port a ``serve_cli --port-dir`` replica wrote for ``tag``."""
    path = os.path.join(port_dir, f"{tag}.json")
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica {tag} died before binding: rc={proc.returncode}")
        try:
            with open(path) as fh:
                return int(json.load(fh)["port"])
        except (OSError, ValueError, KeyError):
            time.sleep(0.2)
    raise RuntimeError(f"replica {tag} never wrote its port record")


def wait_ready(host, port, proc, timeout=180.0, path="/readyz"):
    """Poll ``path`` until it answers 200; a dead ``proc`` raises."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"process died before ready: rc={proc.returncode}")
        try:
            conn = http.client.HTTPConnection(host, port, timeout=5.0)
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                resp.read()
                if resp.status == 200:
                    return
            finally:
                conn.close()
        except OSError:
            pass
        time.sleep(0.2)
    raise RuntimeError(f"{host}:{port}{path} never went ready "
                       f"within {timeout:.0f}s")
