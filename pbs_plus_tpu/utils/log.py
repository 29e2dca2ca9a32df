"""Scoped structured logging with dedup window.

Reference: internal/log/log.go:18-135 — slog JSON logger with scope fields
(JobID/BackupID/RestoreID/VerifyID) and a sha256-keyed dedup window
(default 5 s, env LOG_DEDUP_WINDOW).
"""

from __future__ import annotations

import hashlib
import json
import os
import logging
import sys
import threading
import time
from typing import Any

_lock = threading.Lock()
_dedup: dict[bytes, float] = {}


class _JSONFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        entry: dict[str, Any] = {
            "time": self.formatTime(record, "%Y-%m-%dT%H:%M:%S"),
            "level": record.levelname,
            "msg": record.getMessage(),
        }
        scope = getattr(record, "scope", None)
        if scope:
            entry.update(scope)
        if record.exc_info and record.exc_info[0] is not None:
            entry["exc"] = self.formatException(record.exc_info)
        return json.dumps(entry, separators=(",", ":"))


_root = logging.getLogger("pbs_plus_tpu")
if not _root.handlers:
    _h = logging.StreamHandler(sys.stderr)
    _h.setFormatter(_JSONFormatter())
    _root.addHandler(_h)
    _root.setLevel(logging.INFO)


class Logger:
    """Scoped logger: ``L.with_scope(job_id=...)`` attaches fields to every
    record, and repeated identical messages inside the dedup window are
    dropped (reference behavior: sha256-keyed, default 5 s)."""

    def __init__(self, scope: dict[str, Any] | None = None,
                 dedup_window_s: float | None = None):
        self._scope = dict(scope or {})
        if dedup_window_s is None:
            from . import conf
            dedup_window_s = conf.env().log_dedup_window_s
        self._window = dedup_window_s

    @property
    def scope(self) -> dict[str, Any]:
        """The fields this logger attaches to every record."""
        return dict(self._scope)

    def with_scope(self, **fields: Any) -> "Logger":
        s = dict(self._scope)
        s.update(fields)
        return Logger(s, self._window)

    def _should_emit(self, level: int, msg: str) -> bool:
        if self._window <= 0:
            return True
        key = hashlib.sha256(
            f"{level}|{msg}|{sorted(self._scope.items())}".encode()
        ).digest()
        now = time.monotonic()
        with _lock:
            last = _dedup.get(key, 0.0)
            if now - last < self._window:
                return False
            _dedup[key] = now
            if len(_dedup) > 4096:
                cutoff = now - self._window
                for k in [k for k, v in _dedup.items() if v < cutoff]:
                    del _dedup[k]
        return True

    def _log(self, level: int, msg: str, *args: Any, **kw: Any) -> None:
        if args:
            msg = msg % args
        if not self._should_emit(level, msg):
            return
        _root.log(level, msg, extra={"scope": self._scope}, **kw)

    def debug(self, msg: str, *a: Any) -> None: self._log(logging.DEBUG, msg, *a)
    def info(self, msg: str, *a: Any) -> None: self._log(logging.INFO, msg, *a)
    def warning(self, msg: str, *a: Any) -> None: self._log(logging.WARNING, msg, *a)
    def error(self, msg: str, *a: Any) -> None: self._log(logging.ERROR, msg, *a)
    def exception(self, msg: str, *a: Any) -> None:
        self._log(logging.ERROR, msg, *a, exc_info=True)


L = Logger()


def set_level(level: int) -> None:
    _root.setLevel(level)


def add_rotating_file(path: str, *, max_bytes: int = 50 << 20,
                      backups: int = 5) -> "logging.Handler":
    """Size-rotated JSON log file (reference: lumberjack rotation,
    internal/log/log_unix.go).  Returns the handler so callers can
    remove it on shutdown."""
    import logging.handlers
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    h = logging.handlers.RotatingFileHandler(
        path, maxBytes=max_bytes, backupCount=backups)
    h.setFormatter(_JSONFormatter())
    _root.addHandler(h)
    return h


def remove_rotating_file(h: "logging.Handler") -> None:
    """Detach + close a handler returned by add_rotating_file."""
    _root.removeHandler(h)
    h.close()
