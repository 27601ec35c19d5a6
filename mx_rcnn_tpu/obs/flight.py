"""Flight recorder: bounded in-memory rings of recent events and spans,
dumped to a postmortem artifact when something dies.

Every event emitted through the plane (configured or not) and every
finished span lands here — events in one fixed-size ``collections.deque``
and spans in another of the same size, so steady-state cost is one append,
old entries fall off the back, and the few spans a training step records
never push out the rare events a postmortem is read for
(``checkpoint_saved``, ``guardian_loss_spike``, ``recompiled``).  A span
goes in as the object it is and becomes its Chrome-trace dict only when the
rings are read (``entries``, ``dump``: one list, in order of time): the hot
path builds none.  On a trigger (engine watchdog fire, ``engine.kill``, fleet
replica retirement, guardian ``TrainingDiverged``, or an unhandled
exception via the installed crash handler) the ring is written out as
``flight_<trigger>_<pid>_<n>.json`` under the configured obs dir: the
last-N-things-that-happened record a human (or ``tools/obs_report.py``)
reads first in a postmortem.

Dumps are best-effort by design: the recorder must never turn a dying
process's last breath into a second crash.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import traceback
from typing import Optional

__all__ = ["FlightRecorder"]


class FlightRecorder:
    """Bounded rings (``size`` events, ``size`` spans) + dump-on-trigger.
    Thread-safe."""

    def __init__(self, size: int = 512) -> None:
        self._ring: collections.deque = collections.deque(maxlen=size)
        self._spans: collections.deque = collections.deque(maxlen=size)
        self._lock = threading.Lock()
        self._dumps = 0
        self.out_dir: Optional[str] = None
        self.run_id: str = "-"

    def record(self, entry) -> None:
        """``entry``: an event's dict, or a finished span (anything with
        ``to_chrome()``, rendered when the rings are read; or a dict of
        ``"type": "span"`` that ``entries`` rendered before)."""
        is_event = isinstance(entry, dict) and entry.get("type") != "span"
        with self._lock:
            (self._ring if is_event else self._spans).append(entry)

    def entries(self) -> list[dict]:
        """Events and spans as dicts, oldest first (an event by its
        ``ts_mono_ns``, a span by its end; entries without a time keep
        their place at the front)."""
        with self._lock:
            events, spans = list(self._ring), list(self._spans)
        spans = [
            e if isinstance(e, dict) else {"type": "span", **e.to_chrome()}
            for e in spans
        ]

        def when_ns(e: dict) -> float:
            if e.get("type") == "span":
                return (e.get("ts", 0) + e.get("dur", 0)) * 1e3
            return e.get("ts_mono_ns", 0)

        return sorted(events + spans, key=when_ns)

    def dump(self, trigger: str, extra: Optional[dict] = None
             ) -> Optional[str]:
        """Write the ring to ``flight_<trigger>_<pid>_<n>.json``; returns
        the path, or None when no obs dir is configured (the ring is
        still intact for a later trigger).  Never raises."""
        try:
            out_dir = self.out_dir
            if not out_dir:
                return None
            entries = self.entries()
            with self._lock:
                n = self._dumps
                self._dumps += 1
            safe = "".join(
                c if (c.isalnum() or c in "-_") else "_" for c in trigger
            )
            path = os.path.join(
                out_dir, f"flight_{safe}_{os.getpid()}_{n}.json"
            )
            payload = {
                "run_id": self.run_id,
                "trigger": trigger,
                "ts": round(time.time(), 3),
                "ts_mono_ns": time.monotonic_ns(),
                "pid": os.getpid(),
                "entries": entries,
            }
            if extra:
                payload["extra"] = extra
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f, default=str)
            os.replace(tmp, path)
            return path
        except Exception:  # noqa: BLE001 - postmortems must not re-crash
            return None

    # -- crash handler -----------------------------------------------------

    def install_crash_handler(self) -> None:
        """Chain onto sys.excepthook + threading.excepthook: an unhandled
        exception dumps the ring (trigger "crash") before the normal
        traceback machinery runs."""
        import sys

        prev_hook = sys.excepthook
        prev_thread_hook = threading.excepthook

        def _dump_exc(exc_type, exc, tb, where: str) -> None:
            self.record({
                "type": "event", "subsystem": "crash",
                "kind": "unhandled_exception",
                "ts": round(time.time(), 3),
                "ts_mono_ns": time.monotonic_ns(),
                "payload": {
                    "where": where,
                    "exc_type": getattr(exc_type, "__name__", str(exc_type)),
                    "message": str(exc),
                    "traceback": "".join(
                        traceback.format_exception(exc_type, exc, tb)
                    )[-4000:],
                },
            })
            self.dump("crash")

        def hook(exc_type, exc, tb):
            _dump_exc(exc_type, exc, tb, "main")
            prev_hook(exc_type, exc, tb)

        def thread_hook(args):
            _dump_exc(
                args.exc_type, args.exc_value, args.exc_traceback,
                getattr(args.thread, "name", "thread"),
            )
            prev_thread_hook(args)

        sys.excepthook = hook
        threading.excepthook = thread_hook
