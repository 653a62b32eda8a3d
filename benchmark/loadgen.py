"""Open-loop load: every request is sent when it is DUE, whether or not the
system has kept up, from one thread that sleeps between sends. Latency is
counted from the due time, so a stall charges the requests queued behind it;
`late_s` says how late the generator itself ran (sent - due), so a starved
generator is not read as a fast server."""
from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Sequence


class OpenLoop:
    def __init__(self, due_offsets_s: Sequence[float],
                 send: Callable[[int], object], clock=time.monotonic,
                 sleep=time.sleep):
        self._due = list(due_offsets_s)
        self._send = send
        self._clock = clock
        self._sleep = sleep
        self.t_start: Optional[float] = None
        self.handles: List[object] = []     # send()'s result, or the error
        self.sent_at: List[float] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-loadgen")

    def due_at(self, i: int) -> float:
        """Request i's due time on the clock (valid once started)."""
        return self.t_start + self._due[i]

    def start(self, t_start: Optional[float] = None) -> float:
        self.t_start = self._clock() if t_start is None else t_start
        self._thread.start()
        return self.t_start

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30.0)
        if self._thread.is_alive():
            raise RuntimeError("load generator did not stop")

    @property
    def late_s(self) -> List[float]:
        return [s - self.due_at(i) for i, s in enumerate(self.sent_at)]

    def _run(self) -> None:
        for i, off in enumerate(self._due):
            due = self.t_start + off
            while not self._stop.is_set():
                wait = due - self._clock()
                if wait <= 0:
                    break
                self._sleep(min(wait, 0.05))
            if self._stop.is_set():
                return
            at = self._clock()
            try:
                h = self._send(i)
            except Exception as e:  # a refusal is an outcome, kept per request
                h = e
            self.sent_at.append(at)
            self.handles.append(h)
