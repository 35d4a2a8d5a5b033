"""What the load generators saw, each entry stamped at receipt on
``time.monotonic()``. The end-to-end readers and ``correct`` read
nothing else about the traffic."""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple


class ClientLog:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: (t, job_id, cumulative output tokens, cumulative input tokens)
        self.token_updates: List[Tuple[float, str, int, int]] = []
        #: receipt time of every chat token event
        self.chat_tokens: List[float] = []
        #: one dict a job: job_id, submitted, ended, status, rows,
        #: max_new_tokens, schema (or None), warm, problems [..],
        #: length_rows, prompt_tokens
        self.jobs: List[Dict[str, Any]] = []
        #: one dict a chat: index, due, fired, first, last, done, tokens,
        #: finish_reason, max_tokens, error, warm, trace_id, problems
        self.chats: List[Dict[str, Any]] = []
        #: earlier lines worth printing (run.py prints them)
        self.notes: List[str] = []
        #: harness failures: the run exits non-zero and prints no result
        self.fatals: List[str] = []

    def tokens(self, t: float, job_id: str, out: int, inp: int) -> None:
        with self._lock:
            self.token_updates.append((t, job_id, int(out), int(inp)))

    def chat_token(self, t: float) -> None:
        with self._lock:
            self.chat_tokens.append(t)

    def add_job(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        with self._lock:
            self.jobs.append(rec)
        return rec

    def add_chat(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        with self._lock:
            self.chats.append(rec)
        return rec

    def note(self, text: str) -> None:
        with self._lock:
            self.notes.append(text)

    def fatal(self, text: str) -> None:
        with self._lock:
            self.fatals.append(text)

    # -- views the readers share -------------------------------------------

    def cumulative_tokens(self, which: int = 2) -> List[Tuple[float, int]]:
        """(t, total over jobs of the latest cumulative count) at every
        job token update, in time order. ``which`` = 2 for output
        tokens, 3 for input tokens."""
        latest: Dict[str, int] = {}
        total = 0
        out = []
        for rec in sorted(self.token_updates):
            t, job_id = rec[0], rec[1]
            val = rec[which]
            prev = latest.get(job_id, 0)
            if val > prev:
                total += val - prev
                latest[job_id] = val
            out.append((t, total))
        return out

    def window_rate_points(
        self, t0: float, t1: float
    ) -> Optional[Tuple[float, float, int]]:
        """(first update time, last update time, output tokens between
        them) over the job updates received inside [t0, t1], with the
        chat tokens received between those two instants added. None
        with fewer than two updates."""
        series = [(t, n) for t, n in self.cumulative_tokens() if t0 <= t <= t1]
        if len(series) < 2:
            chats = sorted(t for t in self.chat_tokens if t0 <= t <= t1)
            if len(chats) < 2:
                return None
            return chats[0], chats[-1], len(chats) - 1
        (ta, na), (tb, nb) = series[0], series[-1]
        chat = sum(1 for t in self.chat_tokens if ta < t <= tb)
        return ta, tb, (nb - na) + chat
