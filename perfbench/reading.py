"""What a metric reader is handed: the client log, the window, the
registry snapshots taken at the window's edges, the flight-recorder
spans, the compile events and (in a traced run) the reduced trace.

A reader is a module ``e2e_metrics/<name>.py`` or
``layer_metrics/<name>.py`` with ``read(r) -> float | None`` and the
constants UNIT, BETTER, SOURCE (and LAYER, MOVES for a per-layer one),
which a test holds equal to the metric's entry in BENCHMARK.json. A
reader that finds nothing to read returns None and the metric is left
out of the line.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from . import bytes_and_flops
from .clientlog import ClientLog


@dataclasses.dataclass
class Reading:
    log: ClientLog
    t0: float                       # window start, time.monotonic()
    t1: float                       # window end
    startup_seconds: float
    n_chips: int
    device_kind: str
    cfg: Dict[str, Any]             # the configuration file
    traffic: Dict[str, Any]         # the traffic file
    reg0: Dict[str, Any]            # registry at t0
    reg1: Dict[str, Any]            # registry at t1
    spans: List[Tuple[str, float, float, Dict]]   # recorder, monotonic s
    compiles: List[Tuple[float, str, float]]      # (t, program, seconds)
    memory_peak_bytes: int
    sut: Any = None
    trace: Optional[Dict[str, Any]] = None        # trace_reduce.reduce_trace
    trace_span: Optional[Tuple[float, float]] = None  # monotonic s
    # the recorder at the window's end (``spans`` is the recorder at the
    # trace's end in a traced run); the ring holds the newest 4096
    window_spans: Optional[List[Tuple[str, float, float, Dict]]] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def peaks(self) -> Dict[str, float]:
        return bytes_and_flops.load_peaks(self.device_kind)

    # -- registry deltas over the window -------------------------------------

    @staticmethod
    def _series(reg: Dict[str, Any], name: str, key: str):
        return (reg.get(name) or {}).get("series", {}).get(key)

    def counter_delta(self, name: str, key: str = "") -> float:
        a = self._series(self.reg0, name, key) or 0.0
        b = self._series(self.reg1, name, key) or 0.0
        return float(b) - float(a)

    def hist_delta(self, name: str, key: str) -> Tuple[int, float]:
        """(observations, summed value) a histogram series gained."""
        a = self._series(self.reg0, name, key) or {"count": 0, "sum": 0.0}
        b = self._series(self.reg1, name, key) or {"count": 0, "sum": 0.0}
        return int(b["count"]) - int(a["count"]), float(b["sum"]) - float(a["sum"])

    # -- views of the client log ---------------------------------------------

    def window_chats(self) -> List[Dict[str, Any]]:
        """Chats the schedule put inside the window."""
        return [
            c for c in self.log.chats
            if not c["warm"] and self.t0 <= c["due"] < self.t1
        ]

    def answered(self) -> List[Dict[str, Any]]:
        return [
            c for c in self.window_chats()
            if c["error"] is None and c["first"] is not None
        ]

    def window_output_tokens(self) -> Optional[float]:
        pts = self.log.window_rate_points(self.t0, self.t1)
        return None if pts is None else float(pts[2])

    def spans_in_trace(self, name: str) -> List[Tuple[str, float, float, Dict]]:
        if self.trace_span is None:
            return []
        lo, hi = self.trace_span
        return [s for s in self.spans if s[0] == name and lo <= s[1] < hi]
