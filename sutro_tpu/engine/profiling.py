"""Engine-level profiling.

The reference has no profiling at all (SURVEY §5.1: "No performance
profiling exists"); this is the TPU build's addition: device traces via
``jax.profiler`` (viewable in TensorBoard/XProf) plus host-side step
timing that lands in the job record, so every job reports its own
latency profile without external tooling.

- ``job_trace(profile_dir, job_id)``: context manager capturing an XLA
  device trace for the whole job into ``{profile_dir}/{job_id}`` when
  ``EngineConfig.profile_dir`` is set (off by default — tracing costs
  memory and time).
- ``StepTimer``: cheap wall-clock timer of prefill/decode steps,
  summarized as count/mean/p50/p90/p99 milliseconds, and the
  scheduler's phase cursor: every instant of ``run_multi`` belongs to
  one named phase (OBSERVABILITY.md "Scheduler phases").
"""

from __future__ import annotations

import contextlib
import logging
import random
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

logger = logging.getLogger(__name__)

# jax.profiler supports ONE device trace per process: two co-batched
# jobs with profile_dir set used to both call start_trace and the
# second raised. Refcounted instead — the first job starts the trace,
# later overlapping jobs join it (logged), the last one out stops it.
_trace_lock = threading.Lock()
_trace_state: Dict[str, Any] = {"count": 0, "path": None}


@contextlib.contextmanager
def job_trace(profile_dir: Optional[str], job_id: str) -> Iterator[None]:
    if not profile_dir:
        yield
        return
    import os

    import jax

    with _trace_lock:
        if _trace_state["count"] == 0:
            path = os.path.join(profile_dir, job_id)
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
            _trace_state["path"] = path
        else:
            logger.info(
                "device trace already running (%s); %s joins it "
                "instead of starting a second trace",
                _trace_state["path"], job_id,
            )
        _trace_state["count"] += 1
        active_path = _trace_state["path"]
    # the job's telemetry document records WHERE its device trace went
    # (its own dir, or the co-batched job's trace it joined)
    from .. import telemetry

    if telemetry.enabled():
        telemetry.job(job_id).attrs["profile_trace"] = active_path
    try:
        yield
    finally:
        with _trace_lock:
            _trace_state["count"] -= 1
            if _trace_state["count"] == 0:
                _trace_state["path"] = None
                try:
                    jax.profiler.stop_trace()
                except RuntimeError:
                    # e.g. the trace died with the backend; a profiling
                    # teardown must never fail the job
                    logger.warning(
                        "stop_trace failed", exc_info=True
                    )


#: phases the scheduler's cursor names for its own host work (the device
#: dispatch phases prefill / decode / admit_sample / kv_* keep theirs)
IDLE_PHASE = "sched_idle"
OTHER_PHASE = "sched_other"
#: a phase that outlasts this is flushed as it runs (``tick``): the one
#: open ``sched_idle`` span of an idle daemon, a plan walk over 64 rows
FLUSH_S = 1.0
#: what is left of an outer phase after a nested ``time()`` context is
#: folded into the phase that follows when it is shorter than this
#: (the few statements between a device call and the next ``enter``)
FOLD_S = 50e-6
#: samples kept per phase for ``summary()``'s percentiles
RESERVOIR = 512

# the cursor the calling thread is inside, so that code the scheduler
# calls into (jobstore flushes under ``on_result``) can mark itself as
# a leaf of the same timeline without knowing the batcher
_tls = threading.local()
_NO_PHASE = contextlib.nullcontext()


@contextlib.contextmanager
def host_leaf(phase: str) -> Iterator[None]:
    """Inside a scheduler cursor: switch it to ``phase`` for the block
    WITHOUT emitting (the caller records its own span and histogram
    sample, as the jobstore does for ``flush`` / ``finalize``), so the
    outer phase's spans exclude the block. Elsewhere: nothing."""
    tm = getattr(_tls, "timer", None)
    if tm is None:
        yield
        return
    with tm.time(phase, emit=False):
        yield


def _merged(a: Optional[Dict[str, Any]], b: Dict[str, Any]) -> Dict[str, Any]:
    """Attrs of two merged segments: counts add up, the rest is the
    later one's."""
    out = dict(a) if a else {}
    for k, v in b.items():
        both_counts = type(v) is int and type(out.get(k)) is int
        out[k] = out[k] + v if both_counts else v
    return out


class _PhaseStat:
    """count, total and a fixed-size uniform sample of one phase."""

    __slots__ = ("count", "total", "sample")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.sample: List[float] = []

    def add(self, dt: float, rng: random.Random) -> None:
        self.count += 1
        self.total += dt
        if len(self.sample) < RESERVOIR:
            self.sample.append(dt)
        else:
            # Algorithm R: every observation ends up kept with the same
            # probability RESERVOIR / count
            j = rng.randrange(self.count)
            if j < RESERVOIR:
                self.sample[j] = dt


class StepTimer:
    """Wall-clock step latencies by phase, and the scheduler's phase
    cursor.

    ``with timer.time(phase)`` times one block (the device dispatches:
    "prefill" / "decode" / "admit_sample" / "kv_*"). With ``cursor=True``
    the timer also holds the CURRENT phase of the thread that drives it:
    ``begin()`` opens the timeline, ``enter(phase)`` closes the running
    phase at ``now`` and opens the next with one clock reading, ``time``
    switches and restores, ``end()`` closes. Phases are therefore
    contiguous leaves: no gaps, no overlaps, and whatever the loop has
    not named is ``sched_other``. ``doze()`` holds ONE ``sched_idle``
    span open across idle spins (flushed once a second), during which
    ``enter`` does nothing until ``wake()``.

    ``sink`` (optional) receives every closed segment as ``sink(phase,
    t0, seconds, cpu_s, attrs)`` the moment it lands — the telemetry
    layer's single tap (the scheduler sets it when telemetry is
    enabled; None costs one attribute load per sample). ``cpu_s`` is
    the thread's CPU time inside the segment (``time.thread_time``):
    wall minus CPU in a pure-Python phase is time spent waiting for the
    GIL. With a cursor each phase is also a
    ``jax.profiler.TraceAnnotation("sutro/<stage>")``, so a live
    profiler trace carries the phases on the host line of the thread,
    on the trace's own clock."""

    def __init__(
        self,
        sink: Optional[Any] = None,
        cursor: bool = False,
        stage_names: Optional[Dict[str, str]] = None,
        opened: Optional[Any] = None,
    ) -> None:
        self._stats: Dict[str, _PhaseStat] = {}
        # the reservoirs' own generator: sampling must not advance the
        # global one under a seeded caller
        self._rng = random.Random(0x5747)
        self.sink = sink
        # ``opened(phase, t0)`` hears which phase the cursor has just
        # opened (None: none): what is RUNNING, for readers who look
        # before it ends
        self.opened = opened
        self._cursor = cursor
        # phase -> annotation name; the class is resolved once, here
        self._ann_names: Dict[str, str] = {}
        self._names = stage_names or {}
        self._ann_cls: Optional[Any] = None
        if cursor:
            import jax

            self._ann_cls = jax.profiler.TraceAnnotation
        self._phase: Optional[str] = None   # the open segment, if any
        self._t0 = 0.0
        self._c0 = 0.0
        self._attrs: Optional[Dict[str, Any]] = None
        self._emit = True
        self._merge = False
        self._restored = False
        self._ann: Optional[Any] = None
        self._dozing = False
        # the last closed host segment, held back one transition so
        # that a run of the same phase (an ``emit`` a finished row, with
        # the accept loop's slivers folded in between) lands as ONE
        # span and ONE histogram sample: [phase, t0, end, cpu, attrs]
        self._held: Optional[List[Any]] = None

    # -- segments --------------------------------------------------------

    def _annotate(self, phase: str) -> Any:
        """The one place a TraceAnnotation is constructed (counted by
        benchmarks/profile_host_overhead.py's census). It starts at
        construction and ends at ``__exit__``; with no trace live it is
        a flag test."""
        name = self._ann_names.get(phase)
        if name is None:
            name = self._ann_names[phase] = (
                "sutro/" + self._names.get(phase, phase)
            )
        return self._ann_cls(name)

    def _close(
        self, phase: str, t0: float, now: float, cpu: float,
        attrs: Optional[Dict[str, Any]], merge: bool,
    ) -> None:
        """A segment ended at ``now``. One that may ``merge`` waits a
        transition for a contiguous successor of the same phase; the
        others land at once (their COUNT is read: dispatches, builds)."""
        held = self._held
        if held is not None:
            if merge and held[0] == phase and held[2] == t0:
                held[2] = now
                held[3] += cpu
                if attrs:
                    held[4] = _merged(held[4], attrs)
                return
            self._flush_held()
        if merge:
            self._held = [phase, t0, now, cpu, attrs]
        else:
            self._record(phase, t0, now - t0, cpu, attrs)

    def _flush_held(self) -> None:
        held, self._held = self._held, None
        if held is not None:
            phase, t0, end, cpu, attrs = held
            self._record(phase, t0, end - t0, cpu, attrs)

    def _record(
        self, phase: str, t0: float, dt: float, cpu: float,
        attrs: Optional[Dict[str, Any]],
    ) -> None:
        st = self._stats.get(phase)
        if st is None:
            st = self._stats[phase] = _PhaseStat()
        st.add(dt, self._rng)
        if self.sink is not None:
            self.sink(phase, t0, dt, cpu, attrs)

    def _switch(
        self, phase: Optional[str], attrs: Optional[Dict[str, Any]],
        emit: bool = True, restored: bool = False, merge: bool = False,
        light: bool = False,
    ) -> None:
        """Close the open segment at ``now`` and open ``phase`` (None:
        open nothing, and let go of the held segment). ``light``: into
        and out of a merging block (an ``emit`` a finished row, inside
        an accept loop) the annotation and the open mark stay the outer
        phase's — the trace is coarser than the recorder there, and a
        row costs two clock readings less."""
        now = time.monotonic()
        cpu = time.thread_time()
        if self._ann is not None and not light:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._phase is not None:
            if (
                self._restored and phase is not None
                and now - self._t0 < FOLD_S
            ):
                # the tail of an outer phase after a nested context:
                # too short to be worth a span, it joins what follows
                now, cpu = self._t0, self._c0
            elif self._emit:
                self._close(
                    self._phase, self._t0, now, cpu - self._c0,
                    self._attrs, self._merge,
                )
        self._phase, self._attrs = phase, attrs
        self._t0, self._c0 = now, cpu
        self._emit, self._restored, self._merge = emit, restored, merge
        if phase is None:
            self._flush_held()
        if light:
            return
        if phase is not None and self._cursor:
            self._ann = self._annotate(phase)
        if self.opened is not None:
            self.opened(phase if emit else None, now)

    # -- the cursor ------------------------------------------------------

    def begin(self, phase: str = OTHER_PHASE) -> None:
        """Open the timeline on the calling thread."""
        if not self._cursor:
            return
        self._dozing = False
        _tls.timer = self
        self._switch(phase, None)

    def end(self) -> None:
        """Close the open phase and the timeline."""
        if not self._cursor:
            return
        self._dozing = False
        self._switch(None, None)
        if getattr(_tls, "timer", None) is self:
            _tls.timer = None

    def enter(self, phase: str, **attrs: Any) -> None:
        """The thread is now in ``phase``. Re-entering the running phase
        extends it (later attrs win)."""
        if not self._cursor or self._dozing or self._phase is None:
            return
        if phase == self._phase and self._emit:
            self._restored = False
            self.note(**attrs)
            return
        self._switch(phase, attrs or None)

    def note(self, **attrs: Any) -> None:
        """Attach attrs to the running phase's span."""
        if self._phase is not None and attrs:
            self._attrs = (
                attrs if self._attrs is None else {**self._attrs, **attrs}
            )

    def count(self, key: str, n: int = 1) -> None:
        """Add ``n`` to a counting attr of the running phase's span."""
        if self._phase is not None:
            if self._attrs is None:
                self._attrs = {}
            self._attrs[key] = self._attrs.get(key, 0) + n

    def doze(self) -> None:
        """Nothing runnable: hold one ``sched_idle`` span open across
        spins, flushing it once ``FLUSH_S``. Until ``wake()``,
        ``enter`` is ignored, so an idle daemon adds one span a second
        to the ring, not one a spin."""
        if not self._cursor or self._phase is None:
            return
        if not self._dozing:
            self._dozing = True
            self._switch(IDLE_PHASE, None)
        else:
            self.tick()

    def tick(self) -> None:
        """Call from inside a phase's long loop (once a row): when the
        open segment is ``FLUSH_S`` old, close it and open the same
        phase again. A phase that runs for many seconds is then in the
        ring, and in a live profiler trace, WHILE it runs — a span that
        only lands when it ends is invisible to whoever looks before
        that — at one span a second."""
        if (
            self._phase is not None
            and time.monotonic() - self._t0 >= FLUSH_S
        ):
            self._switch(
                self._phase, self._attrs, emit=self._emit,
                merge=self._merge,
            )

    def wake(self, phase: str, **attrs: Any) -> None:
        """There is work again: leave the doze for ``phase``."""
        self._dozing = False
        self.enter(phase, **attrs)

    @property
    def dozing(self) -> bool:
        return self._dozing

    @contextlib.contextmanager
    def time(
        self, phase: str, emit: bool = True, merge: bool = False,
        **attrs: Any,
    ) -> Iterator[None]:
        """Time one block as ``phase``; inside a cursor, switch to it
        and restore the phase that was running. Each block is one
        sample (a dispatch, a build) unless ``merge`` says a contiguous
        run of them is one. ``emit=False`` keeps the block out of the
        stats and the sink (see ``host_leaf``)."""
        if not self._cursor:
            # no cursor (telemetry off): two clock readings a block
            t0 = time.monotonic()
            try:
                yield
            finally:
                if emit:
                    self._record(
                        phase, t0, time.monotonic() - t0, 0.0,
                        attrs or None,
                    )
            return
        if phase == self._phase and emit == self._emit:
            # already there (a caller entered the phase around a loop
            # of these blocks): one span, not one a block
            self.note(**attrs)
            yield
            return
        outer = (self._phase, self._emit, self._merge)
        light = merge and outer[0] is not None
        self._switch(
            phase, attrs or None, emit=emit, merge=merge, light=light
        )
        try:
            yield
        finally:
            self._switch(
                outer[0], None, emit=outer[1], restored=True,
                merge=outer[2], light=light,
            )

    def host(self, phase: str, merge: bool = False, **attrs: Any) -> Any:
        """``time(phase)`` for a block of the scheduler's own host work:
        nothing at all without a cursor (telemetry off)."""
        if not self._cursor:
            return _NO_PHASE
        return self.time(phase, merge=merge, **attrs)

    def add(self, phase: str, seconds: float) -> None:
        self._record(
            phase, time.monotonic() - seconds, seconds, 0.0, None
        )

    def summary(self) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for phase, st in self._stats.items():
            if not st.count:
                continue
            s = sorted(st.sample)
            n = len(s)

            def pct(p: float) -> float:
                return s[min(int(p * n), n - 1)]

            out[phase] = {
                "count": st.count,
                "total_s": round(st.total, 4),
                "mean_ms": round(1e3 * st.total / st.count, 3),
                "p50_ms": round(1e3 * pct(0.50), 3),
                "p90_ms": round(1e3 * pct(0.90), 3),
                "p99_ms": round(1e3 * pct(0.99), 3),
            }
        return out
