"""Cross-process distributed tracing: the platform-level span runtime.

PR 1 gave every record a run-correlation ID; spans add the *timeline*.
One continuous-training cycle is a tree of timed operations spread over
many processes — DAG task -> launcher -> N SPMD ranks (epochs, data
waits, checkpoint saves) -> serving/deploy — and the span runtime
records that tree so the trace exporter (:mod:`trace_export`) can
render the whole cycle as a single Perfetto-loadable timeline,
complementing the per-device ``jax.profiler`` trace with the
platform-level view the TPU-scale literature treats as an operator
surface.

ID contract (extends the ``DCT_RUN_ID`` contract of :mod:`events`):

- ``trace_id`` IS the run-correlation ID — no second identity to join;
- every span has a ``span_id`` (16 hex chars) and a ``parent_id``
  (``None`` for the trace root);
- a parent process exports its current span ID to children via the
  ``DCT_SPAN_ID`` environment variable (:meth:`SpanRecorder.child_env`);
  a child's top-level spans adopt that value as their parent, so the
  launcher's span is the parent of every rank's ``trainer.fit`` span
  across the process boundary.

Storage: per-process JSONL files under one spans directory (default
``<events_dir>/spans``) — ``rank_<r>.jsonl`` for rank processes,
``host_<pid>.jsonl`` for orchestrator-side ones — one single-line JSON
record per COMPLETED span (``O_APPEND``-atomic, like the event log).
Timestamps are wall-clock ``time.time()`` seconds: cross-process merge
needs one clock, and the hosts of a run share theirs (NTP-level skew is
visible in the trace rather than hidden — that is a feature).

Record schema::

    {"trace_id": "dct-...", "span_id": "8b1f...", "parent_id": "...|null",
     "name": "trainer.epoch", "component": "trainer", "rank": 0,
     "pid": 4242, "tid": 1, "t0": <unix s>, "t1": <unix s>,
     "attrs": {...}}

Telemetry must never fail the run: recording degrades to a no-op on OS
errors, and a disabled recorder still mints span IDs so propagation
(and tests over it) keep working with zero files written.

The profiler's timeline: a span that lives on its thread's stack
(:meth:`SpanRecorder.open`, :meth:`SpanRecorder.span`) also enters a
``jax.profiler.TraceAnnotation`` of its name, with its scalar attrs and
its ``span_id`` as the event's stats, and leaves it when it ends — so a
``jax.profiler`` capture shows the recorder's spans on the thread that
ran them, on the clock of the device trace, whether or not the JSONL
sink is on. The ``span_id`` stat is what tells a program span from the
runtime's own host events, and joins the two timelines. A
:meth:`SpanRecorder.start` span may end on another thread or overlap
its successor, which the profiler's per-thread nesting cannot show: it
stays JSONL-only. A process that never imported jax (DAG tasks, the
launcher) emits nothing and imports nothing. Outside a profiler session
an annotation costs one atomic load.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import uuid

from dct_tpu.observability.events import (
    _jsonable,
    _rank_from_env,
    current_run_id,
    observability_enabled,
)

#: Environment variable carrying the parent span ID across a process
#: spawn (the launcher exports it; rank processes adopt it).
SPAN_ENV = "DCT_SPAN_ID"


def mint_span_id() -> str:
    return uuid.uuid4().hex[:16]


def env_parent_span_id(env=None) -> str | None:
    """The parent span ID a launching process exported, if any."""
    return (env if env is not None else os.environ).get(SPAN_ENV) or None


def _scalars(attrs: dict) -> dict:
    """The attrs a profiler event can carry as stats."""
    return {
        k: v for k, v in attrs.items()
        if isinstance(v, (bool, int, float, str))
    }


class Span:
    """One in-flight timed operation; call :meth:`end` exactly once."""

    __slots__ = (
        "recorder", "name", "component", "span_id", "parent_id",
        "t0", "attrs", "_tid", "_ended", "_ann",
    )

    def __init__(self, recorder, name, component, span_id, parent_id,
                 t0, attrs, tid):
        self.recorder = recorder
        self.name = name
        self.component = component
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.attrs = attrs
        self._tid = tid
        self._ended = False
        self._ann = None

    def _enter_timeline(self) -> None:
        """Enter this span's event on the profiler's timeline. Stack
        spans only: the caller has just pushed it on this thread."""
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return
        self._ann = profiler.TraceAnnotation(
            self.name, **{**_scalars(self.attrs), "span_id": self.span_id}
        )
        self._ann.__enter__()

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        scalars = _scalars(attrs)
        if self._ann is not None and scalars:
            self._ann.set_metadata(**scalars)
        return self

    def end(self, **attrs) -> None:
        if self._ended:
            return
        self._ended = True
        if attrs:
            self.set(**attrs)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        # A span opened with SpanRecorder.open sits on its thread's
        # stack; ending it pops it (identity-checked: ending from
        # another thread, or out of order, never corrupts the stack).
        st = self.recorder._stack()
        if st and st[-1] is self:
            st.pop()
        self.recorder._record(self)


class SpanRecorder:
    """Per-process span writer with a thread-local span stack for
    implicit parenting (``path=None`` disables writes; IDs still mint)."""

    def __init__(
        self,
        path: str | None,
        *,
        trace_id: str,
        rank: int | None = None,
        clock=time.time,
        flush_interval: float = 0.0,
        max_records: int = 128,
        _appender=None,
    ):
        self.path = path
        self.trace_id = trace_id
        self.rank = rank
        self._clock = clock
        self._lock = threading.Lock()
        self._dead = False
        # One persistent-handle appender per file (see buffered.py);
        # for_trace() clones share it so two recorders over one file
        # never hold two competing buffers.
        self._appender = _appender
        if path and self._appender is None:
            from dct_tpu.observability.buffered import BufferedAppender

            self._appender = BufferedAppender(
                path, flush_interval=flush_interval, max_records=max_records
            )
        self._local = threading.local()
        # Parent for spans opened with no enclosing span on their thread:
        # the launching process's exported span, else the trace root.
        self.root_parent = env_parent_span_id()
        # Small stable per-thread ids for the exporter's ``tid`` column.
        self._tids: dict[int, int] = {}

    @property
    def enabled(self) -> bool:
        return bool(self.path) and not self._dead

    # -- parenting -----------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current_span_id(self) -> str | None:
        st = self._stack()
        return st[-1].span_id if st else self.root_parent

    def child_env(self, env: dict | None = None) -> dict:
        """Env additions that make spawned processes' top-level spans
        children of this process's current span (plus the trace ID, so
        an un-launched child still joins the same trace)."""
        out = dict(env or {})
        cur = self.current_span_id()
        if cur:
            out[SPAN_ENV] = cur
        # Authoritative, not setdefault: the child joins THIS trace even
        # when the inherited env still carries a stale DCT_RUN_ID.
        out["DCT_RUN_ID"] = self.trace_id
        return out

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            if ident not in self._tids:
                self._tids[ident] = len(self._tids)
            return self._tids[ident]

    # -- span lifecycle ------------------------------------------------
    def start(
        self,
        name: str,
        *,
        component: str | None = None,
        parent_id: str | None = None,
        **attrs,
    ) -> Span:
        """Open a span WITHOUT pushing it on the thread stack — for
        operations whose end is reaped elsewhere (the launcher's
        per-rank spans) or that span threads."""
        return Span(
            self,
            name,
            component or name.split(".", 1)[0],
            mint_span_id(),
            parent_id if parent_id is not None else self.current_span_id(),
            self._clock(),
            attrs,
            self._tid(),
        )

    def open(
        self,
        name: str,
        *,
        component: str | None = None,
        parent_id: str | None = None,
        **attrs,
    ) -> Span:
        """Open a span AND push it on this thread's stack, for long
        windows that cannot be a ``with`` block (the trainer's whole-fit
        and per-epoch spans). Call :meth:`Span.end` to close."""
        sp = self.start(
            name, component=component, parent_id=parent_id, **attrs
        )
        self._stack().append(sp)
        sp._enter_timeline()
        return sp

    class _Ctx:
        __slots__ = ("recorder", "span")

        def __init__(self, recorder, span):
            self.recorder = recorder
            self.span = span

        def __enter__(self):
            self.recorder._stack().append(self.span)
            self.span._enter_timeline()
            return self.span

        def __exit__(self, exc_type, exc, tb):
            st = self.recorder._stack()
            if st and st[-1] is self.span:
                st.pop()
            if exc_type is not None and "error" not in self.span.attrs:
                self.span.set(error=exc_type.__name__)
            self.span.end()
            return False

    def span(
        self,
        name: str,
        *,
        component: str | None = None,
        parent_id: str | None = None,
        **attrs,
    ):
        """Context-managed span, pushed on this thread's stack so nested
        ``span()`` calls parent to it automatically."""
        return self._Ctx(
            self,
            self.start(
                name, component=component, parent_id=parent_id, **attrs
            ),
        )

    def for_trace(self, trace_id: str | None) -> "SpanRecorder":
        """A recorder writing to the same file under a different trace
        ID (the deploy rollout adopts the shipped cycle's ID, exactly
        like its events do); same object when the ID already matches."""
        if not trace_id or trace_id == self.trace_id:
            return self
        other = SpanRecorder(
            self.path, trace_id=trace_id, rank=self.rank, clock=self._clock,
            _appender=self._appender,
        )
        other.root_parent = None  # foreign trace: no local parent
        return other

    # -- emission ------------------------------------------------------
    def _record(self, span: Span) -> None:
        if not self.enabled:
            return
        rec = {
            "trace_id": self.trace_id,
            "span_id": span.span_id,
            "parent_id": span.parent_id,
            "name": span.name,
            "component": span.component,
            "rank": self.rank,
            "pid": os.getpid(),
            "tid": span._tid,
            "t0": round(span.t0, 6),
            "t1": round(self._clock(), 6),
        }
        if span.attrs:
            rec["attrs"] = _jsonable(span.attrs)
        try:
            line = json.dumps(rec, allow_nan=False) + "\n"
        except ValueError:
            self._dead = True
            return
        if not self._appender.append(line):
            self._dead = True  # tracing degrades to silence, never raises

    def flush(self) -> None:
        """Drain buffered span records to disk (no-op when disabled)."""
        if self._appender is not None:
            self._appender.flush()

    def close(self) -> None:
        """Flush and release the file handle (the recorder stays usable)."""
        if self._appender is not None:
            self._appender.close()

    def set_write_through(self) -> None:
        """Flush and disable batching for the rest of the process."""
        if self._appender is not None:
            self._appender.set_write_through()


# ----------------------------------------------------------------------
# Default recorder plumbing, mirroring events.get_default(): layers with
# no config plumbing (checkpoint manager, serving handlers, DAG task
# callables) record through the process default; the trainer installs a
# config-built one.


def spans_dir_from(events_dir: str | None, spans_dir: str = "") -> str | None:
    """THE spans-directory resolution: explicit ``spans_dir`` wins, else
    ``<events_dir>/spans`` — one definition so every builder agrees."""
    if spans_dir:
        return spans_dir
    return os.path.join(events_dir, "spans") if events_dir else None


def span_file_name(rank: int | None) -> str:
    """Per-process file: ranks by rank (stable across restarts of the
    same rank), orchestrator-side processes by pid."""
    if rank is not None:
        return f"rank_{rank:05d}.jsonl"
    return f"host_{os.getpid()}.jsonl"


def recorder_from_config(cfg, *, rank: int | None = None) -> SpanRecorder:
    """Build the process recorder from an ``ObservabilityConfig`` and
    install it as the process default."""
    trace_id = cfg.run_id or current_run_id()
    directory = (
        spans_dir_from(cfg.events_dir, getattr(cfg, "spans_dir", ""))
        if cfg.enabled
        else None
    )
    rec = SpanRecorder(
        os.path.join(directory, span_file_name(rank)) if directory else None,
        trace_id=trace_id,
        rank=rank,
        flush_interval=getattr(cfg, "telemetry_flush_s", 0.0),
        max_records=getattr(cfg, "telemetry_flush_records", 128),
    )
    set_default(rec)
    return rec


_explicit: SpanRecorder | None = None
_cached: tuple[tuple, SpanRecorder] | None = None
_default_lock = threading.Lock()

_ENV_KEYS = (
    "DCT_OBSERVABILITY",
    "DCT_EVENTS_DIR",
    "DCT_SPANS_DIR",
    "DCT_RUN_ID",
    SPAN_ENV,
    "DCT_PROCESS_ID",
    "NODE_RANK",
    "DCT_TELEMETRY_FLUSH_S",
    "DCT_TELEMETRY_FLUSH_RECORDS",
)


def set_default(rec: SpanRecorder | None) -> None:
    global _explicit
    _explicit = rec


def get_default() -> SpanRecorder:
    """The process default recorder: the explicitly installed one, else
    an env-built one (rebuilt when the relevant env changes, so
    monkeypatched tests see their own sink)."""
    global _cached
    if _explicit is not None:
        return _explicit
    with _default_lock:
        trace_id = current_run_id()
        key = tuple(os.environ.get(k) for k in _ENV_KEYS)
        if _cached is not None and _cached[0] == key:
            return _cached[1]
        directory = (
            spans_dir_from(
                os.environ.get("DCT_EVENTS_DIR", "logs/events"),
                os.environ.get("DCT_SPANS_DIR", ""),
            )
            if observability_enabled()
            else None
        )
        from dct_tpu.observability.events import (
            env_flush_interval,
            env_flush_records,
        )

        rank = _rank_from_env()
        rec = SpanRecorder(
            os.path.join(directory, span_file_name(rank))
            if directory
            else None,
            trace_id=trace_id,
            rank=rank,
            flush_interval=env_flush_interval(),
            max_records=env_flush_records(),
        )
        _cached = (key, rec)
        return rec
