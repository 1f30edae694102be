"""Query lifecycle tracing: hierarchical spans with a thread-local stack.

Mirrors the :class:`~repro.core.ledger.CommLedger` pattern: a
:class:`Tracer` is a context manager that pushes itself onto a thread-local
stack; the module-level helpers (:func:`span`, :func:`record`,
:func:`set_attrs`) log into the innermost active tracer and are **no-ops when
none is active** (a ``nullcontext``; no span and no profiler annotation is
built), so the engine's hot paths pay one truthiness check per node, sort and
host sync when tracing is off.

Span taxonomy (DESIGN.md §14.1)::

    query                      one client submit/ticket, root of the tree
      compile                  SQL -> placed physical plan (cache-aware)
      admit                    accountant admission (+ intent journaling)
      schedule.wait            enqueue -> flush latency of a batched ticket
      batch.flush              one scheduler bucket -> engine pass
        execute                one Engine.execute / execute_batch pass
          node[<Op>]           one plan-node protocol (per slot when split)
            sort               one bitonic network on concrete arrays
            device.wait        the host blocked on the device (``what``)
            xla.compile        one JAX trace/lower/backend phase (``phase``)
      reveal                   result opening + post_reveal derivation
        device.wait            what="reveal": the opened shares reach the host
      record                   accountant record + calibration flush

**One clock with the device.** While a tracer is active, :meth:`Tracer.span`
also opens ``jax.profiler.TraceAnnotation(name)`` around the same interval,
so every span is a host event of any profiler trace taken meanwhile, on the
device's clock. Spans are on the profiler's clock only while a tracer is
active. ``xla.compile`` spans come from JAX's own ``/jax/core/compile/``
monitoring events: the first :meth:`Tracer.__enter__` registers one listener
for their start (a scalar event) and one for their duration, and each phase
becomes a span (and an annotation) from start to end, under whatever span was
innermost. Its ``seconds`` is JAX's own duration, so the spans sum to what a
listener of those events sums, and it starts at JAX's own start time (the
start event's value), so consecutive phases do not overlap. A phase that JAX runs inside another (a jit
traced while another is traced or lowered) is its child.

Every attribute dict passes through :func:`repro.obs.redact.public_view`
before it is stored — a span can never hold a secret-dependent value, no
matter what the instrumented call site passed (the redaction test suite
pins this). Dropped keys are counted in ``Tracer.redactions``.

Export is structured JSONL (:meth:`Tracer.to_jsonl` / :meth:`Tracer.write`):
one object per span with ``span_id``/``parent_id`` linkage, start ``ts``
(the tracer's monotonic clock, offset to the epoch when the tracer was
made), duration ``seconds``, and the redacted ``attrs`` — validated in CI by
``benchmarks/validate_telemetry.py`` against ``benchmarks/telemetry_span_
schema.json``.

Cross-process propagation (DESIGN.md §17): a tracer optionally carries a
``trace_id`` — an opaque hex string naming the whole distributed trace. The
coordinator mints one per traced query (:meth:`Tracer.ensure_trace_id`),
ships it to the party processes in the ``execute`` control frame, and each
party's per-query tracer is constructed with the same id; when set, every
exported span line carries it, so merged multi-process streams stay
attributable to one query. Span ids remain tracer-local — the merge step
(:mod:`repro.obs.distributed`) renumbers them into the coordinator's id
space and re-parents party roots under the coordinator's ``execute`` span.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import time
from typing import Dict, List, Optional

import jax

from . import redact

__all__ = ["Span", "Tracer", "active_tracer", "span", "record", "set_attrs"]

_STATE = threading.local()

_COMPILE_EVENT = "/jax/core/compile/"
_COMPILE_PHASES = {
    "jaxpr_trace_duration": "trace",
    "jaxpr_to_mlir_module_duration": "lower",
    "backend_compile_duration": "backend",
}
# jax.monitoring's listener lists are process-wide, so registration is too
_LISTENING = threading.Lock()
_listening = False


def _stack() -> List["Tracer"]:
    if not hasattr(_STATE, "stack"):
        _STATE.stack = []
    return _STATE.stack


@dataclasses.dataclass
class Span:
    name: str
    span_id: int
    parent_id: Optional[int]
    ts: float  # start: the tracer's perf_counter clock, offset to the epoch
    seconds: float = 0.0
    attrs: Dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "ts": self.ts,
            "seconds": self.seconds,
            "attrs": self.attrs,
        }


class Tracer:
    """Collects a tree of redacted spans for one traced region.

    ``party`` (optional) stamps every span with the RSS party id whose
    process produced it — the multi-party runtime gives each party server
    its own tracer, so exported span streams from a 3-process mesh can be
    merged and still attribute latency per party."""

    def __init__(
        self,
        party: Optional[int] = None,
        trace_id: Optional[str] = None,
    ) -> None:
        self.party = party
        self.trace_id = trace_id
        self.spans: List[Span] = []
        self.redactions: List[str] = []  # dropped attribute keys (audit trail)
        self._open: List[Span] = []
        self._next_id = 0
        # every span's ts on one monotonic clock, so that a child always
        # lies inside its parent; the offset makes it read as wall time
        self._epoch = time.time() - time.perf_counter()

    def ensure_trace_id(self) -> str:
        """Mint the distributed trace id on first use (coordinator side).

        Party-side tracers never mint — they are constructed with the id the
        coordinator shipped, so all processes agree on one trace identity."""
        if self.trace_id is None:
            import os

            self.trace_id = os.urandom(8).hex()
        return self.trace_id

    # -- context management ---------------------------------------------------
    def __enter__(self) -> "Tracer":
        _listen_for_compiles()
        _stack().append(self)
        return self

    def __exit__(self, *exc) -> None:
        top = _stack().pop()
        assert top is self, "Tracer stack corrupted"

    # -- span lifecycle -------------------------------------------------------
    def _new_span(self, name: str, attrs: Dict, start: float) -> Span:
        self._next_id += 1
        if self.party is not None:
            attrs = {**attrs, "party": self.party}
        sp = Span(
            name=name,
            span_id=self._next_id,
            parent_id=self._open[-1].span_id if self._open else None,
            ts=self._epoch + start,
            attrs=redact.public_view(attrs, self.redactions),
        )
        self.spans.append(sp)
        return sp

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """An open span around the ``with`` body, inside a profiler
        annotation of the same name (a host event of any profiler trace
        taken meanwhile)."""
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            sp = self._new_span(name, attrs, t0)
            self._open.append(sp)
            try:
                yield sp
            finally:
                sp.seconds = time.perf_counter() - t0
                popped = self._open.pop()
                assert popped is sp, "span stack corrupted"

    def record(self, name: str, seconds: float = 0.0, **attrs) -> Span:
        """A closed span whose duration was measured elsewhere (e.g. the
        scheduler's enqueue->flush wait), ending now: it starts ``seconds``
        ago. It is not in the profiler's trace."""
        sp = self._new_span(name, attrs, time.perf_counter() - seconds)
        sp.seconds = float(seconds)
        return sp

    def set_attrs(self, sp: Span, **attrs) -> None:
        """Merge (redacted) attributes into ``sp``, e.g. sizes and costs
        known only once its work is done."""
        sp.attrs.update(redact.public_view(attrs, self.redactions))

    # -- export ---------------------------------------------------------------
    def to_jsonl(self) -> str:
        def line(s: Span) -> Dict:
            d = s.to_dict()
            if self.trace_id is not None:
                d["trace_id"] = self.trace_id
            return d

        return "\n".join(
            json.dumps(line(s), sort_keys=True, default=float)
            for s in self.spans
        )

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            txt = self.to_jsonl()
            f.write(txt + ("\n" if txt else ""))

    def find(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def active_tracer() -> Optional[Tracer]:
    stack = _stack()
    return stack[-1] if stack else None


def span(name: str, **attrs):
    """``active_tracer().span(...)`` or a no-op context when tracing is off."""
    tr = active_tracer()
    if tr is None:
        return contextlib.nullcontext()
    return tr.span(name, **attrs)


def record(name: str, seconds: float = 0.0, **attrs) -> None:
    tr = active_tracer()
    if tr is not None:
        tr.record(name, seconds=seconds, **attrs)


def set_attrs(sp: Optional[Span], **attrs) -> None:
    """``active_tracer().set_attrs(sp, ...)`` for a span that :func:`span`
    yielded; a no-op for the ``None`` it yields when tracing is off."""
    tr = active_tracer()
    if sp is not None and tr is not None:
        tr.set_attrs(sp, **attrs)


# -- xla.compile spans from JAX's monitoring events ---------------------------

def _open_compiles() -> list:
    if not hasattr(_STATE, "compiles"):
        _STATE.compiles = []
    return _STATE.compiles


def _on_compile_start(event: str, start: float, **_kw) -> None:
    # JAX marks the start of each phase with a scalar event: its start time
    # on time.time(), taken before the listeners run
    if not event.startswith(_COMPILE_EVENT):
        return
    tr = active_tracer()
    if tr is None:
        return
    now, now_wall = time.perf_counter(), time.time()
    phase = _COMPILE_PHASES.get(event[len(_COMPILE_EVENT):], "other")
    cm = tr.span("xla.compile", phase=phase)
    sp = cm.__enter__()
    # the span's seconds is JAX's duration from that start: start it there,
    # not when this listener runs, or it would end past JAX's end and
    # overlap the phase that follows
    sp.ts = tr._epoch + now - max(0.0, now_wall - start)
    _open_compiles().append((event, cm, sp))


def _on_compile_end(event: str, duration: float, **_kw) -> None:
    if not event.startswith(_COMPILE_EVENT):
        return
    stack = _open_compiles()
    # JAX's phases nest: the innermost open one is the one that ended. One
    # that began while no tracer was active has nothing to close.
    if not stack or stack[-1][0] != event:
        return
    _, cm, sp = stack.pop()
    cm.__exit__(None, None, None)
    sp.seconds = float(duration)


def _listen_for_compiles() -> None:
    global _listening
    with _LISTENING:
        if _listening:
            return
        jax.monitoring.register_scalar_listener(_on_compile_start)
        jax.monitoring.register_event_duration_secs_listener(_on_compile_end)
        _listening = True
