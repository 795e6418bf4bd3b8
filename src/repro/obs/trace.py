"""Ring-buffered structured event recorder — the event half of ``repro.obs``.

The recorder is pure host-side Python: emitters call ``trace.emit(kind,
**fields)`` from *untraced* code (the engine step loop, the scheduler, the
train driver), so an attached recorder never changes a jaxpr and a detached
one costs a single ``is None`` check at the call site. tests/test_obs.py
asserts the stronger claim directly: the decode-step jaxpr with a recorder
attached is byte-identical to one without.

Events are tiny and flat — ``Event(ts, kind, fields)`` with JSON-scalar
fields only — and live in a ``deque(maxlen=capacity)`` ring, so a long
serve run keeps the newest ``capacity`` events and counts what it dropped
(``dropped``). Export (JSONL, Chrome trace) lives in ``export.py``; span
reconstruction (per-request admit→retire trees) in ``spans.py``.

Event kinds emitted by the stack (the trace schema; fields beyond ``ts`` /
``kind`` are per-kind):

====================  =====================================================
kind                  fields
====================  =====================================================
``submit``            rid, prompt_len, max_new
``admit``             rid, slot, pages (pages allocated at admit)
``prefill_chunk``     rid, slot, start, len (one bucketed chunk)
``prefill``           rid, slot, len, dur (whole-prompt wall time)
``first_token``       rid, slot
``decode_step``       step, n_active, free_pages, dur
``moe_hits``          step_num, rows, experts_hit, tokens_here (models with
                      experts: live rows; held experts that got a live
                      token and live token-expert pairs routed to held
                      experts, each summed over the decode step's layers)
``preempt``           rid, slot, gen_len (generated tokens folded back)
``retire``            rid, slot, new_tokens, reason ("eos"|"max_new")
``page_alloc``        slot, page, pos (lazy growth in ``ensure_page``)
``page_free``         slot, n (pages released at retire/preempt)
``cache_hit``         rid, slot, hit_tokens, prompt_len (prefix cache)
``cow_fork``          rid, slot, src_page, dst_page, tokens (mid-page hit)
``prefix_evict``      pages, tokens (one LRU leaf freed under pressure)
``state_snapshot``    slot, nbytes
``state_restore``     slot, nbytes
``train_step``        step, loss, dur (train driver loop)
====================  =====================================================

Profiler spans
--------------

Beside the events, the serving engine marks its step phases in the JAX
profiler's own trace with ``span(name, **fields)`` (a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``) and
``step_span(n)`` (a ``StepTraceAnnotation``). They are always on, with or
without a recorder: the profiler decides whether they are recorded, and a
span costs about a microsecond while it is off. In a trace they sit on the
host plane, on the same clock as the device's operations, with their
fields as event stats. Spans are opened only in host code, never inside a
jitted function, per slot or per token. Fields are JSON scalars.

==============================  =======================  ====================
span                            parent                   fields
==============================  =======================  ====================
``repro.engine.step``           the caller's span        step_num
``repro.engine.admit``          ``repro.engine.step``
``repro.engine.prefill``        ``repro.engine.step``    rid, tokens,
                                                         computed, padded
``repro.engine.pages``          ``repro.engine.step``
``repro.engine.dispatch``       ``repro.engine.step``    rows
``repro.engine.sync``           ``repro.engine.step``
``repro.engine.bookkeeping``    ``repro.engine.step``
==============================  =======================  ====================

``step`` is one ``Engine.step()``; ``step_num`` counts the decode steps
before it. Its children come in the order above and together cover it;
``admit``, ``prefill`` and ``bookkeeping`` may occur several times or not
at all. ``admit``: one ``try_admit`` and its bookkeeping. ``prefill``: one
request's prefill, whole or chunked or after a prefix hit; ``tokens`` is
the prompt's length, ``computed`` the tokens run after a prefix hit,
``padded`` the tokens run with bucket padding. ``pages``: mapping the
pages the step writes, and preemption. ``dispatch``: the decode inputs'
transfer to the device and the step's enqueue, over ``rows`` active slots.
``sync``: the sample's enqueue and the host's wait for its tokens.
``bookkeeping``: tokens, retirement, ``ServeMetrics``, the memory ledger,
recorder events and the health read.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

from jax.profiler import StepTraceAnnotation, TraceAnnotation


@dataclass(frozen=True)
class Event:
    ts: float                       # recorder-clock seconds
    kind: str
    fields: dict[str, Any] = field(default_factory=dict)

    def to_json(self) -> dict[str, Any]:
        return {"ts": self.ts, "kind": self.kind, **self.fields}


class TraceRecorder:
    """Host-side ring buffer of structured events.

    ``clock`` is injectable (tests drive a deterministic counter, matching
    the ``ServeMetrics`` convention); ``capacity`` bounds memory — overflow
    silently evicts the OLDEST events and bumps ``dropped``. ``enabled``
    gates ``emit`` so a recorder can be muted without detaching it.
    """

    def __init__(self, capacity: int = 65536,
                 clock: Callable[[], float] = time.monotonic):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.clock = clock
        self.enabled = True
        self.dropped = 0
        self._ring: deque[Event] = deque(maxlen=capacity)

    def emit(self, kind: str, **fields: Any) -> None:
        if not self.enabled:
            return
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(Event(self.clock(), kind, fields))

    def events(self, kind: str | None = None) -> list[Event]:
        """Snapshot of buffered events, oldest first (optionally one kind)."""
        if kind is None:
            return list(self._ring)
        return [e for e in self._ring if e.kind == kind]

    def __len__(self) -> int:
        return len(self._ring)

    def __iter__(self) -> Iterator[Event]:
        return iter(list(self._ring))

    def clear(self) -> None:
        self._ring.clear()
        self.dropped = 0


def span(name: str, **fields: Any) -> TraceAnnotation:
    """A profiler span ``repro.<name>`` with JSON-scalar ``fields``; use as a
    context manager in host code (see "Profiler spans" above)."""
    return TraceAnnotation(f"repro.{name}", **fields)


def step_span(n: int) -> StepTraceAnnotation:
    """The span around one ``Engine.step()``: ``repro.engine.step`` with
    ``step_num`` ``n``."""
    return StepTraceAnnotation("repro.engine.step", step_num=n)
