"""Where the serving engine's host time goes, program by program.

Every device program ``ServingEngine`` runs goes through one recorder, and
the same calls feed two outputs:

* **Profiler spans** named ``engine.<phase>`` (``jax.profiler.
  TraceAnnotation``).  With a profiler running (``jax.profiler.start_trace``)
  they lie on the device trace's clock, so each idle gap of the device sits
  under the phase the host was in; without one they cost under a microsecond.
* **The dispatch log** ``LOG``: a process-wide deque of the last
  ``LOG_MAX`` records, stamped with ``time.monotonic`` (``clock``).  One
  :class:`Dispatch` per device program and one :class:`Step` per
  ``ServingEngine.step()`` call.

Phases, in the order one step runs them:

``step``
    all of ``ServingEngine.step()`` (a :class:`Step` record).
``schedule``
    lifecycle sweep, admission, growth, the window grant, copy-on-write and
    the dispatch guard.  Not in the log: a step's time outside its
    dispatches' phases is its scheduling (:func:`summary`).
``upload``
    the device block table when the scheduler changed it, and the
    program's host inputs.
``dispatch``
    the call of the jitted program: it returns once the program is enqueued.
``wait``
    ``jax.block_until_ready`` on the program's outputs: the device's time
    left after the enqueue.
``readback``
    copying the results the host needs to host memory.
``drain``
    emitting tokens, trimming tables and the rest of the bookkeeping.

Each phase of a dispatch is a ``(start, end)`` pair on its record; a program
whose outputs stay on the device (``copy_pages``) has no wait, readback or
drain.  Program names are the jitted functions' names, which are also the
XLA modules' names in the device trace (``jit_<name>``): ``decode_step``,
``prefill_step``, ``decode_window_<ticks>``, ``spec_window_<rounds>`` and
``copy_pages``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax

PREFIX = "engine."
LOG_MAX = 8192  # more than ten 51 s windows' worth of dispatches
clock = time.monotonic

Span = Tuple[float, float]


@dataclasses.dataclass
class Step:
    """One ``ServingEngine.step()`` call."""

    t0: float
    t1: float


@dataclasses.dataclass
class Dispatch:
    """One device program: what ran, for whom, and when each phase ran.

    ``ticks`` counts the decode ticks (or speculative rounds, or prefill
    chunks) the program covers; 0 for ``copy_pages``.  ``uids`` are the
    requests in its slots, in slot order.  A prefill program runs ``rows``
    (slots × chunk) rows, of which ``live_rows`` hold prompt tokens.  A
    paged decode program's attention visits ``pages_walked`` KV pages of
    the ``pages_table`` its block tables hold (one KV head's walk, summed
    over ticks, slots and layers; equal where the walk is not bounded by
    the live length).  A prefill program's attention visits
    ``prior_pages_walked`` prior KV pages of the ``prior_pages_table`` its
    grid cells' tables hold (one KV head's walk, summed over the chunk's
    grid cells, slots and layers).  A program of a model with MoE layers
    computes ``expert_rows`` rows of the held experts' FFNs (over layers),
    of which ``expert_rows_routed`` serve a live token that picked that
    expert."""

    program: str
    ticks: int
    uids: Tuple[int, ...]
    rows: int = 0
    live_rows: int = 0
    pages_walked: int = 0
    pages_table: int = 0
    prior_pages_walked: int = 0
    prior_pages_table: int = 0
    expert_rows: int = 0
    expert_rows_routed: int = 0
    upload: Optional[Span] = None
    dispatch: Optional[Span] = None
    wait: Optional[Span] = None
    readback: Optional[Span] = None
    drain: Optional[Span] = None


LOG: "collections.deque" = collections.deque(maxlen=LOG_MAX)


@contextlib.contextmanager
def span(phase: str, record: Optional[Dispatch] = None):
    """The profiler span ``engine.<phase>``; with ``record``, the phase's
    monotonic start and end go into ``record.<phase>``."""
    with jax.profiler.TraceAnnotation(PREFIX + phase):
        t0 = clock()
        yield
        if record is not None:
            setattr(record, phase, (t0, clock()))


def _start(rec) -> float:
    return rec.t0 if isinstance(rec, Step) else rec.dispatch[0]


def window(t0: float, t1: float) -> Optional[list]:
    """The records of ``LOG`` that start inside ``[t0, t1]`` (a dispatch
    starts with its ``dispatch`` phase).  None when the log may have
    dropped some of them: it is full and its oldest record starts after
    ``t0``."""
    recs = list(LOG)
    if recs and len(recs) == LOG.maxlen and _start(recs[0]) > t0:
        return None
    return [r for r in recs if t0 <= _start(r) <= t1]


def _overlap(spans: Iterable[Span], a: float, b: float) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in spans)


def _is_decode(program: str) -> bool:
    return program == "decode_step" or program.startswith("decode_window_")


def summary(records: List, t0: float, t1: float) -> dict:
    """What the host did over ``[t0, t1]``, from ``window``'s records.

    * ``phase_s``: seconds in each dispatch phase; ``schedule`` is the
      steps' time outside them, ``caller`` the window's time outside steps.
    * ``host_gap_s``: the stretches from one program's results being ready
      (end of ``wait``) to the next program's enqueue (end of ``dispatch``),
      counted from ``t0`` to the first enqueue and from the last results to
      ``t1``: the time the device waits on the host.  ``host_gap_split``
      splits it by phase as ``phase_s`` does.
    * ``longest_readback``: ``[seconds, program]`` of the longest readback.
    * ``decode_ms_per_tick``: enqueue start to results ready of the decode
      programs (``decode_step``, ``decode_window_*``), per tick they ran.
    * ``prefill_rows``, ``prefill_live_rows``: summed over ``prefill_step``.
    * ``pages_walked``, ``pages_table``: summed over the decode programs.
    * ``prior_pages_walked``, ``prior_pages_table``: summed over
      ``prefill_step``.
    * ``expert_rows``, ``expert_rows_routed``: summed over every program;
      ``decode_s``, ``decode_expert_rows_routed``: the decode programs'
      enqueue-to-ready seconds and routed rows.
    """
    window_s = t1 - t0
    steps = [(r.t0, r.t1) for r in records if isinstance(r, Step)]
    runs = sorted((r for r in records if isinstance(r, Dispatch)), key=_start)
    phases = ("upload", "dispatch", "wait", "readback", "drain")
    spans: Dict[str, List[Span]] = {
        p: [getattr(r, p) for r in runs if getattr(r, p) is not None] for p in phases
    }
    phase_s = {p: _overlap(spans[p], t0, t1) for p in phases}
    in_steps = _overlap(steps, t0, t1)
    phase_s["schedule"] = in_steps - sum(phase_s.values())
    phase_s["caller"] = window_s - in_steps

    waited = [r for r in runs if r.wait is not None]
    edges = [t0] + [x for r in waited for x in (r.dispatch[1], r.wait[1])] + [t1]
    gaps = [(max(a, t0), min(b, t1)) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    split = {p: sum(_overlap(spans[p], a, b) for a, b in gaps)
             for p in ("readback", "drain", "upload", "dispatch")}
    gap_s = sum(b - a for a, b in gaps)
    split["caller"] = gap_s - sum(_overlap(steps, a, b) for a, b in gaps)
    split["schedule"] = gap_s - sum(split.values())

    readbacks = [(r.readback[1] - r.readback[0], r.program) for r in waited]
    decode = [r for r in waited if _is_decode(r.program)]
    ticks = sum(r.ticks for r in decode)
    decode_s = sum(r.wait[1] - r.dispatch[0] for r in decode)
    prefill = [r for r in runs if r.program == "prefill_step"]
    return {
        "window_s": window_s,
        "dispatches": len(runs),
        "phase_s": phase_s,
        "host_gap_s": gap_s,
        "host_gap_split": split,
        "longest_readback": list(max(readbacks)) if readbacks else None,
        "decode_ms_per_tick": 1e3 * decode_s / ticks if ticks else None,
        "prefill_rows": sum(r.rows for r in prefill),
        "prefill_live_rows": sum(r.live_rows for r in prefill),
        "pages_walked": sum(r.pages_walked for r in decode),
        "pages_table": sum(r.pages_table for r in decode),
        "prior_pages_walked": sum(r.prior_pages_walked for r in prefill),
        "prior_pages_table": sum(r.prior_pages_table for r in prefill),
        "expert_rows": sum(r.expert_rows for r in runs),
        "expert_rows_routed": sum(r.expert_rows_routed for r in runs),
        "decode_s": decode_s,
        "decode_expert_rows_routed": sum(r.expert_rows_routed for r in decode),
    }


def report(t0: float, t1: float) -> Optional[dict]:
    """``summary`` of ``LOG`` over ``[t0, t1]``; None where ``window`` is."""
    recs = window(t0, t1)
    return None if recs is None else summary(recs, t0, t1)
