"""Program spans: named host intervals on the profiler's clock, with their
seconds and counts kept per engine run.

``span(name, phases)`` enters ``jax.profiler.TraceAnnotation(name)``, so a
recorded profile holds the interval in its host plane, on the clock of the
device's ``XLA Ops``, and on exit adds the interval's ``perf_counter``
length and one count to ``phases``: the :class:`Phases` of the engine run,
which the staging producer thread adds to as well.  Nothing else is kept;
with no profile recording a span costs a few microseconds.  Every program
span is named ``repro.<part>``; docs/ENGINES.md ("Reading a run") lists
them and what each covers.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax


class Phases:
    """Seconds and counts per span name, added to from any thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._acc: Dict[str, List] = {}

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            acc = self._acc.setdefault(name, [0.0, 0])
            acc[0] += seconds
            acc[1] += 1

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"s": seconds, "n": count}}``."""
        with self._lock:
            return {k: {"s": s, "n": n} for k, (s, n) in self._acc.items()}


def phase_shares(stats: Dict[str, Dict[str, float]]) -> List[tuple]:
    """``(name, percent)`` of each span of ``last_run_stats`` against the
    run's ``repro.train``, largest first.  The staging producer's spans run
    beside the others, so the shares need not add up to 100."""
    total = stats.get("repro.train", {}).get("s", 0.0)
    if total <= 0:
        return []
    return sorted(((k, 100.0 * v["s"] / total) for k, v in stats.items()
                   if k != "repro.train"), key=lambda kv: -kv[1])


class span:
    """``with span(name, phases) as sp:`` -- one program span; ``sp.seconds``
    holds its length once it has closed.  ``phases=None`` only annotates."""

    __slots__ = ("name", "phases", "seconds", "_t0", "_ann")

    def __init__(self, name: str, phases: Optional[Phases] = None):
        self.name = name
        self.phases = phases
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self.phases is not None:
            self.phases.add(self.name, self.seconds)
