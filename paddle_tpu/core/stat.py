"""Scope timers aggregated into a global stat set — successor of
``paddle/utils/Stat.h:63-242`` (``REGISTER_TIMER*`` / ``globalStat``).

The reference wraps hot scopes in RAII timers compiled out unless WITH_TIMER;
here the equivalent is a context-manager/decorator pair gated by the
``with_timer`` flag.  ``print_all_status`` mirrors the per-pass dump
(``globalStat.printAllStatus()``).  The scopes' place in a TPU profile is the
span tracer's (``telemetry/tracing.py`` mirrors its spans into the profiler's
trace); these timers keep the reference's aggregates only."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

from paddle_tpu.core import flags
from paddle_tpu.core import logger


@dataclasses.dataclass
class StatInfo:
    """Aggregate for one named timer (reference: ``Stat.h`` StatInfo)."""

    total: float = 0.0
    count: int = 0
    max: float = 0.0
    min: float = float("inf")

    def add(self, dt: float) -> None:
        self.total += dt
        self.count += 1
        self.max = max(self.max, dt)
        self.min = min(self.min, dt)

    @property
    def avg(self) -> float:
        return self.total / self.count if self.count else 0.0


class StatSet:
    def __init__(self, name: str = "global"):
        self.name = name
        self.stats: dict[str, StatInfo] = {}

    def add(self, key: str, dt: float) -> None:
        self.stats.setdefault(key, StatInfo()).add(dt)

    def reset(self) -> None:
        self.stats.clear()

    def print_all_status(self) -> None:
        if not self.stats:
            return
        log = logger.get_logger("paddle_tpu.stat")
        log.info("======= StatSet: [%s] status ======", self.name)
        for key, s in sorted(self.stats.items(), key=lambda kv: -kv[1].total):
            log.info(
                "Stat=%-40s total=%.3fms avg=%.3fms max=%.3fms minT=%.3fms count=%d",
                key, s.total * 1e3, s.avg * 1e3, s.max * 1e3,
                (0.0 if s.min == float("inf") else s.min) * 1e3, s.count,
            )


global_stat = StatSet()


@contextlib.contextmanager
def timer(name: str, stat_set: StatSet = global_stat):
    """``with stat.timer("forwardBackward"): ...`` ≅ REGISTER_TIMER_INFO."""
    if not flags.get("with_timer"):
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        stat_set.add(name, time.perf_counter() - t0)


def timed(name: str | None = None):
    """Decorator form of :func:`timer`."""

    def deco(fn):
        key = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with timer(key):
                return fn(*args, **kwargs)

        return wrapper

    return deco
