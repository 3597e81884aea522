"""Client-side back-off for the fleet's admission shedding.

The router's ``submit()`` raises :class:`~paddle_tpu.serving.router.
RetryAfter` (with a ``retry_after_s`` hint) instead of queueing past its
SLO watermarks — and until now every caller re-implemented the retry
loop around it (the chaos benches, the ``__main__`` CLI, ad-hoc tests).
:func:`backoff_submit` is the one shared implementation: honor the
hint, jitter it deterministically (a thundering herd of clients all
waking at exactly ``retry_after_s`` re-creates the overload the shed
was protecting against), cap the wait, bound the attempts, and count
every back-off so shed pressure is visible client-side too
(``client_backoffs``).

Jitter is a pure function of ``seed`` — the same seed replays the same
wait sequence, which is what lets a chaos test assert byte-identical
tokens across runs that both hit shedding.
"""

from __future__ import annotations

import random
import time


def backoff_submit(router, prompt, max_new_tokens: int | None = None,
                   temperature: float = 0.0, ttl_s: float | None = None,
                   *, attempts: int = 16, max_backoff_s: float = 2.0,
                   jitter: float = 0.25, seed: int = 0, wait=None,
                   sleep=time.sleep) -> int:
    """Submit one request, backing off on :class:`RetryAfter`.

    Each shed waits ``min(retry_after_s * j, max_backoff_s)`` where
    ``j`` is a deterministic ±``jitter`` factor drawn from ``seed``,
    then retries — up to ``attempts`` total submits, after which the
    last :class:`RetryAfter` propagates (the fleet is genuinely
    saturated; the caller decides what that means).

    ``wait`` (preferred over ``sleep`` when given) receives the delay
    in seconds: a synchronous driver passes a pump-the-router-for-this-
    long callable — with nobody pumping, the shed condition it is
    waiting out could never clear.  Returns the fleet request id."""
    from paddle_tpu.serving.router import RetryAfter
    from paddle_tpu.telemetry import safe_inc

    rnd = random.Random(f"{seed}/backoff_submit")
    last: RetryAfter | None = None
    for _ in range(max(1, int(attempts))):
        try:
            return router.submit(prompt, max_new_tokens=max_new_tokens,
                                 temperature=temperature, ttl_s=ttl_s)
        except RetryAfter as e:
            last = e
            j = 1.0 + jitter * (2.0 * rnd.random() - 1.0)
            delay = min(max(e.retry_after_s, 0.0) * j,
                        float(max_backoff_s))
            safe_inc("client_backoffs",
                     "submits delayed by RetryAfter shedding",
                     registry=getattr(router, "registry", None))
            (wait if wait is not None else sleep)(delay)
    assert last is not None
    raise last
