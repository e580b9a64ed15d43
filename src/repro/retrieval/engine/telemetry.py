"""The engine's host-time ledger: where each engine call's host time goes,
and what state the two-stage pipeline was in meanwhile.

Both are seconds on the engine's clock, added into flat keys of
`default_stats()`, so `service.stats` carries them and a reader takes
their deltas over a window (no profiler needed).

**Stages** (`STAGE_KEYS`). Each `admit`, `pump` and `drain` call (so
`serve` and `close` too) is cut at the clock stamps where a stage opens
or closes,
and each stretch between two stamps is charged to the innermost stage
open across it: no gaps and no overlaps, so a call's stages sum to its
time exactly. A stage's time leaves out the stages nested in it
(`eng_s_collect` leaves out `eng_s_collect_wait`). `eng_s_other` is what
`pump` and `drain` spend outside every named stage: `_maintain`, the
fault boundaries, recovery and loop overhead. A named stage opens the
`jax.profiler.TraceAnnotation` of its name (`engine.collect.wait` counts
into `eng_s_collect_wait`), tagged `wave=` and `rows=`, around the same
code, so a span and its counter always bracket the same work. Outside a
profiling session the annotation does nothing.

**Pipeline states** (`STATE_KEYS`). From the first admission on, the
engine's time is cut where its state changes, at stamps the stages
already take, into

    inflight       a wave is dispatched and not yet collected: from the
                   end of a stage-A dispatch to the end of the call that
                   collects the last wave in flight
    idle_no_work   nothing in flight and nothing queued: the caller's turn
    idle_held      nothing in flight, requests queued, no flush due yet:
                   the fill or deadline wait, and further admissions
    idle_dispatch  from the poll that flushes to the end of the first
                   stage-A dispatch: poll, make_waves, stage A's host work

so their sum over a window is its time up to the last stamp. A warm-up
stops the states (it is no traffic); the next admission starts them.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

SPANS = ("engine.admit", "engine.poll", "engine.make_waves",
         "engine.dispatch_search", "engine.dispatch_finish", "engine.collect",
         "engine.collect.wait")


def stage_key(name: str) -> str:
    """The counter of a span: `engine.collect.wait` -> eng_s_collect_wait."""
    return "eng_s_" + name.removeprefix("engine.").replace(".", "_")


_KEY = {name: stage_key(name) for name in SPANS}
OTHER = "eng_s_other"
STAGE_KEYS = (*_KEY.values(), OTHER)

INFLIGHT = "inflight"
NO_WORK = "idle_no_work"
HELD = "idle_held"
DISPATCH = "idle_dispatch"
STATE_KEYS = tuple("eng_s_" + s for s in (INFLIGHT, NO_WORK, HELD, DISPATCH))


class HostLedger:
    """Stage and state clocks of one engine (`owner`: its `clock` and
    its current `stats` dict, read at every stamp, so a warm-up's
    throwaway dict takes the warm-up's time)."""

    def __init__(self, owner):
        self._owner = owner
        self._open: list[str] = []  # keys of the open stages, innermost last
        self._t = 0.0               # the last stamp
        self.state: str | None = None  # None: stopped (no admission yet)
        self._state_t = 0.0

    def _stamp(self) -> float:
        t = self._owner.clock()
        if self._open:
            self._owner.stats[self._open[-1]] += t - self._t
        self._t = t
        return t

    def stage(self, name: str, wave: int, rows: int) -> _Frame:
        """A named stage (one of `SPANS`): its span and its counter around
        one block; entering it gives the stage's first stamp."""
        return _Frame(self, _KEY[name],
                      TraceAnnotation(name, wave=wave, rows=rows), None)

    def call(self, rest) -> _Frame:
        """A `pump` or `drain` call: time outside its named stages goes
        to `eng_s_other`; on the way out the state becomes `rest()`."""
        return _Frame(self, OTHER, None, rest)

    def to(self, state: str, t: float | None = None,
           start: bool = False) -> None:
        """Charge the running state up to `t` (default: the last stamp)
        and switch to `state`. While stopped, only `start` (an
        admission) starts the states."""
        t = self._t if t is None else t
        if self.state is not None:
            self._owner.stats["eng_s_" + self.state] += t - self._state_t
        elif not start:
            return
        self.state, self._state_t = state, t


class _Frame:
    """One stage or call of a `HostLedger`, as a context manager (a class,
    not a generator: the engine opens a few per request)."""

    __slots__ = ("_ledger", "_key", "_span", "_rest")

    def __init__(self, ledger: HostLedger, key: str, span, rest):
        self._ledger, self._key, self._span, self._rest = \
            ledger, key, span, rest

    def __enter__(self) -> float:
        if self._span is not None:
            self._span.__enter__()
        t0 = self._ledger._stamp()
        self._ledger._open.append(self._key)
        return t0

    def __exit__(self, *exc) -> None:
        led = self._ledger
        led._stamp()
        led._open.pop()
        if self._rest is not None:
            led.to(self._rest())
        if self._span is not None:
            self._span.__exit__(*exc)
