"""Closed loop: each of `clients` clients sends its next operation as soon
as its last one is answered (a decode batch, a worker pool)."""

from __future__ import annotations

import time

from chipbench.stream import INSERT, Record


def run(env) -> None:
    """Drive `env.service`'s engine for `env.seconds`, then drain."""
    eng, clock, span, rec = env.engine, env.clock, env.span, env.recorder
    owner: dict[int, int] = {}
    t0 = clock()
    t_end = t0 + env.seconds
    rec.window = (t0, t_end)

    def issue(client: int) -> None:
        while True:
            now = clock()
            if now >= t_end:
                return
            with span("bench.loadgen"):
                op = env.stream.next()
                rid = env.new_id()
            if op.kind == INSERT:
                env.insert(rid, op, now)
                continue
            with span("bench.admit"):
                r = env.request(rid, op)
                rec.add(rid, Record(op, due=now, admitted=now,
                                    visible=env.visible()))
                eng.admit([eng.make_request(r, now=now)])
            owner[rid] = client
            return

    for c in range(int(env.traffic["clients"])):
        issue(c)
    while clock() < t_end:
        with span("bench.pump"):
            eng.pump()
        with span("bench.harvest"):
            closed = rec.finish(eng.take_results(), eng.take_failures(),
                                clock())
        for rid in closed:
            issue(owner.pop(rid))
        if not closed:
            nxt = eng.sched.next_deadline()
            wait = (t_end if nxt is None else min(nxt, t_end)) - clock()
            if wait > 0:
                time.sleep(wait)
    env.drain()
