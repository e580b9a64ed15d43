"""Open loop: requests arrive on a schedule from the mix (bursts at a fixed
rate) whether or not earlier ones were answered (independent users).
Latency runs from each request's due time, so a stall also charges the
requests that queued behind it; `env.lateness` records how late the
generator handed each request in."""

from __future__ import annotations

import time

from chipbench.stream import INSERT, Record, arrival_offsets


def run(env) -> None:
    """Offer the mix's arrivals for `env.seconds`, then drain."""
    eng, clock, span, rec = env.engine, env.clock, env.span, env.recorder
    offsets = arrival_offsets(env.traffic, env.seconds, env.seed)
    t0 = clock()
    t_end = t0 + env.seconds
    rec.window = (t0, t_end)
    i = 0
    while clock() < t_end:
        now = clock()
        batch = []
        with span("bench.loadgen"):
            while i < len(offsets) and t0 + offsets[i] <= now:
                due = t0 + float(offsets[i])
                i += 1
                op = env.stream.next()
                rid = env.new_id()
                if op.kind == INSERT:
                    env.insert(rid, op, due)
                    continue
                rec.add(rid, Record(op, due=due, admitted=now,
                                    visible=env.visible()))
                batch.append((env.request(rid, op), due))
        if batch:
            with span("bench.admit"):
                eng.admit([eng.make_request(r, now=due) for r, due in batch])
        with span("bench.pump"):
            eng.pump()
        with span("bench.harvest"):
            rec.finish(eng.take_results(), eng.take_failures(), clock())
        nxt = [t0 + float(offsets[i])] if i < len(offsets) else []
        dl = eng.sched.next_deadline()
        if dl is not None:
            nxt.append(dl)
        wait = min(nxt + [t_end]) - clock()
        if wait > 0:
            time.sleep(wait)
    env.drain()
