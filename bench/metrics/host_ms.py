"""host_ms.<cell>: median over the device programs of the window of the
host's time in the program's round less the program's ``engine.wait``,
in ms.  A round is the enclosing ``serve.dispatch``, or in direct calls
the ``solver.plan`` + ``solver.execute`` of the call (repro.utils.spans);
a round of k programs gives each of them its host time over k."""

import statistics
from collections import defaultdict

import harness


def _round(s, by_id):
    """The span of ``s``'s round: its ``serve.dispatch``, else its
    ``solver.execute``; None where that started before the window."""
    execute = None
    p = by_id.get(s.parent)
    while p is not None:
        if p.name == "serve.dispatch":
            return p
        if p.name == "solver.execute" and execute is None:
            execute = p
        p = by_id.get(p.parent)
    return execute


def read(run):
    spans = harness.plugin("metrics", "_spans").after(run)
    if not spans:
        return None
    end = run.window_start + run.seconds
    by_id = {s.id: s for s in spans}
    plan_of, last_plan = {}, {}       # execute id -> its call's plan
    for s in spans:                   # by start
        if s.name == "solver.plan":
            last_plan[s.parent] = s
        elif s.name == "solver.execute":
            plan_of[s.id] = last_plan.pop(s.parent, None)
    waits = defaultdict(list)
    for s in spans:
        if s.name == "engine.wait":
            r = _round(s, by_id)
            if r is not None and r.t0 <= end:
                waits[r.id].append(s.seconds)
    out = []
    for rid, w in waits.items():
        r = by_id[rid]
        plan = plan_of.get(rid)
        host = r.seconds + (plan.seconds if plan is not None else 0.0) \
            - sum(w)
        out += [host / len(w)] * len(w)
    return 1e3 * statistics.median(out) if out else None
