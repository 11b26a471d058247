"""Mean forward-solve iterations per train step in the window, from the
step's own ``deq_steps`` metric."""


def read(run):
    steps = [s for s in run.counters.get("deq_steps", []) if s >= 0]
    return sum(steps) / len(steps) if steps else None
