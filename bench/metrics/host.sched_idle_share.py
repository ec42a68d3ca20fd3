"""Share (%) of the time the harness spent inside ``engine.step`` in the
traced window in which the device ran nothing and the innermost span open
was a phase of the scheduler (``ps.sched.*``)."""


def read(run):
    split = run.notes.get("host_split")
    return None if split is None else split["host.sched_idle_share"]
