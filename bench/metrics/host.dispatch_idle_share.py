"""Share (%) of the time the harness spent inside ``engine.step`` in the
traced window in which the device ran nothing and the innermost span open
was a program's dispatch or the wait for its results
(``ps.run.<program>``, ``ps.chunk.wait``, ``ps.decode.readback``)."""


def read(run):
    split = run.notes.get("host_split")
    return None if split is None else split["host.dispatch_idle_share"]
