"""Share (%) of the time the harness spent inside ``engine.step`` in the
traced window in which the device ran nothing and the innermost span open
was the building or pushing of a step's inputs (``ps.chunk.build``,
``ps.decode.build``, ``ps.decode.push``)."""


def read(run):
    split = run.notes.get("host_split")
    return None if split is None else split["host.prep_idle_share"]
