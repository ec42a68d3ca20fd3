"""Share (%) of the time the harness spent inside ``engine.step`` in the
traced window in which the device ran nothing and the innermost span open
was JAX tracing, lowering or compiling a program
(``tracefile.JAX_COMPILE_EVENTS``)."""


def read(run):
    split = run.notes.get("host_split")
    return None if split is None else split["host.compile_idle_share"]
