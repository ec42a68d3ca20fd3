"""Programs compiled or loaded from the persistent compile cache before the
window opened: weights, the system prompt, the step shapes made ready and
the warm-up traffic. A program that compiles fewer shapes needs fewer."""


def read(run):
    return float(run.notes["programs_setup"])
