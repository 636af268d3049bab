"""Time to solution: the window over the solves completed in it."""


def read(facts):
    return facts["solve_s"]
