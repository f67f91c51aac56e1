"""Device time of one run of the jitted split train step, from the
device trace of the traced slice: the summed duration of the program
the configuration names (``step_program``) over its number of runs."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    runs, seconds = tr["modules"].get(ctx["step_program"], (0, 0.0))
    if not runs:
        return None
    return 1e3 * seconds / runs
