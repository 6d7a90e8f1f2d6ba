"""Window seconds over the step() calls that began in it: the mean wall
time of a scheduler iteration, its waits included."""


def read(ctx):
    return 1e3 * ctx['seconds'] / len(ctx['steps']) if ctx['steps'] else None
