"""Device time per step of the class ``other`` ops in the program's
``dps.weights`` scope: the weight snap at step start and the re-snap after
the update (Alg. 1 lines 9 and 19), threefry bits and stats included.  A
fusion takes the scope most of its instructions carry (``bench/scopes.py``).
"""

from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "dps.weights")
