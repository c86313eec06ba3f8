"""Device time per step of the class ``other`` ops in the program's
``dps.acts`` scope: the forward activation taps' quantize and stats, their
recomputation under remat included.  A fusion takes the scope most of its
instructions carry (``bench/scopes.py``).
"""

from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "dps.acts")
