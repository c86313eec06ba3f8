"""Device time per step of the class ``other`` ops in the program's
``optim`` scope: the optimizer update (clip norm, moments, weight decay,
the stochastic state cast) and its apply to the parameters.  A fusion takes
the scope most of its instructions carry (``bench/scopes.py``).
"""

from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "optim")
