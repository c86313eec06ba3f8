"""Device time per step of the class ``other`` ops in the program's
``dps.grads`` scope: the backward taps and the quantization of the
parameter gradients before the optimizer.  A fusion takes the scope most of
its instructions carry (``bench/scopes.py``).
"""

from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "dps.grads")
