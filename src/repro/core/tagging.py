"""Trace-time markers for the precision-flow verifier.

``dps_tag`` is an **identity primitive**: at runtime it is a no-op (the
MLIR lowering forwards its operand, so nothing reaches the compiled HLO),
but it survives into the jaxpr, where ``repro.analysis.flow`` reads its
parameters to learn — from *declarations, not guesses* — where quantized
values enter and leave the wire pipeline:

    kind="encode_in"     the fp32 value about to be wire-quantized
    kind="decode_out"    the fp32 value a wire decode just produced
    kind="wire_payload"  the int8 buffer about to enter a collective
    kind="wire_stats"    QuantStats fields a wire leg measured
    kind="sr_bits"       the uniform-bits operand of a stochastic encode
    kind="stats_sink"    a stream a controller is about to consume
    kind="wire_bucket"   a bucketed-wire landmark (repro.dist.overlap):
                         stage="grad" where a bucket's gradient leaf
                         materializes in the backward, stage="ready" on
                         the raw leaf handed to the wire, stage="mean"
                         on the decoded bucket mean — with bucket=b,
                         n=<bucket count> (and leaf=g for per-leaf
                         stages)

Each tag carries the precision ``domain`` it belongs to (taken from the
ambient :func:`domain` context when not given explicitly) plus arbitrary
hashable metadata.  The analyzer taint-propagates from these markers; see
``src/repro/analysis/README.md`` for the rules built on them.

The primitive is registered with identity JVP/transpose/batching rules so
tagged values differentiate and vmap exactly like untagged ones, and the
abstract eval is the identity, so tracing semantics are unchanged.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator, Optional, Tuple

import jax
from jax.extend import core as jax_core
from jax.interpreters import ad, batching, mlir

TAG_PRIMITIVE_NAME = "dps_tag"

dps_tag_p = jax_core.Primitive(TAG_PRIMITIVE_NAME)
dps_tag_p.def_impl(lambda x, **params: x)
dps_tag_p.def_abstract_eval(lambda x, **params: x)

# lowering: forward the operand — the tag never reaches HLO
mlir.register_lowering(dps_tag_p, lambda ctx, x, **params: [x])

# vmap: the tag applies to the batched value unchanged
batching.defvectorized(dps_tag_p)

# JVP: the tangent of a tagged value is the (untagged) tangent; the tag
# is a statement about the primal's role in the wire pipeline.
ad.defjvp(dps_tag_p, lambda g, x, **params: g)
ad.primitive_transposes[dps_tag_p] = lambda ct, x, **params: [ct]


# ---------------------------------------------------------------------------
# Ambient domain context: collectives enter ``with tagging.domain(name)``
# so every tag below them resolves its precision domain without threading
# the name through each helper.
# ---------------------------------------------------------------------------

_DOMAIN_STACK: list = []


@contextlib.contextmanager
def domain(name: str) -> Iterator[None]:
    """Trace-time context: tags bound inside resolve ``domain=name``."""
    _DOMAIN_STACK.append(name)
    try:
        yield
    finally:
        _DOMAIN_STACK.pop()


def current_domain() -> Optional[str]:
    return _DOMAIN_STACK[-1] if _DOMAIN_STACK else None


def _freeze_meta(meta: dict) -> Tuple[Tuple[str, Any], ...]:
    frozen = tuple(sorted(meta.items()))
    for _, v in frozen:
        hash(v)   # params live in the jaxpr: hashable only
    return frozen


def tag(x, kind: str, **meta):
    """Mark ``x`` with ``kind`` for the precision-flow analyzer.

    Identity at runtime.  ``domain`` defaults to the ambient
    :func:`domain` context; any extra keyword metadata must be hashable
    (it is stored as jaxpr equation parameters).
    """
    meta.setdefault("domain", current_domain())
    return dps_tag_p.bind(x, kind=kind, meta=_freeze_meta(meta))


def tag_tree(tree, kind: str, **meta):
    """:func:`tag` every array leaf of a pytree."""
    return jax.tree.map(lambda leaf: tag(leaf, kind, **meta), tree)


def tag_params(eqn_params: dict) -> Optional[dict]:
    """Decode a jaxpr equation's tag parameters, or None if ``eqn_params``
    is not from a ``dps_tag`` equation.  Returns {"kind": ..., **meta}."""
    if "kind" not in eqn_params or "meta" not in eqn_params:
        return None
    out = {"kind": eqn_params["kind"]}
    out.update(dict(eqn_params["meta"]))
    return out
