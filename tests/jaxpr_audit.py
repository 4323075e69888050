"""Shape audit over a jaxpr and every sub-jaxpr (scan and while bodies,
cond branches, pjit and shard_map calls): finds intermediates shaped
like the public logit stack."""
from jax.extend.core import ClosedJaxpr, Jaxpr


def iter_avals(jaxpr):
    """Every intermediate aval in ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v, "aval"):
                yield v.aval
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, ClosedJaxpr):
                    yield from iter_avals(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from iter_avals(sub)


def dense_stack_avals(jaxpr, P, C):
    """Intermediates that hold a public logit stack: last dim C with the
    full public axis P also present (e.g. (n, P, C) or (n, P, S, C))."""
    return [a.shape for a in iter_avals(jaxpr)
            if getattr(a, "shape", ()) and a.shape[-1] == C
            and P in a.shape[:-1]]
