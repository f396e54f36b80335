"""Walking a traced program (``jax.make_jaxpr`` output) for the structure
tests of the paged decode kernel."""


def eqns(jaxpr, outer=()):
    """Every equation of a traced program, sub-jaxprs included, with the
    names of the primitives that enclose it."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn, outer
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from eqns(sub, outer + (eqn.primitive.name,))


def paged_kernel_calls(jaxpr):
    """(page-table shape, enclosing primitives) of each paged decode kernel
    call: the table is the kernel's first operand."""
    return [(eqn.invars[0].aval.shape, outer)
            for eqn, outer in eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.params["jaxpr"].debug_info.func_name
            == "_paged_decode_kernel"]
