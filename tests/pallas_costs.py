"""The ``pallas_call``s of a traced function, and what they declare to XLA
(their ``cost_estimate``), read from the jaxpr: shared by the kernels'
tests."""

import jax


def pallas_calls(f, *args) -> list:
    """Every ``pallas_call`` equation in ``f(*args)``'s jaxpr, in program
    order, through whatever ``jit`` / ``vmap`` / ``custom_vmap`` wrap it."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
                continue
            for value in eqn.params.values():
                if hasattr(value, "eqns"):
                    walk(value)
                elif hasattr(getattr(value, "jaxpr", None), "eqns"):
                    walk(value.jaxpr)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


def primitive_names(jaxpr) -> list:
    """The primitives of ``jaxpr``'s equations in program order, those of
    every jaxpr an equation carries (a ``cond``'s branches, a ``jit``'s
    body) after its own."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    names += primitive_names(inner)
    return names


def declared_costs(f, *args) -> list:
    """The ``CostEstimate`` of every ``pallas_call`` in ``f(*args)``'s
    jaxpr."""
    return [eqn.params["cost_estimate"] for eqn in pallas_calls(f, *args)]
