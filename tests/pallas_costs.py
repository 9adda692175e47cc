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


def declared_costs(f, *args) -> list:
    """The ``CostEstimate`` of every ``pallas_call`` in ``f(*args)``'s
    jaxpr."""
    return [eqn.params["cost_estimate"] for eqn in pallas_calls(f, *args)]
