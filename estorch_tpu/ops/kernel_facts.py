"""What an engine's build reports of the policy's hand-written kernels, and
the one place that knows which modules have some.

A model names the kernels it calls and the widths it calls them with
(``models/perturbed.py::PolicyDeclaration.kernels``: ``(rule, widths)``
pairs); each rule is a ``*_facts(scope, *widths) -> dict`` of the kernel's
own module, which also lists the names it answers for (``FACTS``).  The
engine resolves them with :func:`resolve` and knows none by name: it says
what only it can observe, ONCE, as a :class:`BuildScope`.  Nothing in a
program depends on the answers (a call site takes its form from the shapes
it is traced with, inside the scope the engine opens:
``pallas_attention.kernel_scope``); they are the run's record of it: gauges
and ``run_manifest()["config"]`` (``parallel/engine.py::build_fact_gauges``,
``build_fact_manifest``).

A new kernel: its module, its ``*_facts`` and ``FACTS``, one more entry of
``_MODULES`` below, and an entry in the ``kernels`` of the models that
call it.
"""

from __future__ import annotations

from typing import NamedTuple

from . import (pallas_attention, pallas_combine, pallas_delta, pallas_head,
               pallas_scan)


class BuildScope(NamedTuple):
    """What only the engine knows when it is built, for the kernels' rules:
    the devices' ``platform`` and how many the mesh has, how the perturbed
    form's centre lies (``parallel/sharded.py::centre_form_why``),
    ``traced`` = ``(may Mosaic kernels be traced in the policy's forward?,
    why)`` (``pallas_attention.traced_why`` of the three before it), the
    sequence length, the compute dtype's item size."""

    platform: str
    n_devices: int
    centre_form: str | None
    traced: tuple[bool, str]
    horizon: int
    itemsize: int


_MODULES = (pallas_attention, pallas_head, pallas_scan, pallas_combine,
            pallas_delta)
# every name a kernel's rule may report: the manifest has each for every
# engine, ``None`` where the policy calls no such kernel
FACT_NAMES = tuple(name for module in _MODULES for name in module.FACTS)
# a rule's reason is a sentence: the manifest carries it, no gauge does
SENTENCES = frozenset(name for name in FACT_NAMES if name.endswith("_why"))


def resolve(scope: BuildScope, kernels) -> dict:
    """``{fact: value}`` of a policy's ``kernels`` under ``scope``: each
    rule's answer, merged in the order the policy names them."""
    facts: dict = {}
    for rule, widths in kernels:
        facts.update(rule(scope, *widths))
    return facts
