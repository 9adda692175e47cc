"""Test harness configuration.

Requests the CPU backend with 8 virtual devices BEFORE any jax computation,
so the multi-device sharding tests run without TPU hardware — the standard
JAX "multi-node tests without a cluster" pattern (SURVEY.md §4).  The
tier-1 command also sets ``JAX_PLATFORMS=cpu``; the in-process request is
what provides the 8 devices, and works because pytest imports this conftest
before any test module touches a device.
"""

import os

# XLA compile time dominates this suite (dozens of engine builds, each a
# fresh closure jax's in-memory cache can't reuse).  The persistent cache
# keys on HLO, so identical programs ACROSS tests and across runs load
# from disk instead of recompiling.  The suite keeps ONE fixed directory of
# its own, OUTSIDE the checkout (a tier-1 run writes ~850 MB of XLA:CPU
# entries, and the chip tool copies the checkout as it stands), placed the
# way any cache is placed — through JAX_COMPILATION_CACHE_DIR, set before
# jax is imported so this process AND every process the tests spawn
# (servers, elastic hosts, stage children) agree on it.  A value already in
# the environment wins.  Opt out with ESTORCH_TEST_NO_CACHE=1 (e.g. when
# hunting a miscompile).
_CACHE = not os.environ.get("ESTORCH_TEST_NO_CACHE")
if _CACHE:
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "estorch_tpu",
                     "test_xla_cache"))

# importing estorch_tpu/jax here does not initialize a backend, so the
# config still takes effect
from estorch_tpu.utils import force_cpu_backend  # noqa: E402

force_cpu_backend(8)

import jax  # noqa: E402

if _CACHE:
    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

import contextlib  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture(params=["gathered", "split"])
def centre_form(request, monkeypatch):
    """Both layouts of the centre the sharded engine's perturbed form
    reads, for engines built on a mesh whose ``model`` axis is wider than
    1.  The rule (``parallel/sharded.py::centre_form_why``) reads a chip's
    memory from ``CHIP_MEMORY_BYTES`` wherever the platform reports none,
    as on the suite's CPU devices: a chip with no room keeps the centre
    split; the small trees of the suite fit any other."""
    from estorch_tpu.parallel import sharded

    if request.param == "split":
        monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 0)
    return request.param


def collectives(compiled_text) -> list:
    """``[(kind, dtype, shape)]`` of every collective in the text of a
    compiled program or of one of its computations (the first array of a
    collective that moves a tuple)."""
    import re

    return [(kind, dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims, kind in re.findall(
                r"= \(?(\w+)\[([\d,]*)\]\S* (all-gather|all-reduce|"
                r"reduce-scatter|collective-permute|all-to-all)"
                r"(?:-start)?\(", compiled_text)]


def materialised(es):
    """``es`` with its engine rebuilt WITHOUT a decomposed_apply: the
    materialized-weights path, which no public option selects for a mirrored
    MLP.  The state is engine-agnostic and carries over."""
    from estorch_tpu.parallel.engine import ESEngine

    es.engine = ESEngine(es.env, es._policy_apply, es._spec, es.table,
                         es.optimizer, es.config, es.mesh)
    es.engine.telemetry = es.obs
    assert es.engine.forward_form == "materialised"
    return es


@pytest.fixture
def dma_gather(monkeypatch):
    """``with dma_gather():`` — engines built inside resolve
    ``noise_gather_form == "dma"`` on the suite's CPU mesh, where the rule
    says "slice"; ``_pallas_interpret`` comes from the mesh, so the row
    kernels of ops/pallas_noise.py run under the Pallas interpreter.  A
    fake substituted by the test: nothing in the package reads it."""
    from estorch_tpu.parallel.engine import ESEngine

    @contextlib.contextmanager
    def forced():
        with monkeypatch.context() as m:
            m.setattr(ESEngine, "_resolve_noise_gather_form",
                      lambda self: "dma")
            yield

    return forced


@pytest.fixture
def tiny_widths(monkeypatch):
    """Inside a ``kernel_scope`` a call of ``lm_blocks.attention_core``
    takes the kernel at ANY widths: the suite's tiny models have heads of 8
    and sequences of 16, which the call's own rule
    (``pallas_attention.fits``: Mosaic's 128-lane column blocks) turns
    away and the Pallas interpreter runs.  The rule said of a MODEL
    (``attention_form_why``) is not touched.  A fake substituted by the
    test: nothing in the package reads it."""
    from estorch_tpu.ops import pallas_attention

    monkeypatch.setattr(pallas_attention, "fits", lambda *shapes: True)


@pytest.fixture
def as_tpu(monkeypatch):
    """``with as_tpu():`` — sharded engines built inside hand their
    kernels' rules the scope of a mesh of TPU devices
    (``ShardedESEngine._build_scope``: the platform, and with it where
    Mosaic kernels may be traced) from the suite's CPU mesh as it is: its
    size, the centre's form, the shapes.  ``_pallas_interpret`` still comes
    from the mesh, so what the rules admit runs under the Pallas
    interpreter.  A fake substituted by the test: nothing in the package
    reads it."""
    from estorch_tpu.ops.pallas_attention import traced_why
    from estorch_tpu.parallel.sharded import ShardedESEngine

    observed = ShardedESEngine._build_scope

    def on_tpu(self):
        scope = observed(self)
        return scope._replace(platform="tpu", traced=traced_why(
            "tpu", scope.n_devices, scope.centre_form))

    @contextlib.contextmanager
    def forced():
        with monkeypatch.context() as m:
            m.setattr(ShardedESEngine, "_build_scope", on_tpu)
            yield

    return forced


@pytest.fixture
def kernel_attention(monkeypatch, tiny_widths):
    """``with kernel_attention():`` — sharded engines built inside open
    their ``kernel_scope`` on the suite's CPU mesh, where the rule says no
    kernel may be traced, and report ``attention_form == "kernel"`` at the
    tiny widths of the suite's models (the rule's conditions on the shapes
    are waived, as ``tiny_widths`` waives them for the calls, which then
    take the kernel); ``_pallas_interpret`` comes from the mesh, so the
    kernels run under the Pallas interpreter.  The next-token head's, the
    scan's, the combine's and the delta rule's forms follow from their own
    shapes (ops/pallas_head.py, ops/pallas_scan.py, ...).  A fake
    substituted by the test: nothing in the package reads it."""
    from estorch_tpu.ops import pallas_attention
    from estorch_tpu.parallel.sharded import ShardedESEngine

    observed = ShardedESEngine._build_scope

    @contextlib.contextmanager
    def forced():
        with monkeypatch.context() as m:
            m.setattr(ShardedESEngine, "_build_scope",
                      lambda self: observed(self)._replace(
                          traced=(True, "forced by the test")))
            m.setattr(pallas_attention, "_shape_failures",
                      lambda *shapes: [])
            yield

    return forced
