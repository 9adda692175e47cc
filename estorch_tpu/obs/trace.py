"""Device-trace hooks: the one vocabulary of stages inside a generation
program, and the host's spans on the profiler's clock.

Span telemetry (obs/spans.py) answers "which phase got slower" for free
on every run; a ``jax.profiler`` trace is the heavyweight next step when
a phase needs opening up.  Four hooks make such a trace readable:

- ``trace(logdir)``: context manager around ``jax.profiler`` producing a
  Perfetto/XPlane trace of the compiled generation programs;
- ``stage(name)``: the name-stack scope ``es.<name>`` for one of
  ``STAGES``.  The engines wrap every stage of a generation in one, so
  each operation of the compiled program carries its stage in its
  ``op_name`` (and, on a TPU, in the ``tf_op`` of its trace events).
  Scopes are metadata only: the executable's instructions and the
  compile-cache key do not change.  An operation belongs to the INNERMOST
  ``es.<stage>`` of its name stack; a fusion to the stage of its root;
- ``part(name)``: the name-stack scope ``of.<name>`` beneath a stage, for
  the parameter leaf an operation multiplies.  The name IS the leaf's key
  in the model's parameter tree (``gate``, ``q``, ``in_x``, ``kv_b``,
  ``head``, ``embed`` ...), the word ``parallel/mesh.py``'s partition
  rules and ``ops/lowrank.py``'s specs already use: no second list to keep
  in step with the models.  Parts nest into a path where one key serves
  two places the caller can tell apart (``of.shared/.../of.gate`` reads
  ``shared.gate``).  A part is no stage: ``of.`` never matches a stage
  scope, so every operation books to the stage it booked to before, and a
  correction the compiler did not fuse into its projection reads
  ``.../es.dense/of.gate/es.perturb``.  A new projection gets its part by
  going through ``models/lm_blocks.dense``;
- ``annotate(name, **ids)``: ``jax.profiler.TraceAnnotation``, a host
  span in the same trace.  Every ``Telemetry.phase`` enters one, so
  ``dispatch``/``device``/``host_sync``/``record`` sit on a ``/host:CPU``
  line beside the device operations, with true starts and ends.

A trace shows the metadata of the executable that RAN: the persistent
compile cache keys programs without their metadata, so an entry written
before a scope existed is served without it.  Profile on a cache
directory of its own, or with
``jax_compilation_cache_include_metadata_in_key`` on
(docs/observability.md, "Stages inside the generation program").
"""

from __future__ import annotations

import contextlib
import re

SCOPE_PREFIX = "es."
# a part's scope: a prefix of its own, which no stage scope can match
PART_PREFIX = "of."
_PART_NAME = re.compile(r"[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")

# the stages of one generation, in program order (docs/observability.md)
STAGES = (SAMPLE, NOISE, PERTURB, POLICY, ENV, GATHER, RANK, GRAD, UPDATE,
          DENSE, SSM, ATTN, HEAD, ROPE, EXIT, ROUTE, DISPATCH, EXPERT, GMU,
          DIFF, INDEX, SELECT, MIX) = (
    "sample",    # offsets, signs, member keys
    "noise",     # reading eps: the table gather and the slab it builds
    "perturb",   # theta + sigma * sign * eps, unravel, cast; the rank-r
                 # corrections of the perturbed-dense primitive
    "policy",    # obs normalisation, the forward, select_action
    "env",       # reset, physics, done masking, reward/step accumulation
    "gather",    # collectives
    "rank",      # centered ranks
    "grad",      # the second pass over the noise, the weighted sum
    "update",    # weight decay, optax step, sigma decay, obs-norm probe
    # nested inside es.policy by a sequence model (models/hybrid_lm.py,
    # models/looped_lm.py, models/moe_lm.py, models/sambay_lm.py,
    # models/indexed_moe_lm.py, models/cca_moe_lm.py,
    # models/window_moe_lm.py, models/delta_moe_lm.py,
    # models/gated_window_moe_lm.py on the pieces of models/lm_blocks.py)
    "dense",     # the shared x@W projections and the gated FFN (a gate a
                 # head on the attention's context is the part of.head_gate:
                 # its projection, the sigmoid and the product)
    "ssm",       # conv1d, dt and decay, the scan (Mamba-2's chunked form,
                 # Mamba-1's selective one), the gate; the gated delta rule
                 # names its parts: of.conv, of.decay (beta, g, the L2
                 # norms), of.solve (K Kt, the triangular inverse, W, U),
                 # of.carry (the chain over chunks: V', O, S'), of.gate (the
                 # norm gated by silu(z))
    "attn",      # scores, softmax, P.V (a model with several kinds of
                 # attention names each a part: of.window, of.full, of.cross;
                 # attention over a selection of keys: of.selected;
                 # rotary banded layers beside position-free full ones:
                 # of.window, of.global; banded and full layers of different
                 # head counts: of.sliding, of.full)
    "head",      # the logits (tied or not), log-softmax, the score
    "rope",      # rotary positions: cos/sin (one table a kind of layer
                 # where the kinds differ in theta, width or scaling),
                 # rotating queries and keys
    "exit",      # a looped model's exit gate, the exit distribution and
                 # the weighting of the per-pass scores
    "route",     # an expert layer's router: its matmul, sigmoid, selection
                 # bias, top-k and the renormalised weights (a router that
                 # is an MLP over a carried state names its parts:
                 # of.router_down, of.router_state, of.router_mlp; a router
                 # that reads the layer's input sits AHEAD of es.attn)
    "dispatch",  # sorting (token, k) pairs by held expert, the gather into
                 # expert order and the weighted combine back
    "expert",    # the grouped matmuls over the routed rows and the
                 # experts' gated activation
    "gmu",       # a gated memory unit's gate product silu(u W1) * m, m the
                 # scan output an earlier layer handed on (its two
                 # projections are es.dense parts gmu_in, gmu_out)
    "diff",      # differential attention's combine: lambda, A1 v - lambda
                 # A2 v, the norm over a head pair's values, the scale
    "index",     # a sparse-attention indexer: its three projections (parts
                 # index_q, index_k, index_w), the key's norm, the score
                 # product sum_j w_j relu(q_j . k) and its causal mask
    "select",    # the choice of the topk largest index scores a query (the
                 # bisection on the k-th largest, the ties) and the write of
                 # the [T, T] selection the attention reads
    "mix",       # what attention in a compressed latent does to q, k and v
                 # between their projections and the scores (parts
                 # conv_time, conv_head, qk_mean, value_shift, qk_norm): the
                 # two causal convolutions over q and k, the q-k mean, the
                 # values' shift by one position, the L2 scale under the
                 # learned temperature
)


def stage(name: str):
    """Name-stack scope of one generation stage; a context manager and a
    decorator.  Trace-time only: nothing runs per call of the program."""
    import jax

    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; the stages are {STAGES}")
    return jax.named_scope(SCOPE_PREFIX + name)


def part(name: str):
    """Name-stack scope ``of.<name>`` of the parameter leaf the operations
    inside multiply (or prepare the operand of); entered inside a stage.
    ``name`` is the leaf's key, words of letters, digits and ``_`` joined
    by ``.``.  Trace-time only, as :func:`stage`; this module stays the
    one caller of ``jax.named_scope``."""
    import jax

    if not _PART_NAME.fullmatch(name):
        raise ValueError(f"a part is named by a parameter leaf's key, words "
                         f"joined by '.'; got {name!r}")
    return jax.named_scope(PART_PREFIX + name)


@contextlib.contextmanager
def trace(logdir: str):
    """jax.profiler trace of everything inside the with-block."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **ids):
    """Host span visible in device traces; ``ids`` (e.g. ``generation=``)
    ride along as the event's stats.  About a microsecond while no
    profiler runs."""
    import jax

    return jax.profiler.TraceAnnotation(name, **ids)
