"""The sharded ES generation engine — one XLA program per generation.

This is the TPU-native replacement for the reference's entire distributed
runtime (SURVEY.md §3.2: Python per-member loop → MPI gather of fitness →
master-only update → parameter broadcast).  Design, per BASELINE.json's
north star:

- **Population DP over a device mesh**: each device owns a contiguous shard
  of antithetic pairs (layout in parallel/mesh.py).  Inside ``shard_map``,
  a ``lax.scan`` over evaluation chunks × ``vmap`` within a chunk rolls out
  every member's episode on-device (envs/rollout.py).
- **No noise on the wire**: every device derives the SAME pair offsets from
  the replicated ``(key, generation)`` via a counter-based PRNG and slices
  its shard by ``axis_index`` — ε is regenerated locally from the shared
  table (ops/noise.py).
- **One small all_gather + one psum**: fitness (O(population) floats) is
  all-gathered so every device computes identical centered ranks; the
  rank-weighted noise sum is reduced with a single ``lax.psum`` riding ICI.
- **No parameter broadcast**: the psum result — and hence the optax update —
  is bit-identical on every device, so parameters stay replicated by
  construction.  This deletes the reference's broadcast entirely.

Two entry points share all machinery:
  * ``generation_step`` — fused evaluate+rank+update for vanilla ES.
  * ``evaluate`` / ``apply_weights`` — the split path for the novelty family
    (NS/NSR/NSRA), whose rank weights depend on a host-side archive.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..envs.rollout import carry_init_takes_params, make_obs_probe, make_rollout
from ..obs.spans import NULL_TELEMETRY
from ..obs.trace import (GATHER, GRAD, NOISE, PERTURB, RANK, SAMPLE, UPDATE,
                         stage)
from ..ops import kernel_facts
from ..ops.gradient import es_gradient, rank_weighted_noise_sum
from ..ops.noise import NoiseTable, member_offsets, pair_signs, sample_pair_offsets
from ..ops.params import ParamSpec
from ..ops.ranks import centered_rank_safe
from .mesh import POP_AXIS, padded_count, pairs_per_device


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine configuration (hashable; closed over at trace time)."""

    population_size: int
    sigma: float
    horizon: int
    eval_chunk: int = 0  # members per rollout chunk; 0 → whole local shard
    grad_chunk: int = 256  # noise rows (pairs when mirrored, members when
    # not) per gradient-reduction chunk
    weight_decay: float = 0.0  # L2 pull toward 0, applied with the update
    compute_dtype: str = "float32"  # "bfloat16" runs the POLICY forward in
    # bf16 (MXU-native, half the HBM traffic for the per-member weights);
    # params, noise table, env dynamics, and the update stay float32
    sigma_decay: float = 1.0  # per-generation multiplicative σ annealing
    sigma_min: float = 0.0  # σ floor when annealing
    mirrored: bool = True  # antithetic pairs (variance reduction — kept on
    # by default everywhere, incl. the bundled configs). Set False for the
    # reference's plain per-member sampling (supported on all backends).
    episodes_per_member: int = 1  # rollouts averaged per member (device
    # path only): reduces fitness noise AND raises per-step batch (n·e rows
    # through the policy matmuls — better MXU use for small populations)
    low_rank: int = 0  # >0: per-layer kernel noise E = A·Bᵀ/√r with r =
    # low_rank (ops/lowrank.py, PAPERS.md "ES at the Hyperscale"): member
    # noise state shrinks O(dim) → O(Σ(m+n)·r), the forward's noise term
    # O(m·n) → O((m+n)·r) per step, and the update is one einsum per layer
    # over the population.  Approximates isotropic ES (exact for biases).
    obs_norm: bool = False  # running observation normalization (the
    # OpenAI-ES MuJoCo staple the reference never had): every policy input
    # is (obs - mean)·rsqrt(var) clipped to ±obs_clip, with the running
    # raw-obs moments carried in ESState.obs_stats and refreshed each
    # generation from obs_probe_episodes center-policy episodes — fully
    # in-program, replicated on every device. Composes with every noise
    # representation (materialised/recurrent/pair_shared/low_rank):
    # normalization is an input-side transform, applied to raw obs in f32
    # before any forward. NOTE the stats-refresh data source differs by
    # backend: the device path feeds obs_stats from center-policy probe
    # episodes only, while the pooled path folds in every member's
    # (perturbed-policy) observations — both are self-consistent and
    # checkpoint-compatible, but a run migrated across paths resumes with
    # differently-converged normalization statistics.
    obs_clip: float = 5.0  # normalized-obs clip range
    obs_probe_episodes: int = 1  # center episodes per generation feeding
    # the running stats (more → faster stat convergence, more probe FLOPs)
    obs_warmup_episodes: int = 0  # >0: run this many init-policy probe
    # episodes at init_state so generation 0 already normalizes with real
    # moments instead of the identity init (round-4 A/B: the identity
    # init costs early-generation AUC while the stats converge; warmup
    # removes that transient). Device path only — the pooled path's
    # stats are fed by every member's observations from generation 0
    # onward, so its transient is one generation long already.


class ESState(NamedTuple):
    """Replicated across devices; everything needed to resume exactly."""

    params_flat: jax.Array  # (dim,) float32 — center of the search distribution
    opt_state: Any
    key: jax.Array  # PRNG key, folded with generation for per-gen streams
    generation: jax.Array  # () int32
    sigma: jax.Array  # () float32 — current perturbation scale (annealable)
    obs_stats: Any = None  # obs_norm only: (count, mean, m2) running
    # raw-observation moments in Welford form — mean and m2/count stay O(1)
    # magnitude forever, so no f32 cancellation or accumulator saturation
    # however long the run (naive sum/sumsq would cancel catastrophically
    # on dims with |mean| >> std, exactly the locomotion case obs_norm
    # exists for)


def normalize_obs(obs: jax.Array, obs_stats, clip: float) -> jax.Array:
    """(obs − mean)·rsqrt(var), clipped — the obs_norm transform.

    ``obs_stats`` is the (count, mean, m2) Welford triple (var = m2/count);
    variance is floored at 1e-8 so fresh stats (var≈1 at init) and
    constant dimensions stay finite."""
    cnt, mean, m2 = obs_stats
    var = jnp.maximum(m2 / cnt, 1e-8)
    x = (obs.astype(jnp.float32) - mean) * jax.lax.rsqrt(var)
    return jnp.clip(x, -clip, clip)


def merge_obs_moments_np(obs_stats, cnt1: float, osum1, osumsq1):
    """Host-side float64 Chan merge for POOLED-scale raw sums.

    The in-program f32 merge below is safe only for a few episodes' worth
    of samples; the pooled path accumulates population×horizon steps per
    generation, where ``sumsq − sum·mean`` cancels catastrophically in
    f32 (e.g. c≈1e6 at mean≈100: the f32 ulp of sumsq exceeds the true
    m2).  Merge in f64, hand back an f32 jnp triple for the state."""
    import numpy as np

    # Precision bound: the merge itself is f64-exact, but the count is
    # handed back as f32 for the ESState schema, so past 2^24 (~16.7M)
    # samples the STORED count rounds (ulp 2 at 2^25, …).  mean/m2 keep
    # full f64 accuracy — only the count's least bits are lost, a ≤2^-24
    # relative error in the next merge's weights.  At pooled scale
    # (pop 256 × horizon 1000 → 2^24 in ~65 generations) the documented
    # "count == 1 + env_steps" invariant therefore holds exactly only
    # below 2^24 total samples; beyond it the stats keep converging
    # correctly but the count is a rounded f32.
    c0 = float(np.asarray(obs_stats[0]))
    m0 = np.asarray(obs_stats[1], np.float64)
    M0 = np.asarray(obs_stats[2], np.float64)
    c1 = float(cnt1)
    s1 = np.asarray(osum1, np.float64)
    q1 = np.asarray(osumsq1, np.float64)
    mean1 = s1 / c1
    m2_1 = np.maximum(q1 - s1 * mean1, 0.0)
    tot = c0 + c1
    delta = mean1 - m0
    mean = m0 + delta * (c1 / tot)
    m2 = M0 + m2_1 + delta * delta * (c0 * c1 / tot)
    return (
        jnp.float32(tot),
        jnp.asarray(mean, jnp.float32),
        jnp.asarray(m2, jnp.float32),
    )


def merge_obs_moments(obs_stats, cnt1, osum1, osumsq1):
    """Chan parallel update: fold one generation's raw probe sums (small —
    a few episodes' worth, safe in f32) into the running Welford triple.
    For pooled-scale sums use :func:`merge_obs_moments_np`.

    Saturation bound of the all-f32 device-path merge: the running count
    stops incrementing once cnt1 < ulp(count)/2, i.e. count ≳ cnt1·2^24 —
    at the device path's few-episode probes (cnt1 ≈ 100-1000) that is
    ~10^9-10^10 samples, far past any recorded run; the update weight
    already decays as cnt1/count long before, so the frozen tail is
    benign.  The pooled path never hits this (its merge is
    :func:`merge_obs_moments_np`, f64 on the host)."""
    c0, mean0, m2_0 = obs_stats
    mean1 = osum1 / cnt1
    m2_1 = jnp.maximum(osumsq1 - osum1 * mean1, 0.0)
    tot = c0 + cnt1
    delta = mean1 - mean0
    mean = mean0 + delta * (cnt1 / tot)
    m2 = m2_0 + m2_1 + delta * delta * (c0 * cnt1 / tot)
    return tot, mean, m2


class EvalResult(NamedTuple):
    fitness: jax.Array  # (population,) float32, global member order
    bc: jax.Array  # (population, bc_dim) float32
    steps: jax.Array  # () int32 — total alive env steps this generation


def replicate_on_mesh(tree, mesh: Mesh):
    """Commit every leaf of ``tree`` to ``mesh``, fully replicated — the
    layout every replicated-engine program RETURNS its state in.  A state
    built host-side (init, checkpoint restore) must enter in that same
    layout: jit keys its executables on argument placement, so an
    uncommitted first state would build the generation program once for
    generation 0 and again for generation 1."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def _gen_keys(state: ESState) -> tuple[jax.Array, jax.Array]:
    """Per-generation streams: (offset key, rollout key). Identical everywhere."""
    base = jax.random.fold_in(state.key, state.generation)
    return jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)


def _cast_leaves(tree, dtype):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), tree)


def _check_bf16_params(p) -> None:
    """Trace-time contract check (zero runtime cost): a caller that forgot
    the once-per-member cast would otherwise silently run the rollout in
    f32 (bf16 obs × f32 weights promotes) — losing the perf this path
    exists for with no error anywhere."""
    bad = sorted(
        {
            str(leaf.dtype)
            for leaf in jax.tree_util.tree_leaves(p)
            if jnp.issubdtype(leaf.dtype, jnp.floating)
            and leaf.dtype != jnp.bfloat16
        }
    )
    if bad:
        raise TypeError(
            f"bf16 compute path was handed {bad} params; cast the member "
            "tree once where it is built (ESEngine._member_cast / pooled "
            "materialize) before calling policy_apply"
        )


def _bf16_obs(obs):
    """Floating observations cast to bf16 (integer pixel bytes pass through
    so the policy's own normalization still fires)."""
    if jnp.issubdtype(obs.dtype, jnp.floating):
        return obs.astype(jnp.bfloat16)
    return obs


def _bf16_io_apply(base_apply):
    """Observation/output dtype shim for the bf16 compute path.  Params must
    ALREADY be bf16 — they are cast ONCE per member where they are built
    (``_eval_local`` / center eval), never inside the per-step rollout scan,
    so the steady-state episode loop is cast-free (a wrapper that re-cast
    the whole weight pytree every policy call would rely on XLA CSE to
    hoist it).  Output returns to float32."""

    def wrapped(p, obs):
        _check_bf16_params(p)
        return base_apply(p, _bf16_obs(obs)).astype(jnp.float32)

    return wrapped


def _bf16_io_apply_stateful(base_apply):
    """Recurrent twin of :func:`_bf16_io_apply`: the hidden carry stays
    bf16 across the whole scan (the engine casts ``carry_init`` once), so
    no per-step carry casts exist — only the obs in / action out shims."""

    def wrapped(p, obs, h):
        _check_bf16_params(p)
        out, h_new = base_apply(p, _bf16_obs(obs), h)
        return out.astype(jnp.float32), h_new

    return wrapped


def _choose_eval_chunk(requested: int, local_members: int) -> int:
    """Largest divisor of ``local_members`` that is ≤ the requested chunk."""
    if requested <= 0 or requested >= local_members:
        return local_members
    c = min(requested, local_members)
    while local_members % c != 0:
        c -= 1
    return c


NOISE_KERNEL_MAX_DIM = 1_000_000  # the row kernels hold a few windows of
# dim f32 each in VMEM (weighted_noise_sum: 3·dim, double buffer and
# accumulator; ops/pallas_noise.py); both compile for the v5e here, and past
# it the chunked pure-JAX forms ("slice") handle any dim


# ---- what an engine resolved at build, as ``ES`` publishes it.  A device
# engine's ``build_facts()`` names each fact as its gauge and
# ``run_manifest()["config"]`` do; an engine without one (pooled, host)
# resolves none.  The manifest names these for EVERY engine (``None``: not
# resolved by this one): the engines' own, and whatever a policy's
# hand-written kernels may report (ops/kernel_facts.py has their names)
MANIFEST_BUILD_FACTS = (
    "forward_form", "noise_rows_per_generation", "noise_gather_form",
    *kernel_facts.FACT_NAMES)
# the manifest has the mesh as ``mesh_axes``
_NOT_IN_MANIFEST = frozenset({"mesh_shape", "param_bytes_per_chip"})


def build_fact_gauges(engine) -> dict:
    """The gauges of what ``engine`` resolved at build: a fact it did not
    resolve (``None``) sets none, nor does a kernel rule's reason (a
    sentence: the manifest carries it)."""
    facts = getattr(engine, "build_facts", dict)()
    return {name: value for name, value in facts.items()
            if value is not None and name not in kernel_facts.SENTENCES}


def build_fact_manifest(engine) -> dict:
    """What ``run_manifest()["config"]`` says of ``engine``'s build."""
    facts = getattr(engine, "build_facts", dict)()
    return {**dict.fromkeys(MANIFEST_BUILD_FACTS),
            **{name: value for name, value in facts.items()
               if name not in _NOT_IN_MANIFEST}}


class ESEngine:
    """Compiles and caches the per-generation XLA programs for one setup."""

    # span telemetry hub; ES hands over its own AT construction
    # (``telemetry=``), so the set-up spans of the constructor's callees,
    # of ``init_state`` and of ``compile`` land in it (obs/spans.py).
    # The fused generation program cannot be phase-split host-side — the
    # engine's other contributions are compile events + recompile counters
    telemetry = NULL_TELEMETRY

    def __init__(
        self,
        env: Any,
        policy_apply: Callable[..., Any],  # (p, obs) -> out, or the
        # recurrent (p, obs, carry) -> (out, carry') form when carry_init
        # is given
        spec: ParamSpec,
        table: NoiseTable,
        optimizer: optax.GradientTransformation,
        config: EngineConfig,
        mesh: Mesh,
        decomposed_apply=None,  # (centre, ε tree, c, obs) -> out: given
        # when the module has the x@W + c·(x@ε) form (models/decomposed.py)
        lowrank_apply=None,
        lowrank_spec=None,
        carry_init=None,
        telemetry=None,
    ):
        if telemetry is not None:
            self.telemetry = telemetry
        self.env = env
        if config.obs_norm:
            if env is None:
                raise ValueError(
                    "obs_norm needs device-native rollouts to carry the "
                    "running stats in-program; it is a device-path option"
                )
        if config.low_rank:
            if lowrank_spec is None or (
                lowrank_apply is None and env is not None and carry_init is None
            ):
                # recurrent policies need no lowrank_apply: they perturb via
                # lowrank_tree_perturb and run the standard rollout
                raise ValueError(
                    "EngineConfig.low_rank needs lowrank_apply + lowrank_spec "
                    "(ops/lowrank.py; ES builds them for MLPPolicy)"
                )
        self.lr_spec = lowrank_spec if config.low_rank else None
        # the per-member noise vector the table serves: full-rank ε is (dim,),
        # low-rank is the packed (A‖B‖bias) factors — everything that samples
        # offsets or slices noise uses THIS, not spec.dim
        self.noise_dim = (
            self.lr_spec.noise_dim if config.low_rank else spec.dim
        )
        if config.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"compute_dtype must be float32 or bfloat16, got {config.compute_dtype!r}"
            )
        if config.episodes_per_member < 1:
            raise ValueError(
                f"episodes_per_member must be >= 1, got {config.episodes_per_member}"
            )
        self._bf16 = config.compute_dtype == "bfloat16"
        if self._bf16:
            if carry_init is not None:
                policy_apply = _bf16_io_apply_stateful(policy_apply)
                # cast the episode-start carry ONCE so the scan carry dtype
                # is bf16 throughout (a f32 init would flip dtypes between
                # scan iterations); forward params only to the params-aware
                # form — the legacy zero-arg form (still supported by
                # make_rollout's detection) must keep working under bf16
                base_carry_init = carry_init
                _ci_takes_params = carry_init_takes_params(base_carry_init)
                carry_init = lambda params=None: _cast_leaves(
                    base_carry_init(params) if _ci_takes_params
                    else base_carry_init(), jnp.bfloat16)
            else:
                policy_apply = _bf16_io_apply(policy_apply)
        self._carry_init = carry_init

        self.policy_apply = policy_apply
        self.spec = spec
        self.table = table
        self.optimizer = optimizer
        self.config = config
        self.mesh = mesh
        self.n_devices = mesh.devices.size
        # the Pallas kernels compile through Mosaic on the chip this mesh
        # is made of; anywhere else only the interpreter can run them
        self._pallas_interpret = mesh.devices.flat[0].platform != "tpu"
        # Populations whose pair/member count does not divide the mesh are
        # PADDED up to the next multiple with zero-weighted ghost members:
        # ghosts re-evaluate clamped noise rows (values irrelevant), are
        # sliced out of the gathered fitness before ranking, and their
        # rank weights are zero-padded before the update slice — so they
        # cannot move the parameters.  rows_* is the noise-row structure
        # (pairs when mirrored, members otherwise); only the REAL row
        # count is ever sampled from the table, so a padded run's noise
        # stream is identical to the same population on a dividing mesh.
        if config.mirrored:
            self.pairs_local = pairs_per_device(config.population_size, self.n_devices)
            self.members_local = 2 * self.pairs_local
            self.rows_global = config.population_size // 2
            self.rows_padded = self.pairs_local * self.n_devices
        else:
            self.pairs_local = None  # unmirrored: no pair structure
            self.members_local = (
                padded_count(config.population_size, self.n_devices)
                // self.n_devices
            )
            self.rows_global = config.population_size
            self.rows_padded = self.members_local * self.n_devices
        self.members_padded = self.members_local * self.n_devices
        self.eval_chunk = _choose_eval_chunk(config.eval_chunk, self.members_local)
        # which forward the generation program runs, resolved once from
        # what the engine can observe (run manifest + telemetry gauges)
        if config.low_rank:
            self.forward_form = "low_rank"
        elif (config.mirrored and decomposed_apply is not None
              and carry_init is None and self.eval_chunk % 2 == 0):
            # an antithetic pair's two members share ONE noise tree: per
            # scan step the program reads ε once per pair, half the bytes
            # of per-member perturbed weights (pairs must not straddle
            # chunks, hence the even chunk)
            self.forward_form = "pair_shared"
        else:
            self.forward_form = "materialised"
        # noise-table rows the evaluation gathers per generation
        self.noise_rows_per_generation = (
            config.population_size // 2 if self.forward_form == "pair_shared"
            else config.population_size)
        # how whole rows leave the table in the pair-shared evaluation and
        # in the update's second pass, resolved once like forward_form:
        # "dma" (ops/pallas_noise.py) where Mosaic can compile the kernels
        # for this mesh and table, "slice" (vmap of NoiseTable.slice: the
        # CPU path and the oracle) anywhere else
        self.noise_gather_form = self._resolve_noise_gather_form()

        self._obs_norm = config.obs_norm  # always False when env is None
        # (the guard above rejects obs_norm for update-only engines)
        if env is None:
            # update-only mode: the evaluation happens elsewhere (e.g. the
            # pooled host-env path, parallel/pooled.py) and only the
            # offset-derivation + psum-update programs are built
            self.bc_dim = 0
            self._rollout = None
            self._build_update_programs()
            return
        self.bc_dim = int(env.bc_dim)

        # obs_norm: every rollout's apply takes (params, obs_stats) packed —
        # the running stats ride the SAME traced state the params do, so the
        # whole generation (members + probe + center eval) normalizes with
        # one consistent snapshot
        rollout_apply = policy_apply
        rollout_carry_init = carry_init
        if config.obs_norm:
            clip = float(config.obs_clip)
            base_apply = policy_apply
            if carry_init is not None:
                def rollout_apply(packed, obs, h):
                    p, stats = packed
                    return base_apply(p, normalize_obs(obs, stats, clip), h)

                # the rollout's "params" are the packed (params, obs_stats)
                # pair — a learned episode-start carry must read from the
                # PARAMS half (models/policies.py learned_carry)
                base_ci = carry_init

                def rollout_carry_init(packed=None):
                    return base_ci(None if packed is None else packed[0])
            else:
                def rollout_apply(packed, obs):
                    p, stats = packed
                    return base_apply(p, normalize_obs(obs, stats, clip))

        self._rollout = make_rollout(
            env, rollout_apply, config.horizon, carry_init=rollout_carry_init
        )
        self._obs_probe = (
            make_obs_probe(env, rollout_apply, config.horizon,
                           carry_init=rollout_carry_init)
            if config.obs_norm else None
        )

        self._rollout_lowrank = None
        if config.low_rank and carry_init is None:
            # the MLP per-step factored form; recurrent low_rank reuses
            # self._rollout on per-episode-materialized trees instead
            def lr_packed_apply(packed, obs):
                shared, lrn, c = packed
                return lowrank_apply(shared, lrn, c, obs)

            if self._bf16:
                lr_packed_apply = _bf16_io_apply(lr_packed_apply)

            if config.obs_norm:
                # normalization wraps OUTSIDE the bf16 shim: raw obs are
                # normalized in f32 against the generation's stats snapshot,
                # then cast — the same order as the standard path above
                base_lr_apply = lr_packed_apply

                def lr_packed_apply(packed, obs):
                    inner, stats = packed
                    return base_lr_apply(inner, normalize_obs(obs, stats, clip))

            self._rollout_lowrank = make_rollout(env, lr_packed_apply, config.horizon)

        if self.forward_form == "pair_shared":
            from ..envs.rollout import make_batched_rollout

            self._rollout_batched = make_batched_rollout(env, config.horizon)

            def decomposed_forward(shared, noise, c, stats, obs):
                # one raw observation → f32 policy output.  The trees arrive
                # pre-cast from _eval_local_pairs; the scale c stays f32 (a bf16
                # σ·sign would be 0.1–0.4% off σ).  Raw obs are normalized in
                # f32, then cast — the same order as the standard path above
                if config.obs_norm:
                    obs = normalize_obs(obs, stats, clip)
                if self._bf16:
                    _check_bf16_params((shared, noise))
                    obs = _bf16_obs(obs)
                return decomposed_apply(shared, noise, c, obs).astype(
                    jnp.float32)

            self._decomposed_forward = decomposed_forward

        # All inputs/outputs are fully replicated (P()); the population axis
        # only exists INSIDE the program (axis_index-derived shards).
        self._generation_step = jax.jit(
            jax.shard_map(
                self._generation_body,
                mesh=mesh,
                in_specs=(P(),),
                out_specs=(P(), P()),
                check_vma=False,
            )
        )
        # split path: evaluate, then apply host-computed weights
        self._evaluate = jax.jit(
            jax.shard_map(
                self._evaluate_body,
                mesh=mesh,
                in_specs=(P(),),
                out_specs=P(),
                check_vma=False,
            )
        )
        self._build_update_programs()

        def center_eval(state: ESState):
            _, rkey = _gen_keys(state)
            ckey = jax.random.fold_in(rkey, 2**31 - 1)  # stream disjoint from members
            params = self._member_cast(self.spec.unravel(state.params_flat))
            if self._obs_norm:
                params = (params, state.obs_stats)
            return self._rollout(params, ckey)

        # evaluates the unperturbed center policy (reference's `es.policy`):
        # used for best-snapshot logging and the novelty family's archive BCs
        self._center_eval = jax.jit(center_eval)

    def _resolve_noise_gather_form(self) -> str:
        """``"dma"`` or ``"slice"``: see ``noise_gather_form``."""
        if self._pallas_interpret or self.config.low_rank:
            return "slice"
        from ..ops.pallas_noise import rows_fit_dma

        fits = (self.spec.dim <= NOISE_KERNEL_MAX_DIM
                and rows_fit_dma(self.table.data, self.spec.dim))
        return "dma" if fits else "slice"

    def _build_update_programs(self):
        self._apply_weights = jax.jit(
            jax.shard_map(
                self._apply_weights_body,
                mesh=self.mesh,
                in_specs=(P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
        )

    def all_pair_offsets(self, state: ESState) -> jax.Array:
        """The full per-PAIR (mirrored) or per-MEMBER (unmirrored) offset
        vector for this generation — the same derivation every device
        performs inside the update program, so external evaluators (pooled
        path) perturb with identical noise."""
        okey, _ = _gen_keys(state)
        n = (
            self.config.population_size // 2
            if self.config.mirrored
            else self.config.population_size
        )
        return sample_pair_offsets(okey, n, self.table.size, self.noise_dim)

    def _member_cast(self, tree):
        """bf16 path: cast a member's param tree once, where it is built."""
        return _cast_leaves(tree, jnp.bfloat16) if self._bf16 else tree

    # ---- shard-local bodies (run once per device under shard_map) ----

    @stage(SAMPLE)
    def _local_offsets_signs_keys(self, state: ESState):
        """This device's (reduction offsets, member offsets, signs, keys).

        Mirrored: one table offset per antithetic pair; member offsets repeat
        it, signs alternate, and pair members share a rollout key (common
        random numbers).  Unmirrored (reference's plain ES): one independent
        offset and key per member, all signs +1; the reduction offsets ARE
        the member offsets.
        """
        cfg = self.config
        okey, rkey = _gen_keys(state)
        d = jax.lax.axis_index(POP_AXIS)
        if cfg.mirrored:
            all_pair_offsets = self._pad_rows(sample_pair_offsets(
                okey, cfg.population_size // 2, self.table.size, self.noise_dim
            ))
            pair_offs = jax.lax.dynamic_slice(
                all_pair_offsets, (d * self.pairs_local,), (self.pairs_local,)
            )
            member_offs = member_offsets(pair_offs)
            signs = pair_signs(self.members_local)
            pair_keys = self._pad_rows(
                jax.random.split(rkey, cfg.population_size // 2))
            local_pair_keys = jax.lax.dynamic_slice(
                pair_keys, (d * self.pairs_local, 0), (self.pairs_local, pair_keys.shape[1])
            )
            member_keys = jnp.repeat(local_pair_keys, 2, axis=0)
            return pair_offs, member_offs, signs, member_keys
        all_offsets = self._pad_rows(sample_pair_offsets(
            okey, cfg.population_size, self.table.size, self.noise_dim
        ))
        member_offs = jax.lax.dynamic_slice(
            all_offsets, (d * self.members_local,), (self.members_local,)
        )
        signs = jnp.ones((self.members_local,), jnp.float32)
        keys = self._pad_rows(jax.random.split(rkey, cfg.population_size))
        member_keys = jax.lax.dynamic_slice(
            keys, (d * self.members_local, 0), (self.members_local, keys.shape[1])
        )
        return member_offs, member_offs, signs, member_keys

    def _pad_rows(self, x: jax.Array) -> jax.Array:
        """Pad a per-row array (offsets / pair keys) to the padded row
        count by repeating row 0 — ghost rows carry zero weight in every
        reduction, so the clamped values are never observable."""
        pad = self.rows_padded - self.rows_global
        if pad == 0:
            return x
        ghost = jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])
        return jnp.concatenate([x, ghost], axis=0)

    def _pad_member_weights(self, weights: jax.Array) -> jax.Array:
        """Zero-pad per-member rank weights from the real population to
        the padded member count (the update-side half of the ghost-member
        contract: clamped rows × zero weights contribute nothing)."""
        pad = self.members_padded - self.config.population_size
        if pad == 0:
            return weights
        return jnp.concatenate(
            [weights, jnp.zeros((pad,), weights.dtype)])

    def _eval_local(self, state: ESState, member_offs, signs, member_keys):
        """Rollout this device's members in eval_chunk-sized compiled
        chunks, through the one body ``forward_form`` names."""
        body = {
            "pair_shared": self._eval_local_pairs,
            "low_rank": self._eval_local_lowrank,
            "materialised": self._eval_local_materialised,
        }[self.forward_form]
        return body(state, member_offs, signs, member_keys)

    def _eval_local_materialised(self, state, member_offs, signs, member_keys):
        """Per-member evaluation (``forward_form == "materialised"``): each
        member's θ = centre + σ·sign·ε is built once and rolled out alone
        under the member vmap.  Unmirrored, recurrent, VBN and conv runs,
        and mirrored runs whose chunk is odd."""
        dim = self.spec.dim

        def member_eval(off, sign, key):
            with stage(NOISE):
                eps = self.table.slice(off, dim)
            with stage(PERTURB):
                theta = state.params_flat + state.sigma * sign * eps
                # once-per-member cast (bf16 path): the rollout scan
                # below runs on dtype-pure params, no per-step casts
                params = self._member_cast(self.spec.unravel(theta))
            if self._obs_norm:
                # every member this generation normalizes with the
                # SAME stats snapshot (vmap broadcasts the pack)
                params = (params, state.obs_stats)
            return self._member_rollout(self._rollout, params, key)

        return self._scan_members(member_eval, member_offs, signs, member_keys)

    def _eval_local_lowrank(self, state, member_offs, signs, member_keys):
        """Low-rank evaluation (``forward_form == "low_rank"``): a member's
        table row is the packed (A‖B‖bias) factors of ops/lowrank.py."""
        # shared center tree: unraveled (and, for bf16, cast) ONCE, enters
        # the member vmap as an un-batched constant — its matmuls fuse
        # across the population.  The f32 original stays around for the
        # recurrent branch, which perturbs in f32 and casts per member (the
        # materialised body's theta ordering)
        with stage(PERTURB):
            center_f32 = self.spec.unravel(state.params_flat)
            shared_tree = self._member_cast(center_f32)

        def member_eval(off, sign, key):
            with stage(NOISE):
                nvec = self.table.slice(off, self.noise_dim)
            if self._carry_init is not None:
                # recurrent: dense perturbation materialized ONCE per
                # episode (ops/lowrank.py tree form) — noise STATE stays
                # O(noise_dim); the rollout is the standard carry-threaded
                # scan
                from ..ops.lowrank import lowrank_tree_perturb

                with stage(PERTURB):
                    theta_tree = lowrank_tree_perturb(
                        self.lr_spec, center_f32, nvec, state.sigma * sign)
                    params = self._member_cast(theta_tree)
                rollout = self._rollout
            else:
                # MLP: the factors stay packed — no dense noise matrix
                # ever exists on this path
                rollout = self._rollout_lowrank
                with stage(PERTURB):
                    params = (
                        shared_tree,
                        self._member_cast(self.lr_spec.unpack(nvec)),
                        self._member_cast(state.sigma * sign),
                    )
            if self._obs_norm:
                params = (params, state.obs_stats)
            return self._member_rollout(rollout, params, key)

        return self._scan_members(member_eval, member_offs, signs, member_keys)

    def _scan_members(self, member_eval, member_offs, signs, member_keys):
        """``member_eval(offset, sign, key)`` vmapped over each chunk."""

        def chunk_body(_, xs):
            f, bc, st = jax.vmap(member_eval)(*xs)
            return 0, (f, bc, st)

        return self._scan_chunks(chunk_body, member_offs, signs, member_keys)

    def _member_rollout(self, rollout, params, key):
        """One member's fitness/bc/steps, honoring episodes_per_member."""
        cfg = self.config
        if cfg.episodes_per_member > 1:
            ep_keys = jax.random.split(key, cfg.episodes_per_member)
            res = jax.vmap(rollout, in_axes=(None, 0))(params, ep_keys)
            # fitness = mean return; BC = first episode's; steps summed
            return (
                res.total_reward.mean(),
                jax.tree_util.tree_map(lambda x: x[0], res.bc),
                res.steps.sum(),
            )
        res = rollout(params, key)
        return res.total_reward, res.bc, res.steps

    def _scan_chunks(self, chunk_body, member_offs, signs, member_keys):
        """Dispatch the local shard through ``chunk_body`` in eval_chunk
        pieces (single-chunk: no 1-iteration scan layer) and restore the
        member-major result shapes.  Shared by the per-member vmap bodies
        and the pair-shared batched body."""
        n_chunks = self.members_local // self.eval_chunk
        if n_chunks == 1:
            _, (f, bc, st) = chunk_body(0, (member_offs, signs, member_keys))
        else:
            xs = (
                member_offs.reshape(n_chunks, self.eval_chunk),
                signs.reshape(n_chunks, self.eval_chunk),
                member_keys.reshape(n_chunks, self.eval_chunk, -1),
            )
            _, (f, bc, st) = jax.lax.scan(chunk_body, 0, xs)
        return (
            f.reshape(self.members_local),
            bc.reshape(self.members_local, self.bc_dim),
            st.reshape(self.members_local),
        )

    def _eval_local_pairs(self, state, member_offs, signs, member_keys):
        """Pair-shared evaluation (``forward_form == "pair_shared"``): one
        table row and one ε tree per antithetic PAIR.  The decomposed
        forward is vmapped pairs ⊃ signs (⊃ episodes) with ε batched over
        the pairs only, so x₊@ε and x₋@ε are one [2,m]×[m,n] product that
        reads ε once — half the weight bytes per step of per-member
        perturbed weights.  Only the forward sees the pair axis: the env
        steps a flat member axis (one policy call per step for the chunk),
        whose arrays tile the way the per-member path's do; a (pairs, 2) env
        batch put the sign axis in the sublanes of every physics array on
        the v5e and cost more than the forward saved."""
        cfg = self.config
        n_ep = cfg.episodes_per_member
        with stage(PERTURB):
            shared_tree = self._member_cast(
                self.spec.unravel(state.params_flat))
        forward = self._decomposed_forward  # (centre, ε, c, stats, obs)
        if n_ep > 1:
            forward = jax.vmap(forward, in_axes=(None, None, None, None, 0))
        forward = jax.vmap(forward, in_axes=(None, None, 0, None, 0))
        forward = jax.vmap(forward, in_axes=(None, 0, 0, None, 0))

        def chunk_body(_, xs):
            # chunks are even and member-major (2k, 2k+1): whole pairs
            offs_c, signs_c, keys_c = xs
            lead = (offs_c.shape[0] // 2, 2) + ((n_ep,) if n_ep > 1 else ())
            with stage(NOISE):
                if self.noise_gather_form == "dma":
                    from ..ops.pallas_noise import gather_noise_rows

                    # cast in VMEM, after the DMA: the same bits as the
                    # cast below gives the slice form's f32 rows
                    eps_c = gather_noise_rows(
                        self.table.data, offs_c[::2], dim=self.spec.dim,
                        dtype=jnp.bfloat16 if self._bf16 else jnp.float32,
                        interpret=self._pallas_interpret)
                else:
                    eps_c = jax.vmap(
                        lambda off: self.table.slice(off, self.spec.dim)
                    )(offs_c[::2])
            with stage(PERTURB):
                noise_c = jax.vmap(
                    lambda eps: self._member_cast(self.spec.unravel(eps))
                )(eps_c)
                c_c = state.sigma * signs_c.reshape(lead[:2])

            def batched_apply(obs_batch):
                out = forward(shared_tree, noise_c, c_c, state.obs_stats,
                              obs_batch.reshape(lead + obs_batch.shape[1:]))
                return out.reshape(obs_batch.shape[:1] + out.shape[len(lead):])

            if n_ep > 1:
                keys_c = jax.vmap(
                    lambda key: jax.random.split(key, n_ep)
                )(keys_c).reshape(-1, keys_c.shape[-1])
            res = self._rollout_batched(batched_apply, keys_c)
            f, bc, st = res.total_reward, res.bc, res.steps
            if n_ep > 1:
                # as _member_rollout: mean return, first episode's BC,
                # steps summed
                f = f.reshape(-1, n_ep).mean(axis=1)
                bc = bc.reshape(-1, n_ep, self.bc_dim)[:, 0]
                st = st.reshape(-1, n_ep).sum(axis=1)
            return 0, (f, bc, st)

        return self._scan_chunks(chunk_body, member_offs, signs, member_keys)

    @stage(GATHER)
    def _gather_global(self, fitness_local, bc_local, steps_local):
        """Device-major all_gather → identical global arrays on every device.

        Padded runs: the gathered arrays are sliced back to the REAL
        population (ghost members vanish before ranking/metrics) and
        ghost steps are masked out of the env-steps count so throughput
        numbers never include padding work."""
        cfg = self.config
        fitness = jax.lax.all_gather(fitness_local, POP_AXIS).reshape(-1)
        bc = jax.lax.all_gather(bc_local, POP_AXIS).reshape(-1, self.bc_dim)
        if self.members_padded == cfg.population_size:
            steps = jax.lax.psum(steps_local.sum(), POP_AXIS)
        else:
            d = jax.lax.axis_index(POP_AXIS)
            idx = d * self.members_local + jnp.arange(self.members_local)
            alive = idx < cfg.population_size
            steps = jax.lax.psum(
                jnp.where(alive, steps_local, 0).sum(), POP_AXIS)
            fitness = fitness[: cfg.population_size]
            bc = bc[: cfg.population_size]
        return fitness, bc, steps

    @stage(GRAD)
    def _local_grad(self, state: ESState, weights, reduction_offs):
        """This device's pre-psum partial of the rank-weighted estimator.

        ``reduction_offs`` is per-PAIR (mirrored; folded estimator) or
        per-MEMBER (unmirrored; direct weighted sum).
        """
        cfg = self.config
        d = jax.lax.axis_index(POP_AXIS)
        weights = self._pad_member_weights(weights)
        w_local = jax.lax.dynamic_slice(
            weights, (d * self.members_local,), (self.members_local,)
        )
        if cfg.low_rank:
            # one einsum per layer over the stacked factor slices — no dense
            # E_i is ever materialized (ops/lowrank.py)
            from ..ops.gradient import fold_mirrored_weights as _fold_lr
            from ..ops.lowrank import lowrank_tree_weighted_sum

            row_w = _fold_lr(w_local) if cfg.mirrored else w_local
            noise_local = jax.vmap(
                lambda o: self.table.slice(o, self.noise_dim)
            )(reduction_offs)
            tree = lowrank_tree_weighted_sum(self.lr_spec, noise_local, row_w)
            grad_local = self.spec.flatten(tree) / (
                cfg.population_size * state.sigma
            )
        elif self.noise_gather_form == "dma":
            # Pallas row reduction: each ε row is DMA'd once and FMA'd
            # (f32, on the VPU) into a VMEM accumulator — no materialized
            # noise blocks
            from ..ops.gradient import fold_mirrored_weights as _fold
            from ..ops.pallas_noise import weighted_noise_sum

            row_w = _fold(w_local) if cfg.mirrored else w_local
            grad_local = weighted_noise_sum(
                self.table.data, reduction_offs, row_w, dim=self.spec.dim,
                interpret=self._pallas_interpret,
            ) / (cfg.population_size * state.sigma)
        elif cfg.mirrored:
            # local folded partial of the estimator; scaling commutes with psum
            grad_local = es_gradient(
                self.table, reduction_offs, w_local,
                sigma=state.sigma, population_size=cfg.population_size,
                dim=self.spec.dim, chunk=cfg.grad_chunk,
            )
        else:
            grad_local = rank_weighted_noise_sum(
                self.table, reduction_offs, w_local,
                dim=self.spec.dim, chunk=cfg.grad_chunk,
            ) / (cfg.population_size * state.sigma)
        return grad_local

    def _update_from_weights(self, state: ESState, weights, reduction_offs):
        """Optax step from per-member rank weights. Identical on all devices."""
        grad_local = self._local_grad(state, weights, reduction_offs)
        with stage(GATHER):
            grad_ascent = jax.lax.psum(grad_local, POP_AXIS)
        return self._finish_update(state, grad_ascent)

    @stage(UPDATE)
    def _finish_update(self, state: ESState, grad_ascent):
        """Weight decay + optax step + σ annealing from a replicated ascent
        direction (identical on every device by construction)."""
        cfg = self.config
        if cfg.weight_decay > 0.0:
            grad_ascent = grad_ascent - cfg.weight_decay * state.params_flat
        updates, new_opt_state = self.optimizer.update(
            -grad_ascent, state.opt_state, state.params_flat
        )
        new_params = optax.apply_updates(state.params_flat, updates)
        new_sigma = state.sigma
        if cfg.sigma_decay != 1.0:
            new_sigma = jnp.maximum(state.sigma * cfg.sigma_decay, cfg.sigma_min)
        new_obs_stats = state.obs_stats
        if self._obs_norm:
            # refresh the running stats from center-policy probe episodes —
            # deterministic and identical on every device (replicated
            # params + keys); Chan merge keeps the Welford triple O(1)
            c1, s1, q1 = self._probe_obs_moments(state)
            new_obs_stats = merge_obs_moments(state.obs_stats, c1, s1, q1)
        new_state = ESState(
            params_flat=new_params,
            opt_state=new_opt_state,
            key=state.key,
            generation=state.generation + 1,
            sigma=new_sigma,
            obs_stats=new_obs_stats,
        )
        return new_state, jnp.linalg.norm(grad_ascent)

    def _probe_moments_sum(self, base_key, n_episodes, params_flat, obs_stats):
        """Summed (count, obs_sum, obs_sumsq) over ``n_episodes`` probe
        episodes of the policy at ``params_flat`` — the ONE probe-fanout
        recipe, shared by the per-generation refresh and the init
        warm-start so their keying/batching can never diverge."""
        keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(
            jnp.arange(n_episodes)
        )
        params = self._member_cast(self.spec.unravel(params_flat))
        packed = (params, obs_stats)
        c, s, q = jax.vmap(self._obs_probe, in_axes=(None, 0))(packed, keys)
        return c.sum(), s.sum(axis=0), q.sum(axis=0)

    def _probe_obs_moments(self, state: ESState):
        """Per-generation refresh moments, keyed disjointly from
        member/center streams."""
        _, rkey = _gen_keys(state)
        base = jax.random.fold_in(rkey, 2**31 - 2)
        return self._probe_moments_sum(
            base, self.config.obs_probe_episodes,
            state.params_flat, state.obs_stats,
        )

    # ---- shard_map bodies ----

    def _generation_body(self, state: ESState):
        red_offs, member_offs, signs, member_keys = self._local_offsets_signs_keys(state)
        f_l, bc_l, st_l = self._eval_local(state, member_offs, signs, member_keys)
        fitness, bc, steps = self._gather_global(f_l, bc_l, st_l)
        # NaN-safe ranking: a failed rollout (NaN/inf fitness) is dropped and
        # survivors renormalized — same semantics as the host backend's
        # utils/fault.py::rank_weights_with_failures, but inside the program
        with stage(RANK):
            weights, n_valid = centered_rank_safe(fitness)
        new_state, gnorm = self._update_from_weights(state, weights, red_offs)
        with stage(UPDATE):
            # post-update anomaly guard input: replicated boolean — a
            # non-finite parameter vector or update norm after the optax
            # step means ES.train must reject this generation (restore the
            # previous state) instead of training on poisoned params
            update_finite = jnp.logical_and(
                jnp.isfinite(gnorm),
                jnp.isfinite(new_state.params_flat).all(),
            )
        metrics = {
            "fitness": fitness,
            "bc": bc,
            "steps": steps,
            "grad_norm": gnorm,
            "n_valid": n_valid,
            "update_finite": update_finite,
        }
        return new_state, metrics

    def _evaluate_body(self, state: ESState):
        _, member_offs, signs, member_keys = self._local_offsets_signs_keys(state)
        f_l, bc_l, st_l = self._eval_local(state, member_offs, signs, member_keys)
        fitness, bc, steps = self._gather_global(f_l, bc_l, st_l)
        return EvalResult(fitness=fitness, bc=bc, steps=steps)

    def _apply_weights_body(self, state: ESState, weights):
        red_offs, _, _, _ = self._local_offsets_signs_keys(state)
        new_state, gnorm = self._update_from_weights(state, weights, red_offs)
        return new_state, gnorm

    # ---- public API ----

    def init_state(self, params_flat: jax.Array, key: jax.Array) -> ESState:
        with self.telemetry.phase("setup/init_state"):
            return self._init_state(params_flat, key)

    def _init_state(self, params_flat, key) -> ESState:
        import chex

        chex.assert_shape(params_flat, (self.spec.dim,))
        chex.assert_tree_all_finite(params_flat)
        obs_stats = None
        if self._obs_norm:
            # count=1, mean=0, m2=1 → var 1: the first generation
            # normalizes as identity-ish and real moments take over as the
            # probe count grows
            obs_dim = int(self.env.obs_dim)
            obs_stats = (
                jnp.float32(1.0),
                jnp.zeros((obs_dim,), jnp.float32),
                jnp.ones((obs_dim,), jnp.float32),
            )
            warm = self.config.obs_warmup_episodes
            if warm > 0:
                # warm-start: init-policy probe episodes folded in BEFORE
                # generation 0, keyed disjointly from every training
                # stream (member/center/per-gen-probe use fold_in of the
                # per-generation base; this folds the RAW state key).
                # init_state runs host-side, so the f64 merge is free —
                # and warmup is exactly the many-episodes-at-once case
                # the in-program f32 merge is documented unsafe for.
                import numpy as np

                base = jax.random.fold_in(key, 2**31 - 3)
                c, s, q = self._probe_moments_sum(
                    base, warm, params_flat, obs_stats
                )
                obs_stats = merge_obs_moments_np(
                    obs_stats, float(c), np.asarray(s), np.asarray(q)
                )
        return replicate_on_mesh(ESState(
            params_flat=params_flat,
            opt_state=self.optimizer.init(params_flat),
            key=key,
            generation=jnp.int32(0),
            sigma=jnp.float32(self.config.sigma),
            obs_stats=obs_stats,
        ), self.mesh)

    # what this engine resolves at build, by attribute: the names its
    # gauges and ``run_manifest()["config"]`` carry
    BUILD_FACTS = ("forward_form", "noise_rows_per_generation",
                   "noise_gather_form")

    def build_facts(self) -> dict:
        return {name: getattr(self, name) for name in self.BUILD_FACTS}

    def compile(self, state: ESState) -> float:
        """AOT-compile the fused generation program; returns seconds spent.

        Called once before the timed loop so env-steps/sec — the primary
        metric — never includes XLA trace+compile time.
        """
        import time as _time

        obs = self.telemetry
        with obs.phase("setup/compile"):
            t0 = _time.perf_counter()
            with obs.phase("lower"):
                lowered = self._generation_step.lower(state)
            with obs.phase("acquire"):
                compiled = lowered.compile()
            dt = _time.perf_counter() - t0
            # ledger entry + recompiles counter + per-program gauges + ring
            # event in one call; `compiled` contributes XLA's own FLOPs/
            # bytes/peak-memory estimates where this jax version exposes
            # them (obs/profile/ledger.py)
            with obs.phase("facts"):
                obs.compile_event("generation_step", dt,
                                  compiled=compiled, first_call=True)
        return dt

    def compile_split(self, state: ESState) -> float:
        """AOT-compile the split-path programs (evaluate, apply_weights,
        center eval) used by the novelty family; returns seconds spent."""
        import time as _time

        obs = self.telemetry
        total = 0.0
        dummy_w = jnp.zeros((self.config.population_size,), jnp.float32)
        with obs.phase("setup/compile"):
            for program, lower in (
                ("evaluate", lambda: self._evaluate.lower(state)),
                ("apply_weights", lambda: self._apply_weights.lower(
                    state, dummy_w)),
                ("center_eval", lambda: self._center_eval.lower(state)),
            ):
                t0 = _time.perf_counter()
                with obs.phase("lower"):
                    lowered = lower()
                with obs.phase("acquire"):
                    compiled = lowered.compile()
                dt = _time.perf_counter() - t0
                # per-program ledger entries: the split path's three
                # programs have very different costs, and the ledger is
                # what tells them apart (one blended "split_path" entry
                # could not)
                with obs.phase("facts"):
                    obs.compile_event(program, dt, compiled=compiled,
                                      first_call=True)
                total += dt
        return total

    def generation_step(self, state: ESState):
        """Fused ES generation: returns (new_state, metrics dict)."""
        return self._generation_step(state)

    def evaluate(self, state: ESState) -> EvalResult:
        """Population evaluation only (novelty family / center evaluation)."""
        return self._evaluate(state)

    def apply_weights(self, state: ESState, weights: jax.Array):
        """Update from host-computed per-member weights (novelty family)."""
        return self._apply_weights(state, weights)

    # ---- importance-weighted sample reuse (algo/iwes.py) ----

    def _require_dense_noise(self, what: str):
        if self.config.low_rank:
            raise ValueError(
                f"{what} needs the dense (dim,) noise representation. "
                "low_rank packs rank-r factors instead (ops/lowrank.py), "
                "and IW reuse is not merely unimplemented there — it is "
                "ill-posed: the reused perturbation seen from the drifted "
                "center, dense(v) + (c_old - c_new)/sigma, generally lies "
                "outside the rank-r image, so no factor-space importance "
                "ratio exists (the induced distribution on dense "
                "perturbations is singular; ROADMAP item 7)"
            )

    def noise_stats(self, offsets: jax.Array, d_vec: jax.Array):
        """(ε·d, |ε|²) for every table row in ``offsets`` — the per-sample
        statistics the importance ratio λ needs (algo/iwes.py).  Sharded:
        each device computes its contiguous block, results all_gather'd."""
        self._require_dense_noise("noise_stats")
        if not hasattr(self, "_noise_stats_progs"):
            self._noise_stats_progs = {}
        cache_n = int(offsets.shape[0])
        if cache_n not in self._noise_stats_progs:
            n = cache_n
            k_local = n // self.n_devices
            if k_local * self.n_devices != n:
                raise ValueError(
                    f"offsets ({n}) must divide evenly over {self.n_devices} "
                    "devices"
                )
            chunk = _choose_eval_chunk(self.config.grad_chunk, k_local)

            def body(offs, d_vec):
                dev = jax.lax.axis_index(POP_AXIS)
                o_local = jax.lax.dynamic_slice(offs, (dev * k_local,), (k_local,))

                def chunk_stats(_, o_c):
                    eps = jax.vmap(lambda o: self.table.slice(o, self.spec.dim))(o_c)
                    return 0, (eps @ d_vec, jnp.sum(eps * eps, axis=-1))

                if k_local == chunk:
                    _, (dots, norms) = chunk_stats(0, o_local)
                else:
                    _, (dots, norms) = jax.lax.scan(
                        chunk_stats, 0, o_local.reshape(-1, chunk)
                    )
                    dots = dots.reshape(k_local)
                    norms = norms.reshape(k_local)
                return (
                    jax.lax.all_gather(dots, POP_AXIS).reshape(-1),
                    jax.lax.all_gather(norms, POP_AXIS).reshape(-1),
                )

            self._noise_stats_progs[cache_n] = jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh, in_specs=(P(), P()),
                    out_specs=(P(), P()), check_vma=False,
                )
            )
        return self._noise_stats_progs[cache_n](offsets, d_vec)

    def apply_weights_reuse(
        self, state: ESState, weights: jax.Array, old_offsets: jax.Array,
        old_w: jax.Array, d_stack: jax.Array, coeff_d,
    ):
        """Update from fresh rank weights PLUS reused-sample terms.

        Supports a multi-generation reuse window: ``old_offsets``/``old_w``
        are the CONCATENATION over reused generations (per old PAIR when
        mirrored, per old member otherwise), ``d_stack`` is (n_gens, dim)
        of per-generation drift vectors and ``coeff_d`` their (n_gens,)
        coefficients.  The combined-estimator scaling contract
        (algo/iwes.py): ``weights`` are pre-scaled so the engine's internal
        1/(population·σ) yields 1/(n_total·σ); ``old_w`` and ``coeff_d``
        arrive FULLY pre-scaled, so the reuse terms are added raw:
        ∇̂ += Σ old_w·ε_old + coeff_d @ d_stack.
        """
        self._require_dense_noise("apply_weights_reuse")
        d_stack = jnp.atleast_2d(d_stack)
        coeff_d = jnp.atleast_1d(jnp.asarray(coeff_d, jnp.float32))
        if not hasattr(self, "_apply_weights_reuse_progs"):
            self._apply_weights_reuse_progs = {}
        cache_key = (int(old_offsets.shape[0]), int(d_stack.shape[0]))
        if cache_key not in self._apply_weights_reuse_progs:
            n_old = cache_key[0]
            k_local = n_old // self.n_devices
            if k_local * self.n_devices != n_old:
                raise ValueError(
                    f"old_offsets ({n_old}) must divide evenly over "
                    f"{self.n_devices} devices"
                )

            def body(state, weights, old_offs, old_w, d_st, cd):
                red_offs, _, _, _ = self._local_offsets_signs_keys(state)
                grad_local = self._local_grad(state, weights, red_offs)
                dev = jax.lax.axis_index(POP_AXIS)
                o_local = jax.lax.dynamic_slice(
                    old_offs, (dev * k_local,), (k_local,)
                )
                w_local = jax.lax.dynamic_slice(
                    old_w, (dev * k_local,), (k_local,)
                )
                grad_local = grad_local + rank_weighted_noise_sum(
                    self.table, o_local, w_local,
                    dim=self.spec.dim, chunk=self.config.grad_chunk,
                )
                grad_ascent = jax.lax.psum(grad_local, POP_AXIS)
                grad_ascent = grad_ascent + cd @ d_st
                return self._finish_update(state, grad_ascent)

            self._apply_weights_reuse_progs[cache_key] = jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(P(), P(), P(), P(), P(), P()),
                    out_specs=(P(), P()),
                    check_vma=False,
                )
            )
        return self._apply_weights_reuse_progs[cache_key](
            state, weights, old_offsets, old_w, d_stack, coeff_d,
        )

    def evaluate_center(self, state: ESState):
        """One episode with the unperturbed center params → RolloutResult."""
        return self._center_eval(state)

    def member_params(self, state: ESState, member_index: int) -> jax.Array:
        """Reconstruct one member's flat params from the noise table (host
        convenience — e.g. to snapshot the best member, reference's
        ``best_policy``)."""
        okey, _ = _gen_keys(state)
        if self.config.mirrored:
            all_pair_offsets = sample_pair_offsets(
                okey, self.config.population_size // 2, self.table.size, self.noise_dim
            )
            off = all_pair_offsets[member_index // 2]
            sign = 1.0 if member_index % 2 == 0 else -1.0
        else:
            all_offsets = sample_pair_offsets(
                okey, self.config.population_size, self.table.size, self.noise_dim
            )
            off = all_offsets[member_index]
            sign = 1.0
        if self.config.low_rank:
            from ..ops.lowrank import lowrank_tree_noise

            dense = lowrank_tree_noise(
                self.lr_spec, self.table.slice(off, self.noise_dim))
            return state.params_flat + state.sigma * sign * self.spec.flatten(dense)
        eps = self.table.slice(off, self.spec.dim)
        return state.params_flat + state.sigma * sign * eps
