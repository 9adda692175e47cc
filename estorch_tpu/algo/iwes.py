"""IW-ES — importance-weighted reuse of the previous generation's rollouts.

PAPERS.md "Importance Weighted Evolution Strategies" (1811.04624): after the
center moves θ_t → θ_{t+1}, the generation-t members θ_i = θ_t + σ_t s_i ε_i
are still valid Monte-Carlo samples for the gradient at θ_{t+1} — under the
new search distribution they are the perturbations

    ε'_i = (θ_i − θ_{t+1}) / σ_{t+1} = d + c·s_i ε_i,
    d = (θ_t − θ_{t+1})/σ_{t+1},   c = σ_t/σ_{t+1}

with importance ratio

    λ_i = N(θ_i; θ_{t+1}, σ²_{t+1}) / N(θ_i; θ_t, σ²_t)
        = c^dim · exp((‖ε_i‖² − ‖ε'_i‖²)/2).

Each generation this class evaluates the fresh population as usual, then
forms the update from fresh members with their ranks PLUS up to
``reuse_window`` previous generations' members with rank × self-normalized
λ (each buffered generation admitted independently by its own ESS) — up to
(1+W)× the effective sample count per rollout budget.  The classic failure mode (a big center move
collapses the ratios) is guarded by the effective sample size
ESS = (Σλ)²/Σλ²: when ESS/n_old < ``ess_min`` the stale set is dropped and
the generation proceeds as vanilla ES.  (The c^dim prefactor is common to
every member, so self-normalization cancels it — collapse comes from the
SPREAD of the per-member exponents: big center moves, or c ≠ 1 amplifying
the ‖ε‖² spread at large dim.  Annealed runs therefore still fall back to
no-reuse naturally; the guard handles it, no special case.)

Nothing about the reused set is re-evaluated and no old noise is stored:
old ε_i regenerate from the shared table via the PREVIOUS state's offsets
(the same derivation every device already performs —
engine.all_pair_offsets), old fitness is a host-side (n,) float array, and
the two device passes the reuse needs (per-sample ε·d / ‖ε‖², and the
Σ wλε update term) are sharded psum/all_gather programs
(parallel/engine.py::noise_stats / apply_weights_reuse).

Device path only; low_rank is not supported (packed factor noise has no
dense ε for the ratio), and the host/pooled backends raise as usual.
Checkpoint/resume: the reuse ring is deliberately NOT part of run state —
post-resume generations run vanilla until the ring refills (utils/
checkpoint.py stays bit-exact for everything that matters).
"""

from __future__ import annotations

import collections
import time
from typing import Callable

import jax.numpy as jnp
import numpy as np

from ..ops.gradient import fold_mirrored_weights
from ..utils.fault import rank_weights_with_failures
from .es import ES


def stale_log_ratios(dots, norms, d2: float, c: float, dim: int):
    """Per-member log importance ratios of samples drawn under an older
    (θ_old, σ_old) seen from the current (θ_new, σ_new) — THE IW-ES
    formula (module docstring), shared by :class:`IW_ES` and the async
    scheduler's late-result fold (algo/scheduler.py).

    ``dots`` are the SIGNED per-member ε·d values (s_i already applied;
    the mirrored expansion is the caller's job), ``norms`` the per-member
    ‖ε‖², ``d2`` = ‖d‖² with d = (θ_old − θ_new)/σ_new, ``c`` =
    σ_old/σ_new.  Returns log λ (unnormalized — λ only ever enters
    self-normalized, so callers shift by the max before exponentiating).
    """
    dots = np.asarray(dots)
    norms = np.asarray(norms)
    eps_new_sq = d2 + 2.0 * c * dots + c * c * norms
    return dim * np.log(c) + 0.5 * (norms - eps_new_sq)


def mirrored_member_stats(dots, norms):
    """Expand per-PAIR noise stats (``engine.noise_stats``) to the
    mirrored member layout — member 2k = +ε_k, member 2k+1 = −ε_k, the
    ops/noise.py convention every estimator in this family leans on.
    One home for the sign/repeat rule so the three λ computations
    (IW_ES, worker fold, host fold) can never drift apart."""
    dots = np.asarray(dots)
    return (np.repeat(dots, 2) * np.tile([1.0, -1.0], dots.shape[0]),
            np.repeat(np.asarray(norms), 2))


def clipped_stale_lambdas(dots, norms, d2: float, c: float, dim: int,
                          iw_clip: float) -> np.ndarray:
    """Per-member truncated importance weights for ONE stale source —
    the fold rule shared verbatim by the worker-granular and
    host-granular schedulers (docs/async.md, docs/multihost.md):
    :func:`stale_log_ratios`, max-shift stabilization (λ only ever
    enters self-normalized; shift-invariant in log space), mean-1
    self-normalization within the source (IW-ES), then IMPACT's
    truncation at ``iw_clip`` so one wild ratio cannot hijack the
    update.  ``dots`` are SIGNED per-member values (mirrored expansion
    already applied)."""
    log_lam = stale_log_ratios(dots, norms, d2, c, dim)
    log_lam -= log_lam.max()
    lam = np.exp(log_lam)
    lam = lam * (len(lam) / max(lam.sum(), 1e-30))
    return np.minimum(lam, iw_clip).astype(np.float32)


class IW_ES(ES):
    """ES with importance-weighted reuse of the previous generation."""

    def __init__(self, *args, ess_min: float = 0.5, reuse_window: int = 1,
                 **kwargs):
        if not 0.0 < ess_min <= 1.0:
            raise ValueError(f"ess_min must be in (0, 1], got {ess_min}")
        if reuse_window < 1:
            raise ValueError(f"reuse_window must be >= 1, got {reuse_window}")
        self.ess_min = float(ess_min)
        self.reuse_window = int(reuse_window)
        super().__init__(*args, **kwargs)
        if self.backend != "device":
            raise ValueError(
                "IW_ES is a device-path algorithm (the reuse terms are "
                f"sharded table reductions); got backend={self.backend!r}"
            )
        if self._low_rank:
            raise ValueError(
                "IW_ES does not support low_rank — and not merely as "
                "pending work: the reused perturbation seen from the "
                "drifted center, dense(v) + (c_old - c_new)/sigma, "
                "generally has no rank-r preimage, so the factor-space "
                "importance ratio is ill-posed (ROADMAP item 7)"
            )
        if self._obs_norm:
            raise ValueError(
                "IW_ES does not support obs_norm: buffered generations' "
                "fitness was measured under OLDER running stats, so the "
                "effective policy f(θ) the density ratio assumes fixed "
                "drifts with the normalization — the reuse estimate would "
                "be silently biased"
            )
        # newest-last ring of minimal per-generation reuse records:
        # (params_flat, sigma, pair_offsets, fitness).  Deliberately NOT the
        # whole ESState — that would pin reuse_window copies of the optax
        # moments (~3·W·dim floats) on device for nothing; offsets are
        # computed ONCE here since they are a pure function of the state
        self._prev = collections.deque(maxlen=self.reuse_window)
        self._dry_gens = 0  # consecutive full-ring generations with no reuse
        self._dry_best_ess = 0.0  # best ESS seen anywhere in the dry streak
        self._warned_never_reusing = False

    def train(
        self,
        n_steps: int,
        n_proc: int = 1,
        log_fn: Callable[[dict], None] | None = None,
        verbose: bool = True,
    ):
        self._setup_n_proc(n_proc)
        obs = self.obs
        obs.discard_phases()  # drop partial spans from an aborted generation
        if self.compile_time_s is None:
            self.compile_time_s = self.engine.compile_split(self.state)
            self.compile_time_s += self._warm_reuse_programs()
        n = self.population_size
        for _ in range(n_steps):
            t0 = time.perf_counter()
            st = self.state
            with obs.phase("eval"):
                ev = self.engine.evaluate(st)
                fitness = np.asarray(ev.fitness)  # fences the eval program
            # base-class parity BEFORE anything mutates: a dead env (fewer
            # than 2 valid FRESH members) must hard-fail with state intact —
            # reuse must not let stale samples train through a dead generation
            if int(np.isfinite(fitness).sum()) < 2:
                raise RuntimeError(
                    f"only {int(np.isfinite(fitness).sum())}/{n} population "
                    "members produced valid fitness — cannot form an update; "
                    "check env/rollout health"
                )

            # admit each buffered generation independently by its own ESS
            with obs.phase("reuse_ratios"):
                accepted, best_ess = [], 0.0
                for entry in self._prev:
                    lam, d_vec, c, offs = self._ratios(entry, st)
                    ess = (
                        float(lam.sum() ** 2 / (lam**2).sum())
                        if lam.sum() > 0 else 0.0
                    )
                    best_ess = max(best_ess, ess)
                    if ess >= self.ess_min * n:
                        accepted.append((entry[3], lam, d_vec, c, offs))
            reused = bool(accepted)
            with obs.phase("update"):
                if reused:
                    self._dry_gens = 0
                    self._dry_best_ess = 0.0
                    new_st, gnorm = self._reuse_update(st, fitness, accepted)
                else:
                    if len(self._prev) == self.reuse_window:
                        self._dry_gens += 1
                        self._dry_best_ess = max(self._dry_best_ess, best_ess)
                        self._maybe_warn_never_reusing()
                    weights = jnp.asarray(rank_weights_with_failures(fitness))
                    new_st, gnorm = self.engine.apply_weights(st, weights)
                jnp.asarray(new_st.params_flat).block_until_ready()

            self.state = new_st
            with obs.phase("sample"):
                # buffer this generation for future reuse.  The offsets
                # program is left async on purpose (its consumer is next
                # generation's ratio pass) — this span clocks dispatch +
                # the σ host copy, not the offsets compute
                self._prev.append((
                    st.params_flat, float(np.asarray(st.sigma)),
                    self.engine.all_pair_offsets(st), fitness,
                ))
            dt = time.perf_counter() - t0

            record = self._base_record(
                st, fitness, int(ev.steps), float(np.asarray(gnorm)), dt
            )
            record.update(
                reused_prev=reused,
                reused_gens=len(accepted),
                ess=round(best_ess, 2),
                effective_samples=n * (1 + len(accepted)),
            )
            self._emit_record(record, log_fn, verbose)
        return self

    # ------------------------------------------------------------ internals

    DRY_WARN_AFTER = 20

    def _maybe_warn_never_reusing(self) -> None:
        """One-time diagnostic when the ESS guard rejects every generation.

        The log-ratio spread is d·ε ~ N(0, ‖Δθ/σ‖²), so reuse survives only
        when the per-generation center move is small: with a coordinate-wise
        optimizer (Adam) that means lr ≲ σ/√dim.  Users who pick a
        known-good vanilla-ES lr are usually 10× above that and silently get
        vanilla ES at IW-ES prices — say so once, with the fix."""
        if self._warned_never_reusing or self._dry_gens < self.DRY_WARN_AFTER:
            return
        self._warned_never_reusing = True
        import warnings

        sigma = float(np.asarray(self.state.sigma))
        warnings.warn(
            f"IW_ES: no generation passed the ESS guard in the last "
            f"{self._dry_gens} generations (best ESS over the streak "
            f"{self._dry_best_ess:.1f} < ess_min*n = "
            f"{self.ess_min * self.population_size:.1f}); every "
            "update ran as vanilla ES while paying the ratio-computation "
            "overhead. The center is moving too far per generation for "
            "reuse: shrink the step so that lr ≲ sigma/sqrt(dim) "
            f"(≈ {sigma / max(self._spec.dim, 1) ** 0.5:.1e} here), or raise "
            "sigma, or drop back to plain ES.",
            RuntimeWarning,
            stacklevel=3,
        )

    def _warm_reuse_programs(self) -> float:
        """Trace+compile noise_stats and every reuse-window shape of
        apply_weights_reuse OUTSIDE the timed loop (the codebase invariant:
        the primary metric env_steps_per_sec never includes compile time).
        The concatenated old set can be any of 1..reuse_window generations
        long, so each length is a distinct XLA program — warm them all."""
        t0 = time.perf_counter()
        st = self.state
        offsets = self.engine.all_pair_offsets(st)
        zeros_d = jnp.zeros_like(st.params_flat)
        self.engine.noise_stats(offsets, zeros_d)
        n_rows = int(offsets.shape[0])
        dummy_w = jnp.zeros((self.population_size,), jnp.float32)
        for w in range(1, self.reuse_window + 1):
            out, _ = self.engine.apply_weights_reuse(
                st, dummy_w,
                jnp.tile(offsets, w), jnp.zeros((n_rows * w,), jnp.float32),
                jnp.tile(zeros_d[None, :], (w, 1)),
                jnp.zeros((w,), jnp.float32),
            )
            jnp.asarray(out.params_flat).block_until_ready()
        dt = time.perf_counter() - t0
        # one ledger entry for the whole reuse-window warm: reuse_window+1
        # distinct XLA programs (noise_stats + one apply_weights_reuse per
        # window length), traced+executed so only wall seconds are known
        self.obs.compile_event("iwes_reuse_warm", dt,
                               count_recompiles=self.reuse_window + 1,
                               programs=self.reuse_window + 1,
                               first_call=True)
        return dt

    def _ratios(self, entry, st):
        """Per-old-member importance ratios λ under the CURRENT state.

        ``entry`` is a ring record (params_flat, sigma, pair_offsets,
        fitness) — see train()."""
        prev_params, sigma_old, offsets, _ = entry
        sigma_new = float(np.asarray(st.sigma))
        c = sigma_old / sigma_new
        d_vec = (prev_params - st.params_flat) / sigma_new
        dots, norms = self.engine.noise_stats(offsets, d_vec)
        dots, norms = np.asarray(dots), np.asarray(norms)
        d2 = float(jnp.vdot(d_vec, d_vec))
        if self._mirrored:
            dots, norms = mirrored_member_stats(dots, norms)
        log_lam = stale_log_ratios(dots, norms, d2, c, self._spec.dim)
        # log-sum-exp style stabilization: λ only ever enters self-normalized
        # (λ̃ and ESS are shift-invariant in log space)
        log_lam -= log_lam.max()
        return np.exp(log_lam), d_vec, c, offsets

    def _reuse_update(self, st, fitness, accepted):
        """One combined-estimator update: fresh ranks + λ-weighted old ranks
        from every accepted generation.

        Scaling contract with engine.apply_weights_reuse: fresh weights are
        rescaled by n/n_tot so the engine's 1/(n·σ) denominator becomes
        1/(n_tot·σ); the old-side coefficients arrive fully scaled.
        """
        n = self.population_size
        n_tot = n * (1 + len(accepted))
        sigma_new = float(np.asarray(st.sigma))

        combined = np.concatenate([fitness] + [a[0] for a in accepted])
        w_all = rank_weights_with_failures(combined)
        w_fresh = w_all[:n]

        old_w_parts, offs_parts, d_rows, coeff_rows = [], [], [], []
        for g, (prev_fit, lam, d_vec, c, offs) in enumerate(accepted):
            w_old = w_all[n * (g + 1): n * (g + 2)]
            lam_tilde = lam * (n / max(lam.sum(), 1e-30))  # mean-1 normalized
            w_old_eff = w_old * lam_tilde
            # old ε-term: Σ w λ̃ (d + c·s·ε) → the s·ε part folds per pair
            if self._mirrored:
                folded = fold_mirrored_weights(jnp.asarray(w_old_eff))
            else:
                folded = jnp.asarray(w_old_eff)
            old_w_parts.append(folded * (c / (n_tot * sigma_new)))
            offs_parts.append(offs)
            d_rows.append(d_vec)
            coeff_rows.append(w_old_eff.sum() / (n_tot * sigma_new))

        weights = jnp.asarray(w_fresh * (n / n_tot))
        return self.engine.apply_weights_reuse(
            st, weights,
            jnp.concatenate(offs_parts), jnp.concatenate(old_w_parts),
            jnp.stack(d_rows), jnp.asarray(coeff_rows, jnp.float32),
        )
