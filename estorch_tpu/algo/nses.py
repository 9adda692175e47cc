"""NS-ES / NSR-ES / NSRA-ES — the novelty-search family (Conti et al. 2018).

Reference classes ``NS_ES``, ``NSR_ES(NS_ES)``, ``NSRA_ES(NSR_ES)`` in
``estorch/estorch.py`` (SURVEY.md §2 items 3-5, call stack §3.4):

- a meta-population of M policies; each generation picks ONE to update, with
  probability proportional to the novelty of its center behavior;
- rollouts return (reward, bc); member novelty = mean k-NN distance of its
  BC to the archive;
- update direction: NS = novelty ranks only; NSR = ½(reward + novelty
  ranks); NSRA = w·reward + (1−w)·novelty ranks with adaptive w (w rises on
  improvement, decays toward novelty after ``stagnation_patience``
  generations without a new best);
- after the update, the (unperturbed) center's BC is appended to the archive.

TPU-native split: the population evaluation and the rank-weighted update are
the engine's compiled programs (parallel/engine.py evaluate/apply_weights);
the archive, k-NN, meta-selection, and w schedule run host-side on O(pop)
floats — exactly the split BASELINE.json's north star prescribes.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.ranks import centered_rank_np
from .archive import NoveltyArchive
from .es import ES


class NS_ES(ES):
    """Novelty-Search ES: follows novelty ranks only (pure exploration)."""

    def __init__(
        self,
        policy,
        agent,
        optimizer,
        *,
        k: int = 10,
        meta_population_size: int = 3,
        archive_max_size: int = 0,
        **kwargs,
    ):
        if kwargs.get("scenarios") is not None:
            raise ValueError(
                "scenarios is not wired into the novelty family: the "
                "ScenarioEnv appends the variant id to the BC vector, "
                "which would silently distort archive k-NN novelty "
                "(estorch_tpu/scenarios; use plain ES or PBTController)"
            )
        super().__init__(policy, agent, optimizer, **kwargs)
        self.k = k
        self.meta_population_size = int(meta_population_size)
        bc_dim = getattr(self.engine, "bc_dim", None) or None
        self.archive = NoveltyArchive(
            k=k, bc_dim=bc_dim, max_size=archive_max_size
        )

        # meta-population: M independent centers sharing one engine/noise table.
        # state[0] reuses the base-class init; the rest start from fresh
        # policy initializations so the centers are distinct.
        self.meta_states = [self.state]
        for m in range(1, self.meta_population_size):
            self.meta_states.append(self._new_center_state(m))
        # center BC per meta-individual (seeds the archive, reference
        # behavior: the initial centers' BCs are the first archive entries)
        self._center_bc = []
        for st in self.meta_states:
            res = self.engine.evaluate_center(st)
            bc = np.asarray(res.bc)
            self._center_bc.append(bc)
            self.archive.add(bc)
        self._rng = np.random.default_rng(self.seed)

    def _new_center_state(self, m: int):
        """Fresh meta-individual center: re-initialized policy + own RNG stream."""
        if self.backend == "host":
            fresh = self.engine.policy_factory()
            import torch

            with torch.no_grad():
                flat = (
                    torch.nn.utils.parameters_to_vector(fresh.parameters())
                    .cpu()
                    .numpy()
                )
            return self.engine.init_state(flat, key=self.seed + 7919 * m)
        vs = self._module_init(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), 1000 + m)
        )
        flat = self._spec.flatten(vs["params"])
        return self.engine.init_state(
            flat, jax.random.fold_in(jax.random.PRNGKey(self.seed), 2000 + m)
        )

    # ---- variant-specific weighting -------------------------------------

    def _combine_weights(self, fitness: np.ndarray, novelty: np.ndarray) -> np.ndarray:
        """NS-ES: novelty ranks only (reference NS_ES gradient)."""
        return centered_rank_np(novelty)

    def _weights_with_failures(self, fitness: np.ndarray, novelty: np.ndarray) -> np.ndarray:
        """Variant weights with failed (NaN-fitness) members dropped.

        np.argsort sorts NaN LAST — without this guard a failed member would
        receive the TOP centered rank and dominate the update.  Valid members
        are ranked among themselves; failures are zero-weighted and survivors
        renormalized (utils/fault.py straggler-drop scheme).
        """
        from ..utils.fault import mask_and_renormalize, valid_mask

        valid = valid_mask(fitness)
        if valid.all():
            return self._combine_weights(fitness, novelty)
        w = np.zeros(fitness.shape[0], dtype=np.float32)
        w[valid] = self._combine_weights(fitness[valid], novelty[valid])
        return mask_and_renormalize(w, valid)

    # ---- training loop ---------------------------------------------------

    def _select_meta_index(self) -> int:
        """P(m) ∝ novelty of m's center BC against the archive."""
        nov = self.archive.novelty(np.stack(self._center_bc))
        total = float(nov.sum())
        if total <= 0 or not np.isfinite(total):
            probs = np.full(len(nov), 1.0 / len(nov))
        else:
            probs = nov / total
        return int(self._rng.choice(len(nov), p=probs))

    def _post_update(self, record: dict) -> None:
        """Hook for NSRA's w schedule."""

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
            # AOT-compile the split-path programs outside the timed loop,
            # same invariant as ES.train for the primary metric
            self.compile_time_s = self.engine.compile_split(self.meta_states[0])
        for _ in range(n_steps):
            t0 = time.perf_counter()
            # the split path has REAL host-visible phase boundaries (unlike
            # ES's fused program): each span below ends on a host
            # materialization of its device outputs, so device time lands
            # in the phase that spent it (esguard R07 fencing contract)
            with obs.phase("select"):
                m = self._select_meta_index()
            st = self.meta_states[m]

            with obs.phase("eval"):
                ev = self.engine.evaluate(st)
                fitness = np.asarray(ev.fitness)  # fences the eval program
                bc = np.asarray(ev.bc)
            with obs.phase("novelty_knn"):
                novelty = self.archive.novelty(bc)
                weights = self._weights_with_failures(fitness, novelty)
                if self.backend == "device":
                    weights = jnp.asarray(weights)

            with obs.phase("update"):
                new_st, gnorm = self.engine.apply_weights(st, weights)
                if self.backend != "host":
                    jax.block_until_ready(new_st.params_flat)
            self.meta_states[m] = new_st
            if m == 0:
                self.state = new_st  # keep base-class accessors on meta[0]

            # center of the UPDATED policy: archive entry + meta bookkeeping
            with obs.phase("archive"):
                cres = self.engine.evaluate_center(new_st)
                cbc = np.asarray(cres.bc)
                self.archive.add(cbc)
                self._center_bc[m] = cbc
            dt = time.perf_counter() - t0

            record = self._base_record(
                st, fitness, int(ev.steps), float(np.asarray(gnorm)), dt
            )
            record.update(
                meta_index=m,
                center_reward=float(cres.total_reward),
                novelty_mean=float(novelty.mean()),
                novelty_max=float(novelty.max()),
                archive_size=len(self.archive),
            )
            self._post_update(record)
            self._emit_record(record, log_fn, verbose)
        return self

    def _format_record(self, r: dict) -> str:
        return (
            f"gen {r['generation']:4d}  meta {r['meta_index']}  "
            f"max {r['reward_max']:9.2f}  "
            f"nov {r['novelty_mean']:7.3f}  "
            f"archive {r['archive_size']:4d}  "
            f"steps/s {r['env_steps_per_sec']:,.0f}"
        )


class NSR_ES(NS_ES):
    """Novelty+Reward ES: equal mix of reward and novelty ranks."""

    def _combine_weights(self, fitness: np.ndarray, novelty: np.ndarray) -> np.ndarray:
        return 0.5 * centered_rank_np(fitness) + 0.5 * centered_rank_np(novelty)


class NSRA_ES(NSR_ES):
    """Adaptive NSR-ES: w·reward + (1−w)·novelty with w adapted on progress.

    Reference ctor extras (SURVEY.md Appendix A): initial ``weight``,
    ``weight_delta`` (step), ``stagnation_patience`` (generations without a
    new best before w decays toward novelty).
    """

    def __init__(
        self,
        policy,
        agent,
        optimizer,
        *,
        weight: float = 1.0,
        weight_delta: float = 0.05,
        stagnation_patience: int = 10,
        **kwargs,
    ):
        self.weight = float(weight)
        self.weight_delta = float(weight_delta)
        self.stagnation_patience = int(stagnation_patience)
        self._stagnation = 0
        super().__init__(policy, agent, optimizer, **kwargs)

    def _combine_weights(self, fitness: np.ndarray, novelty: np.ndarray) -> np.ndarray:
        w = self.weight
        return w * centered_rank_np(fitness) + (1.0 - w) * centered_rank_np(novelty)

    def _post_update(self, record: dict) -> None:
        # ``improved_best`` comes from the shared best tracking in
        # ES._base_record — no separate best mirror to drift from it
        if record["improved_best"]:
            self.weight = min(1.0, self.weight + self.weight_delta)
            self._stagnation = 0
        else:
            self._stagnation += 1
            if self._stagnation >= self.stagnation_patience:
                self.weight = max(0.0, self.weight - self.weight_delta)
                self._stagnation = 0
        record["nsra_weight"] = self.weight
