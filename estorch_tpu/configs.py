"""The driver-mandated benchmark configurations (BASELINE.json `configs`).

Each config is a ready-to-run recipe mapping a BASELINE entry to the backend
this environment can execute it on:

1. cartpole_smoke   — CartPole-v1, 2-layer MLP, vanilla ES, pop 64
                      (device path: the env itself runs on-chip)
2. halfcheetah_vbn  — HalfCheetah (gymnasium MuJoCo), MLP+VBN, pop 1k
                      (host path: MuJoCo steps on CPU workers; MJX is not in
                      this image, so the device-physics variant is deferred)
3. humanoid_mirrored— Humanoid (gymnasium MuJoCo), MLP, mirrored ES, pop 10k
                      (host path, same note)
4. humanoid_nsres   — NSR-ES on Humanoid with BC = final (x, y) torso position
5. pong84_conv      — the conv-rollout stress path: NatureCNN population on
                      the bundled 84×84 C++ pixel pong (pooled execution);
                      stands in for the Atari config without ALE
6. atari_frostbite  — Frostbite Nature-CNN pop 5k — GATED: ale_py is not in
                      this image; raises with a clear message.

Use:  python -m estorch_tpu.configs <name> [--generations N] [--n-proc K]
"""

from __future__ import annotations

import argparse
from typing import Callable

import numpy as np


def _torch_mlp(n_in: int, n_out: int, hidden=(64, 64), vbn: bool = False):
    import torch

    from .models.vbn_torch import TorchVirtualBatchNorm

    class MLP(torch.nn.Module):
        def __init__(self):
            super().__init__()
            layers = []
            last = n_in
            for h in hidden:
                layers.append(torch.nn.Linear(last, h))
                if vbn:
                    layers.append(TorchVirtualBatchNorm(h))
                layers.append(torch.nn.Tanh())
                last = h
            layers.append(torch.nn.Linear(last, n_out))
            self.net = torch.nn.Sequential(*layers)

        def forward(self, x):
            return self.net(x)

    return MLP


def _mujoco_agent(env_id: str, bc_xy: bool = False):
    """Host agent for gymnasium MuJoCo envs (reference rollout contract)."""
    import gymnasium as gym
    import torch

    class MujocoAgent:
        def __init__(self):
            self.env = gym.make(env_id)

        def rollout(self, policy, render=False):
            obs, _ = self.env.reset()
            total, steps, done = 0.0, 0, False
            with torch.no_grad():
                while not done:
                    a = policy(torch.from_numpy(np.asarray(obs, np.float32)))
                    obs, r, term, trunc, _ = self.env.step(a.numpy())
                    total += float(r)
                    steps += 1
                    done = term or trunc
            self.last_episode_steps = steps
            if bc_xy:
                # BC: final torso (x, y) — the Conti-2018 Humanoid BC
                data = self.env.unwrapped.data
                return total, np.asarray(data.qpos[:2], np.float32)
            return total

    return MujocoAgent


def cartpole_smoke(**over):
    """BASELINE config 1 — device-native CartPole ES, population 64."""
    import optax

    from . import ES, JaxAgent, MLPPolicy
    from .envs import CartPole

    kw = dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=64,
        sigma=0.1,
        policy_kwargs={"action_dim": 2, "hidden": (32, 32)},
        agent_kwargs={"env": CartPole()},
        optimizer_kwargs={"learning_rate": 3e-2},
    )
    kw.update(over)
    return ES(**kw)


def _planar_device(env, population, hidden, horizon, lr, over,
                   sigma=0.08):
    """Shared recipe body for the device-native locomotion configs: MLP
    policy on the JaxAgent path, physics compiled into the generation."""
    import optax

    from . import ES, JaxAgent, MLPPolicy

    kw = dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=population,
        sigma=sigma,
        policy_kwargs={"action_dim": env.action_dim, "hidden": hidden,
                       "discrete": False, "action_scale": 1.0},
        agent_kwargs={"env": env, "horizon": horizon},
        optimizer_kwargs={"learning_rate": lr},
    )
    kw.update(over)
    return ES(**kw)


def swimmer2d_device(**over):
    """Device-native locomotion: pure-JAX planar swimmer, whole generation
    compiled on-chip (envs/locomotion.py — the MJX-fallback path)."""
    from .envs import Swimmer2D

    return _planar_device(Swimmer2D(), 512, (32, 32), 300, 3e-2, over)


def hopper2d_device(**over):
    """Device-native locomotion with contact + falling termination: pure-JAX
    planar hopper (envs/locomotion.py), Hopper-class difficulty."""
    from .envs import Hopper2D

    return _planar_device(Hopper2D(), 1024, (64, 64), 400, 2e-2, over)


def walker2d_device(**over):
    """Device-native locomotion, planar biped (Walker2d-class): two-legged
    balance + gait with falling termination — the in-tree stepping stone
    toward the Humanoid north star."""
    from .envs import Walker2D

    return _planar_device(Walker2D(), 1024, (64, 64), 400, 2e-2, over)


def humanoid2d_device(**over):
    """Device-native locomotion, planar humanoid (11 bodies, 10 joints):
    the hardest in-tree task — balance a jointed column on two legs with
    free-swinging arm counterweights — and the device-native stand-in for
    the MuJoCo-Humanoid configs (BASELINE config 3 stays on host/pooled).

    obs_norm defaults ON (an earlier round's learning-curve A/B:
    Humanoid2D's obs variance spans 165×, and normalization won 2/2 seeds
    on final mean and AUC — passing the raw-observation run's
    600-generation plateau by gen 80); pass
    obs_norm=False for the raw-observation variant — including to
    RESTORE checkpoints saved before round 4 (the running stats are
    training state, so restore_checkpoint rejects an obs_norm
    mismatch).

    obs_probe_episodes defaults to 4 here (an earlier round's
    learning-curve A/B: 4 probes tied 1 probe on one seed and found a
    2.2× better optimum on the other, at ~0.6% extra episodes — the same
    faster-stats lever as warmup).  The ENGINE default stays 1 (parity-minimal,
    goldens pinned); this is a recipe-level choice.  Unlike obs_norm,
    the probe count is NOT training state and restore does not gate on
    it — resuming a pre-round-5 run under this default accumulates
    stats 4× faster from the resume point (statistically sound either
    way); pass obs_probe_episodes=1 for a procedure-exact
    continuation."""
    from .envs import Humanoid2D

    return _planar_device(Humanoid2D(), 1024, (64, 64), 400, 2e-2,
                          {"obs_norm": True, "obs_probe_episodes": 4,
                           **over})


def cheetah2d_device(**over):
    """Device-native locomotion, 7-body planar runner (HalfCheetah-class):
    the on-chip stand-in for BASELINE config 2 until mjx is installable."""
    from .envs import Cheetah2D

    return _planar_device(Cheetah2D(), 1024, (64, 64), 500, 2e-2, over)


def halfcheetah_vbn(**over):
    """BASELINE config 2 — HalfCheetah MLP+VBN, population 1k (host path)."""
    import torch

    from . import ES

    kw = dict(
        policy=_torch_mlp(17, 6, hidden=(64, 64), vbn=True),
        agent=_mujoco_agent("HalfCheetah-v5"),
        optimizer=torch.optim.Adam,
        population_size=1000,
        sigma=0.02,
        optimizer_kwargs={"lr": 1e-2},
        weight_decay=0.005,
    )
    kw.update(over)
    es = ES(**kw)
    _freeze_host_vbn(es)
    return es


def humanoid2d_pop10k(**over):
    """Config-3 scale on the DEVICE path: Humanoid2D at population 10240
    with rank-1 perturbations, running obs normalization, and a
    Humanoid-sized policy (256×256).

    Why these engine modes: `low_rank=1` shrinks the member noise state
    from O(dim) to O(Σ(m+n)r), which is what lets 10240 members of a
    256×256 policy fit; it beat the full-rank forward on the CPU mesh in
    earlier rounds, and its standing against the other forwards on the
    chip is not measured yet (ROADMAP S4).  `obs_norm` measured +30-43%
    held-out eval on real MuJoCo (3/3 HalfCheetah seeds, pooled path).
    The two compose (normalization is an input-side transform,
    independent of the noise representation).  eval_chunk bounds
    materialized member weights the same way the bench's pop-10k point
    does.  obs_probe_episodes=4 per the probe-count A/B (see
    humanoid2d_device).  This is the configuration `chip_smoke.py` trains
    on the v5e: 75,018 parameters (Humanoid2D obs 25 → 256 → 256 → 10)."""
    from .envs import Humanoid2D

    return _planar_device(Humanoid2D(), 10240, (256, 256), 400, 2e-2,
                          {"low_rank": 1, "obs_norm": True,
                           "obs_probe_episodes": 4,
                           "eval_chunk": 1024, **over})


def humanoid_mirrored(**over):
    """BASELINE config 3 — Humanoid mirrored-sampling ES, population 10k."""
    import torch

    from . import ES

    kw = dict(
        policy=_torch_mlp(348, 17, hidden=(256, 256)),
        agent=_mujoco_agent("Humanoid-v5"),
        optimizer=torch.optim.Adam,
        population_size=10000,
        sigma=0.02,
        optimizer_kwargs={"lr": 1e-2},
        weight_decay=0.005,
    )
    kw.update(over)
    return ES(**kw)


def humanoid_nsres(**over):
    """BASELINE config 4 — NSR-ES on Humanoid, BC = final torso (x, y)."""
    import torch

    from . import NSR_ES

    kw = dict(
        policy=_torch_mlp(348, 17, hidden=(256, 256)),
        agent=_mujoco_agent("Humanoid-v5", bc_xy=True),
        optimizer=torch.optim.Adam,
        population_size=1000,
        sigma=0.02,
        k=10,
        meta_population_size=3,
        optimizer_kwargs={"lr": 1e-2},
    )
    kw.update(over)
    return NSR_ES(**kw)


def halfcheetah_pooled(**over):
    """BASELINE config 2, pooled edition: HalfCheetah physics in gym.vector
    workers while the population's policy forwards run device-batched —
    the no-MJX path to MuJoCo at scale (vs halfcheetah_vbn's per-member
    host rollouts).  Pass ``obs_norm=True`` for the OpenAI-ES MuJoCo
    setup (running observation normalization; default off for reference
    parity — estorch has no such machinery)."""
    import optax

    from . import ES, MLPPolicy, PooledAgent

    kw = dict(
        policy=MLPPolicy,
        agent=PooledAgent,
        optimizer=optax.adam,
        population_size=1000,
        sigma=0.02,
        policy_kwargs={"action_dim": 6, "hidden": (64, 64), "discrete": False},
        agent_kwargs={"env_name": "gym:HalfCheetah-v5", "horizon": 1000},
        optimizer_kwargs={"learning_rate": 1e-2},
        weight_decay=0.005,
    )
    kw.update(over)
    return ES(**kw)


def humanoid_pooled(**over):
    """BASELINE config 3's pooled edition on REAL MuJoCo (round-4 verdict
    next #2 — the one BASELINE env besides gated Atari never trained on):
    Humanoid-v5 physics in gym.vector workers, device-batched population
    forwards, the Humanoid-sized MLP (obs 348 → 256×256 → 17, actions
    squashed to the env's ±0.4 bound), mirrored sampling, obs_norm on
    (the OpenAI-ES Humanoid setup — the 348-dim observation spans wildly
    different scales).  Population defaults to 512 (CPU-feasible at tens
    of generations; pass population_size=10000 for the full config-3
    scale on the chip)."""
    import optax

    from . import ES, MLPPolicy, PooledAgent

    kw = dict(
        policy=MLPPolicy,
        agent=PooledAgent,
        optimizer=optax.adam,
        population_size=512,
        sigma=0.02,
        policy_kwargs={"action_dim": 17, "hidden": (256, 256),
                       "discrete": False, "action_scale": 0.4},
        agent_kwargs={"env_name": "gym:Humanoid-v5", "horizon": 1000},
        optimizer_kwargs={"learning_rate": 1e-2},
        weight_decay=0.005,
        obs_norm=True,
    )
    kw.update(over)
    return ES(**kw)


def halfcheetah_nsres(**over):
    """BASELINE config 4, pooled edition on REAL MuJoCo: NSR-ES on
    HalfCheetah with BC = final x-position (Conti et al.'s locomotion
    characterization).  ``env_kwargs`` puts the x-position into the
    observation (gymnasium excludes it by default) and ``bc_indices=(0,)``
    selects it as the archive's 1-dim BC — the novelty family then
    searches over where the gait ENDS, not what it looks like."""
    import optax

    from . import NSR_ES, MLPPolicy, PooledAgent

    kw = dict(
        policy=MLPPolicy,
        agent=PooledAgent,
        optimizer=optax.adam,
        population_size=256,
        sigma=0.02,
        k=10,
        meta_population_size=3,
        policy_kwargs={"action_dim": 6, "hidden": (64, 64), "discrete": False},
        agent_kwargs={
            "env_name": "gym:HalfCheetah-v5",
            "horizon": 1000,
            "env_kwargs": {"exclude_current_positions_from_observation": False},
            "bc_indices": (0,),
        },
        optimizer_kwargs={"learning_rate": 1e-2},
        weight_decay=0.005,
    )
    kw.update(over)
    return NSR_ES(**kw)


def pong84_conv(**over):
    """Conv-rollout stress without ALE: NatureCNN on the bundled C++ pixel
    pong (84×84), pooled execution with the full Atari preprocessing stack
    (4-frame stacking → the CNN's designed 84×84×4 input, action repeat,
    sticky actions; envs/atari_wrappers.py) — the same machinery BASELINE
    config 5 exercises, with the env swapped for the in-tree stand-in."""
    import optax

    from . import ES, NatureCNN, PooledAgent

    kw = dict(
        policy=NatureCNN,
        agent=PooledAgent,
        optimizer=optax.adam,
        population_size=256,
        sigma=0.02,
        policy_kwargs={"action_dim": 3, "use_vbn": True},
        agent_kwargs={"env_name": "pong84", "horizon": 500,
                      "frame_stack": 4, "action_repeat": 2,
                      "sticky_prob": 0.25},
        optimizer_kwargs={"learning_rate": 1e-2},
        table_size=1 << 23,
    )
    kw.update(over)
    return ES(**kw)


def atari_frostbite(**over):
    """BASELINE config 5 — Frostbite Nature-CNN pop 5k. Gated: needs ALE."""
    try:
        import ale_py  # noqa: F401
    except ImportError as e:
        raise ImportError(
            "the Atari config needs ale_py, which is not in this image; "
            "the NatureCNN policy (models/policies.py) and the pooled "
            "execution path are ready for it once ALE is available"
        ) from e
    raise NotImplementedError("wire up ALE via PooledAgent once available")


def _freeze_host_vbn(es) -> None:
    """Collect a random-rollout batch and freeze VBN stats via the engine."""
    env = es.agent.env  # the prototype agent's env (worker 0)
    frames = []
    obs, _ = env.reset(seed=0)
    for _ in range(128):
        obs, _, term, trunc, _ = env.step(env.action_space.sample())
        frames.append(np.asarray(obs, np.float32))
        if term or trunc:
            obs, _ = env.reset()
    es.engine.freeze_vbn(np.stack(frames))


CONFIGS: dict[str, Callable] = {
    "cartpole_smoke": cartpole_smoke,
    "swimmer2d_device": swimmer2d_device,
    "hopper2d_device": hopper2d_device,
    "walker2d_device": walker2d_device,
    "humanoid2d_device": humanoid2d_device,
    "humanoid2d_pop10k": humanoid2d_pop10k,
    "cheetah2d_device": cheetah2d_device,
    "halfcheetah_vbn": halfcheetah_vbn,
    "humanoid_mirrored": humanoid_mirrored,
    "humanoid_nsres": humanoid_nsres,
    "halfcheetah_pooled": halfcheetah_pooled,
    "halfcheetah_nsres": halfcheetah_nsres,
    "humanoid_pooled": humanoid_pooled,
    "pong84_conv": pong84_conv,
    "atari_frostbite": atari_frostbite,
}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("config", choices=sorted(CONFIGS))
    p.add_argument("--generations", type=int, default=10)
    p.add_argument("--n-proc", type=int, default=8)
    p.add_argument("--population", type=int, default=None)
    p.add_argument("--log-jsonl", type=str, default=None)
    args = p.parse_args(argv)

    over = {}
    if args.population:
        over["population_size"] = args.population
    es = CONFIGS[args.config](**over)

    log_fn = None
    if args.log_jsonl:
        from .utils import JsonlWriter, MultiWriter

        log_fn = MultiWriter([JsonlWriter(args.log_jsonl)], echo=True)
    es.train(args.generations, n_proc=args.n_proc, log_fn=log_fn)
    print(f"\nbest reward: {es.best_reward:.2f}")
    return es


if __name__ == "__main__":
    main()
