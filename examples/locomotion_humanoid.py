"""Device-native locomotion capstone: the planar humanoid stays upright.

Humanoid2D (envs/locomotion.py) is the hardest in-tree task — an 11-body
jointed pelvis–torso–head column on two walker legs with free-swinging arm
counterweights, terminating when the column falls.  Physics runs INSIDE
the compiled generation program, the device-native stand-in for the
reference users' MuJoCo-Humanoid configs (those stay on the host/pooled
paths; BASELINE config 3).

Within ~30 generations the population mean roughly triples as policies
learn to balance; a 300-generation run reaches mean 160 / best 407 — best
members hold the full 400-step horizon while moving (an earlier round's
CPU-mesh run).

Run: python examples/locomotion_humanoid.py
"""

from estorch_tpu.configs import humanoid2d_device


def main():
    es = humanoid2d_device(population_size=512)
    es.train(n_steps=30)
    ev = es.evaluate_policy(n_episodes=10)
    print(f"\nbest member reward: {es.best_reward:.1f}")
    print(f"center policy held-out mean: {ev['mean']:.1f}")
    return es


if __name__ == "__main__":
    main()
