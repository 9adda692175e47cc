from .acrobot import Acrobot
from .base import EnvSpec, JaxEnv
from .cartpole import CartPole
from .mountain_car import MountainCarContinuous
from .mountain_car_discrete import MountainCar
from .locomotion import (Cheetah2D, DeceptiveValley, Hopper2D,
                         Humanoid2D, PositionOnly,
                         Swimmer2D, Walker2D)
from .pendulum import Pendulum
from .rollout import RolloutResult, make_population_rollout, make_rollout, select_action
from .sequence import TokenScoreEnv
from .synthetic import RecallEnv, SyntheticEnv

__all__ = [
    "Acrobot",
    "EnvSpec",
    "JaxEnv",
    "CartPole",
    "Cheetah2D",
    "Hopper2D",
    "Humanoid2D",
    "DeceptiveValley",
    "PositionOnly",
    "Swimmer2D",
    "Walker2D",
    "MountainCar",
    "MountainCarContinuous",
    "Pendulum",
    "RecallEnv",
    "SyntheticEnv",
    "TokenScoreEnv",
    "RolloutResult",
    "make_population_rollout",
    "make_rollout",
    "select_action",
]
