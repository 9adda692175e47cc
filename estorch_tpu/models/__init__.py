from .cca_moe_lm import CCAMoELM
from .delta_moe_lm import DeltaMoELM
from .gated_window_moe_lm import GatedWindowMoELM
from .hybrid_lm import HybridLM
from .indexed_moe_lm import IndexedMoELM
from .looped_lm import LoopedLM
from .moe_lm import MoELM
from .sambay_lm import SambaYLM
from .policies import MLPPolicy, NatureCNN, RecurrentNatureCNN, RecurrentPolicy
from .window_moe_lm import WindowMoELM
from .vbn import VirtualBatchNorm, capture_reference_stats


def __getattr__(name):
    # torch imports are deferred: device-path users never pay for them
    if name == "TorchVirtualBatchNorm":
        from .vbn_torch import TorchVirtualBatchNorm

        return TorchVirtualBatchNorm
    if name == "TorchRunningObsNorm":
        from .obsnorm_torch import TorchRunningObsNorm

        return TorchRunningObsNorm
    raise AttributeError(name)


__all__ = [
    "CCAMoELM",
    "DeltaMoELM",
    "GatedWindowMoELM",
    "HybridLM",
    "IndexedMoELM",
    "LoopedLM",
    "MLPPolicy",
    "MoELM",
    "NatureCNN",
    "RecurrentNatureCNN",
    "TorchRunningObsNorm",
    "RecurrentPolicy",
    "SambaYLM",
    "VirtualBatchNorm",
    "WindowMoELM",
    "TorchVirtualBatchNorm",
    "capture_reference_stats",
]
