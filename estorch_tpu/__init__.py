"""estorch_tpu — a TPU-native Evolution Strategies framework.

Re-designs the capabilities of the reference library (goktug97/estorch — ES,
NS-ES, NSR-ES, NSRA-ES, VirtualBatchNorm, distributed population evaluation)
for TPU hardware: one compiled XLA program per generation, shared-noise-table
perturbations vmapped over the population in HBM, and a single ``lax.psum``
over the device mesh in place of MPI gather + master broadcast.

Public API mirrors the reference (SURVEY.md Appendix A); the algorithm
classes are re-exported here as they land:

    from estorch_tpu import ES, NS_ES, NSR_ES, NSRA_ES, VirtualBatchNorm
"""

__version__ = "0.3.0"

from . import (envs, models, obs, ops, parallel, resilience,  # noqa: F401
               scenarios, serve, utils)
from .algo import ES, IW_ES, NS_ES, NSR_ES, NSRA_ES, NoveltyArchive
from .envs.agent import JaxAgent, PooledAgent
from .models import (MLPPolicy, NatureCNN, RecurrentNatureCNN,
                     RecurrentPolicy, VirtualBatchNorm)

__all__ = [
    "ES",
    "IW_ES",
    "NS_ES",
    "NSR_ES",
    "NSRA_ES",
    "NoveltyArchive",
    "JaxAgent",
    "PooledAgent",
    "MLPPolicy",
    "NatureCNN",
    "RecurrentNatureCNN",
    "RecurrentPolicy",
    "VirtualBatchNorm",
    "envs",
    "models",
    "obs",
    "ops",
    "parallel",
    "resilience",
    "scenarios",
    "serve",
    "utils",
    "__version__",
]

# the last line of the import: "before the program" ends here on the
# process's set-up timeline (obs/spans.py)
obs.spans.TIMELINE.mark_imported()
