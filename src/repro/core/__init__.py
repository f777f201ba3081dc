"""repro.core — the paper's contribution: distributed VB in natural-parameter
space (dSVB, Algorithm 1; dVB-ADMM, Algorithm 2) plus the cVB / noncoop /
nsg-dVB baselines, for conjugate-exponential models.

The unified engine is `run_vb(model, data, topology, ...)` (core/engine.py)
over the `ConjugateExpModel` protocol (core/model.py); the named `run_*`
functions are backward-compatible wrappers binding the GMM instance."""
from repro.core import (  # noqa: F401
    algorithms, engine, expfam, gmm, model, network, refperm,
)
from repro.core.algorithms import (  # noqa: F401
    ALGORITHMS, VBRun, run_cvb, run_dsvb, run_dvb_admm, run_noncoop,
    run_nsg_dvb,
)
from repro.core.engine import (  # noqa: F401
    ADMMConsensus, Diffusion, FusionCenter, Isolated, MeshExecutor,
    RingDiffusion, Schedule, run_vb,
)
from repro.core.expfam import (  # noqa: F401
    GMMPosterior, noninformative_prior, pack_natural, unpack_natural,
)
from repro.core.model import ConjugateExpModel, GMMModel, LinRegModel  # noqa: F401
from repro.core import linreg  # noqa: F401  (2nd conjugate-exp instance)
