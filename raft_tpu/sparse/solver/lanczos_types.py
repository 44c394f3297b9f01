"""Lanczos solver configuration.

(ref: cpp/include/raft/sparse/solver/lanczos_types.hpp:20
``LANCZOS_WHICH::{LA,LM,SA,SM}`` and :40 ``lanczos_solver_config
{n_components, max_iterations, ncv, tolerance, which, seed}``.)
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional


class LANCZOS_WHICH(enum.Enum):
    """(ref: lanczos_types.hpp:20)

    Note on SM: like the reference, SM selects smallest-magnitude ritz
    values from the same Krylov process — WITHOUT shift-invert. Interior
    eigenvalues converge slowly (or stall) for ill-conditioned spectra;
    extremal modes (SA/LA/LM) are the well-conditioned ones.
    """

    LA = "LA"  # largest algebraic
    LM = "LM"  # largest magnitude
    SA = "SA"  # smallest algebraic
    SM = "SM"  # smallest magnitude


@dataclasses.dataclass
class LanczosSolverConfig:
    """(ref: lanczos_types.hpp:40 ``lanczos_solver_config``)

    ``jit_loop=None`` (default) compiles the loop on accelerator
    backends and keeps the host loop on CPU; ``jit_loop=True`` compiles
    the whole thick-restart loop into ONE program (``lax.while_loop``
    over cycles) — no per-cycle host dispatch — at the cost of
    host-side cancellation points and the stagnation heuristic
    (bounded by max_iterations instead).
    """

    n_components: int
    max_iterations: int = 1000
    ncv: Optional[int] = None
    tolerance: float = 1e-6
    which: LANCZOS_WHICH = LANCZOS_WHICH.SA
    seed: int = 42
    jit_loop: Optional[bool] = None
