"""TPU solver: dense vmapped placement engine (the north-star component)."""
from .cache import enable_compile_cache

# every program factory (this package, parallel/mesh.py) is reached
# through this import, so the persistent cache is on before any compile
enable_compile_cache()

from .binpack import (  # noqa: F401
    NodeConst, NodeState, PlacementBatch, make_node_const, make_node_state,
    solve_placements,
)
from .service import TpuPlacement, TpuPlacementService, tg_solver_eligible  # noqa: F401
