"""The solver's answers altered where they are produced: within each
lane the placements take each other's nodes in reverse order (the same
nodes, so every plan still verifies) and keep the scores of their own.
Every dispatch comes through `solve_lane_fused`, the wavefront of a
narrow window and the whole-axis scan of a wide one alike."""
import numpy as np

from nomad_tpu.solver import binpack

_solve = binpack.solve_lane_fused


def altered(*a, **kw):
    chosen, *rest = _solve(*a, **kw)
    chosen = np.array(chosen)
    lanes = chosen.reshape(-1, chosen.shape[-1])
    for lane in lanes:
        placed = np.nonzero(lane >= 0)[0]
        lane[placed] = lane[placed][::-1]
    return (lanes.reshape(chosen.shape), *rest)


binpack.solve_lane_fused = altered
