"""Every score the solver reports altered where it is produced: one
part in a hundred low, as a wrong constant in the scoring would read."""
import numpy as np

from nomad_tpu.solver import binpack

_solve = binpack.solve_lane_wave


def altered(*a, **kw):
    chosen, scores, n_yielded = _solve(*a, **kw)
    return chosen, np.asarray(scores) * 0.99, n_yielded


binpack.solve_lane_wave = altered
