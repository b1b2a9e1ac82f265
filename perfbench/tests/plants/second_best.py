"""One answer of each lane altered where it is produced, with nothing a
look at the alloc alone would show: the lane's first placement goes to
the first other node in scan order that it fits, and reports that
node's honest score."""
import numpy as np

from nomad_tpu.solver import binpack

_solve = binpack.solve_lane_wave


def honest(cap_cpu, cap_mem, used_cpu, used_mem):
    free_cpu = np.float32(1) - np.float32(used_cpu) / np.float32(cap_cpu)
    free_mem = np.float32(1) - np.float32(used_mem) / np.float32(cap_mem)
    total = np.float32(10) ** free_cpu + np.float32(10) ** free_mem
    return np.clip(np.float32(20) - total, 0, 18) / np.float32(18)


def altered(const, init, batch, **kw):
    chosen, scores, n_yielded = _solve(const, init, batch, **kw)
    chosen, scores = np.array(chosen), np.array(scores)
    shape = chosen.shape
    lanes = chosen.reshape(-1, shape[-1])
    lane_scores = scores.reshape(lanes.shape)

    def of(tree_field):
        a = np.asarray(tree_field)
        return a.reshape(lanes.shape[0], -1)
    cpu_cap, mem_cap = of(const.cpu_cap), of(const.mem_cap)
    used_cpu, used_mem = of(init.used_cpu), of(init.used_mem)
    ask_cpu, ask_mem = of(batch.ask_cpu), of(batch.ask_mem)
    feasible = of(const.feasible)
    for e, lane in enumerate(lanes):
        if lane[0] < 0:
            continue
        after_cpu = used_cpu[e] + ask_cpu[e, 0]
        after_mem = used_mem[e] + ask_mem[e, 0]
        fits = (feasible[e].astype(bool) & (after_cpu <= cpu_cap[e])
                & (after_mem <= mem_cap[e]))
        fits[lane[0]] = False
        others = np.nonzero(fits)[0]
        if others.size:
            pos = others[0]
            lane[0] = pos
            lane_scores[e, 0] = honest(cpu_cap[e, pos], mem_cap[e, pos],
                                       after_cpu[pos], after_mem[pos])
    return lanes.reshape(shape), lane_scores.reshape(scores.shape), n_yielded


binpack.solve_lane_wave = altered
