"""Event-spacing analysis for the block-merge wavefront idea: replays
the classic per-placement wave kernel semantics for ONE headline lane in
numpy and counts 'events' (winner saturation -> refill, skip-set growth,
penalty steps). Average placements-per-event bounds the speedup of a
block kernel that commits all placements between events in one chain
step. Host-side numpy: run with JAX_PLATFORMS=cpu to keep off the chip."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import bench

h, job, nodes = bench.build_world()
from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import AllocPlaceResult
from nomad_tpu.solver.service import TpuPlacementService
from nomad_tpu.structs import Plan
from nomad_tpu.solver.binpack import (MAX_SKIP, SKIP_THRESHOLD,
                                      wavefront_compact_host, _wave_p_bucket)

snap = h.state.snapshot()
j = mock.job(id="evstat")
P = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
j.task_groups[0].count = P
tg = j.task_groups[0]
plan = Plan(eval_id="evstat-eval-0000000000000001", priority=50, job=j)
ctx = EvalContext(snap, plan)
places = [AllocPlaceResult(name=f"{j.id}.{tg.name}[{k}]", task_group=tg)
          for k in range(P)]
svc = TpuPlacementService(ctx, j, batch_mode=False, spread_alg=False)
lane = svc.pack(tg, places, nodes)
B = lane.wavefront_B()
compact, scal_f, scal_i, pen, sp = wavefront_compact_host(
    lane.const, lane.init, lane.batch, lane.dtype_name,
    p_pad=_wave_p_bucket(P), B=B)
ask_cpu, ask_mem, count = [float(x) for x in scal_f]
L, n_active = [int(x) for x in scal_i]
C = compact.shape[0]
print(f"B={B} L={L} n_active={n_active} C={C} "
      f"ask_cpu={ask_cpu} ask_mem={ask_mem}")
print(f"capacity col stats: c>0 rows={int((compact[:,0]>0).sum())} "
      f"min={compact[compact[:,0]>0,0].min():.0f} "
      f"median={np.median(compact[compact[:,0]>0,0]):.0f} "
      f"max={compact[:,0].max():.0f}")

# numpy replay of the per-step kernel, tracking events
slot = compact[:B].copy()
jv = np.zeros(B, dtype=np.int64)
cursor = B
events = 0
sat_events = 0
skip_prev = None
run_winner, runs = None, []
t0 = time.time()
for i in range(n_active):
    cs = slot[:, 0]
    fit = jv < cs
    jp1 = (jv + 1).astype(np.float32)
    free_cpu = 1.0 - (slot[:, 1] + jp1 * ask_cpu) / np.maximum(slot[:, 3], 1e-9)
    free_mem = 1.0 - (slot[:, 2] + jp1 * ask_mem) / np.maximum(slot[:, 4], 1e-9)
    binpack = 18.0 - np.exp2(-10.0 * free_cpu) - np.exp2(-10.0 * free_mem)
    coll = slot[:, 5] + jv
    anti = np.where(coll > 0, -(coll + 1.0) / max(count, 1.0), 0.0)
    nsc = 1.0 + (coll > 0) + (slot[:, 6] != 0.0)
    final = (binpack + anti + slot[:, 6]) / nsc
    low = fit & (final <= SKIP_THRESHOLD)
    skip_rank = np.cumsum(low)
    skipped = low & (skip_rank <= MAX_SKIP)
    if skip_prev is not None and not np.array_equal(skipped, skip_prev):
        events += 1
    skip_prev = skipped.copy()
    counted = fit & ~skipped
    cpos = np.cumsum(counted)
    window = counted & (cpos <= L)
    srank = np.cumsum(skipped)
    deficit = max(0, L - min(int(cpos[-1]), L))
    fallback = skipped & (srank <= deficit)
    yielded = window | fallback
    if not yielded.any():
        break
    order = np.where(window, cpos, L + srank)
    eff = np.where(yielded, final, -np.inf)
    best = eff.max()
    is_best = yielded & (eff == best)
    border = order[is_best].min()
    w = int(np.argmax(is_best & (order == border)))
    if run_winner != w:
        runs.append(1)
        run_winner = w
    else:
        runs[-1] += 1
    jv[w] += 1
    if jv[w] >= cs[w]:
        sat_events += 1
        skip_prev = None
        # shift/refill
        entry = compact[min(cursor, C - 1)]
        jv = np.concatenate([jv[:w], jv[w + 1:], [0]])
        slot = np.concatenate([slot[:w], slot[w + 1:], entry[None]], axis=0)
        cursor += 1
print(f"replay {time.time()-t0:.1f}s: placed={i+1} sat_events={sat_events} "
      f"skipset_changes={events}")
runs = np.array(runs)
print(f"winner runs: n={len(runs)} mean={runs.mean():.2f} "
      f"median={np.median(runs):.0f} max={runs.max()}")
total_events = sat_events + events
print(f"placements per (sat+skip) event: "
      f"{(i+1)/max(total_events,1):.1f}")
