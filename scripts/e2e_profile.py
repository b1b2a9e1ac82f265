"""Sampled-stack profile of the headline-shape e2e round (the r5 pass-3
methodology): run bench.time_batched_path under a 200Hz all-thread
sampler, aggregate leaf frames and (module, function) self-time, print
the top entries. Host control-plane profile on whatever platform JAX
comes up on (JAX_PLATFORMS=cpu keeps it off the chip); the solver
dispatch itself is timed separately by bench."""
import collections
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

samples = collections.Counter()
leaf_samples = collections.Counter()
stop = threading.Event()


def sampler():
    me = threading.get_ident()
    while not stop.is_set():
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            f = frame
            leaf = f"{os.path.basename(f.f_code.co_filename)}:" \
                   f"{f.f_code.co_name}"
            leaf_samples[leaf] += 1
            seen = set()
            while f is not None:
                key = (os.path.basename(f.f_code.co_filename),
                       f.f_code.co_name)
                if key not in seen:
                    seen.add(key)
                    samples[key] += 1
                f = f.f_back
        time.sleep(0.005)


E = int(sys.argv[1]) if len(sys.argv) > 1 else 32
P = int(sys.argv[2]) if len(sys.argv) > 2 else 2000

t = threading.Thread(target=sampler, daemon=True)
t.start()
t0 = time.perf_counter()
dt, evals, placed = bench.time_batched_path(bench.N_NODES, E, P)
stop.set()
t.join(timeout=2)
total = time.perf_counter() - t0
print(f"\nround: {evals} evals x {P} -> {placed} placed in {dt:.2f}s "
      f"({placed/max(dt,1e-9):.0f}/s); wall incl. warm {total:.1f}s")
n = sum(leaf_samples.values())
print(f"\n== top leaf frames ({n} samples) ==")
for k, v in leaf_samples.most_common(25):
    print(f"{v*100.0/max(n,1):5.1f}%  {k}")
print("\n== top on-stack (module,fn) ==")
for (m, fn), v in samples.most_common(25):
    print(f"{v*100.0/max(n,1):5.1f}%  {m}:{fn}")
