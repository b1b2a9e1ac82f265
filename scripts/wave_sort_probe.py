"""Cost of the block-merge step's selection primitive on chip: times a
while-loop of NBLK sequential steps, each doing a (B*K,) multi-key sort
/ top_k over vmapped E lanes -- the candidate structure of the block
kernel. If a sort step costs ~<=150us, block-merge wins (250 steps vs
2048 x 33us)."""
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

E, B, K = 32, 32, 32
NBLK = 250

key = jax.random.PRNGKey(0)
eff = jax.random.uniform(key, (E, B * K), dtype=jnp.float32)
order = jax.random.randint(key, (E, B * K), 0, 64, dtype=jnp.int32)
midx = jnp.tile(jnp.arange(B * K, dtype=jnp.int32) % K, (E, 1))


def timeit(name, fn, *args):
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = f(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    med = statistics.median(ts)
    print(f"{name:<34} {med*1000:8.2f}ms total  {med/NBLK*1e6:7.1f}us/step",
          flush=True)


def loop_sort3(eff, order, midx):
    def one(effl, orderl, midxl):
        def body(carry, _):
            e, acc = carry
            s = jax.lax.sort((-e, orderl, midxl, e), num_keys=3)
            top = s[3][:K]
            # carry-dependent perturbation so nothing hoists
            e2 = e + top.sum() * 1e-9
            return (e2, acc + top[0]), None
        (ef, acc), _ = jax.lax.scan(body, (effl, jnp.float32(0)), None,
                                    length=NBLK)
        return acc
    return jax.vmap(one)(eff, order, midx)


def loop_topk(eff, order, midx):
    def one(effl, orderl, midxl):
        def body(carry, _):
            e, acc = carry
            vals, idx = jax.lax.top_k(e, K)
            e2 = e + vals.sum() * 1e-9
            return (e2, acc + vals[0]), None
        (ef, acc), _ = jax.lax.scan(body, (effl, jnp.float32(0)), None,
                                    length=NBLK)
        return acc
    return jax.vmap(one)(eff, order, midx)


def loop_sort1(eff, order, midx):
    """Single fused int32 key (total-order float bits + idx tiebreak
    infeasible in 32 bits; this times the raw single-key sort cost)."""
    def one(effl, orderl, midxl):
        def body(carry, _):
            e, acc = carry
            s = jax.lax.sort(-e)
            e2 = e + s[:K].sum() * 1e-9
            return (e2, acc + s[0]), None
        (ef, acc), _ = jax.lax.scan(body, (effl, jnp.float32(0)), None,
                                    length=NBLK)
        return acc
    return jax.vmap(one)(eff, order, midx)


print(f"backend={jax.default_backend()} E={E} BK={B*K} NBLK={NBLK}",
      flush=True)
timeit("3-key lax.sort (1024)", loop_sort3, eff, order, midx)
timeit("top_k (1024->32)", loop_topk, eff, order, midx)
timeit("1-key lax.sort (1024)", loop_sort1, eff, order, midx)


# --- paranoid re-timing: force host materialization per rep ---
def timeit_sync(name, fn, *args):
    f = jax.jit(fn)
    _ = np.asarray(f(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        res = np.asarray(f(*args))
        ts.append(time.perf_counter() - t0)
    med = statistics.median(ts)
    print(f"{name:<34} {med*1000:8.2f}ms total  {med/NBLK*1e6:7.1f}us/step"
          f"  (sync)", flush=True)


def rtt_probe(eff, order, midx):
    return eff[:, 0] + 1.0


timeit_sync("dispatch RTT (trivial program)", rtt_probe, eff, order, midx)
timeit_sync("3-key lax.sort (1024)", loop_sort3, eff, order, midx)
timeit_sync("top_k (1024->32)", loop_topk, eff, order, midx)
