"""Bisect the wave-kernel step cost on chip: times lax.scan programs at
the headline shape (E=32 lanes vmapped, P=2048 steps, B=32 window,
C=P+B rows) with progressively larger step bodies, all on random data.
Identifies which part of the step the 38us/step goes to. Experiment
only -- no production semantics."""
import functools
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

E, P, B = 32, 2048, 32
C = P + B
UNROLL = 8

key = jax.random.PRNGKey(0)
compact = jax.random.uniform(key, (E, C, 12), dtype=jnp.float32) + 1.0
pen = jnp.zeros((E, P), dtype=jnp.int32) - 1


def timeit(name, fn, *args):
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = f(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    print(f"{name:<28} {med*1000:8.2f}ms  {med/P*1e6:6.2f}us/step",
          flush=True)
    return med


arangeB = jnp.arange(B, dtype=jnp.int32)
arangeC = jnp.arange(C, dtype=jnp.int32)


def scan_over(step, n_carry_extra=0):
    def one_lane(compact_l, pen_l):
        slot0 = compact_l[:B]
        carry0 = (jnp.zeros(B, jnp.int32), slot0, jnp.int32(B))
        _, ys = jax.lax.scan(
            functools.partial(step, compact_l=compact_l), carry0,
            (jnp.arange(P, dtype=jnp.int32), pen_l), unroll=UNROLL)
        return ys
    return jax.vmap(one_lane)


# --- variant 1: trivial body (scan floor) ---
def step_floor(carry, xs, compact_l):
    j, slot, cursor = carry
    i, pen_i = xs
    return (j + 1, slot, cursor + 1), (i, jnp.float32(0.0), i)


# --- variant 2: score math only (elementwise over B + argmax) ---
def step_score(carry, xs, compact_l):
    j, slot, cursor = carry
    i, pen_i = xs
    cs = slot[:, 0]
    fit = j.astype(jnp.float32) < cs
    jp1 = (j + 1).astype(jnp.float32)
    new_cpu = slot[:, 1] + jp1 * 0.5
    new_mem = slot[:, 2] + jp1 * 0.5
    free_cpu = 1.0 - new_cpu / jnp.maximum(slot[:, 3], 1e-9)
    free_mem = 1.0 - new_mem / jnp.maximum(slot[:, 4], 1e-9)
    binpack = 18.0 - jnp.exp2(-10.0 * free_cpu) - jnp.exp2(-10.0 * free_mem)
    coll = slot[:, 5] + j.astype(jnp.float32)
    anti = jnp.where(coll > 0, -(coll + 1.0) / 2000.0, 0.0)
    is_pen = (pen_i >= 0) & (slot[:, 7] == pen_i.astype(jnp.float32))
    final = (binpack + anti + jnp.where(is_pen, -1.0, 0.0) + slot[:, 6])
    eff = jnp.where(fit, final, -jnp.inf)
    w = jnp.argmax(eff)
    oh_w = arangeB == w
    j2 = j + oh_w.astype(jnp.int32)
    return (j2, slot, cursor), (w, jnp.max(eff), i)


# --- variant 3: score + selection-window cumsums ---
def step_select(carry, xs, compact_l):
    j, slot, cursor = carry
    i, pen_i = xs
    cs = slot[:, 0]
    fit = j.astype(jnp.float32) < cs
    jp1 = (j + 1).astype(jnp.float32)
    free_cpu = 1.0 - (slot[:, 1] + jp1 * 0.5) / jnp.maximum(slot[:, 3], 1e-9)
    free_mem = 1.0 - (slot[:, 2] + jp1 * 0.5) / jnp.maximum(slot[:, 4], 1e-9)
    final = 18.0 - jnp.exp2(-10.0 * free_cpu) - jnp.exp2(-10.0 * free_mem)
    low = fit & (final <= 0.0)
    skip_rank = jnp.cumsum(low.astype(jnp.int32))
    skipped = low & (skip_rank <= 3)
    counted = fit & ~skipped
    cpos = jnp.cumsum(counted.astype(jnp.int32))
    window = counted & (cpos <= 8)
    srank = jnp.cumsum(skipped.astype(jnp.int32))
    fallback = skipped & (srank <= 2)
    yielded = window | fallback
    order = jnp.where(window, cpos, 8 + srank)
    eff = jnp.where(yielded, final, -jnp.inf)
    best = jnp.max(eff)
    is_best = yielded & (eff == best)
    border = jnp.min(jnp.where(is_best, order, 2 ** 30))
    w = jnp.argmax(is_best & (order == border))
    oh_w = arangeB == w
    j2 = j + oh_w.astype(jnp.int32)
    return (j2, slot, cursor), (w, best, jnp.sum(yielded.astype(jnp.int32)))


# --- variant 4: score + select + refill/shift (the full structure) ---
def step_full(carry, xs, compact_l):
    (j2, slot, cursor), (w, best, ny) = step_select(carry, xs, compact_l)
    i, pen_i = xs
    oh_w = arangeB == w
    cs = slot[:, 0]
    jw = jnp.sum(jnp.where(oh_w, j2, 0), dtype=jnp.int32)
    csw = jnp.sum(jnp.where(oh_w, cs, 0.0))
    sat = jw.astype(jnp.float32) >= csw
    oh_c = arangeC == jnp.clip(cursor, 0, C - 1)
    entry_row = jnp.sum(jnp.where(oh_c[:, None], compact_l, 0.0), axis=0)
    take_next = arangeB >= w
    is_last = arangeB == B - 1
    j_sh = jnp.where(is_last, 0,
                     jnp.where(take_next, jnp.roll(j2, -1), j2))
    slot_sh = jnp.where(
        is_last[:, None], entry_row[None, :],
        jnp.where(take_next[:, None], jnp.roll(slot, -1, axis=0), slot))
    j3 = jnp.where(sat, j_sh, j2)
    slot2 = jnp.where(sat, slot_sh, slot)
    cursor2 = cursor + sat.astype(jnp.int32)
    return (j3, slot2, cursor2), (w, best, ny)


print(f"backend={jax.default_backend()} E={E} P={P} B={B} unroll={UNROLL}",
      flush=True)
timeit("floor (trivial body)", scan_over(step_floor), compact, pen)
timeit("score+argmax", scan_over(step_score), compact, pen)
timeit("score+window-select", scan_over(step_select), compact, pen)
timeit("full (incl refill/shift)", scan_over(step_full), compact, pen)


# --- finer bisect: what inside score+argmax costs ---
def step_ew_only(carry, xs, compact_l):
    """Elementwise score math, NO reductions (winner = rotating slot)."""
    j, slot, cursor = carry
    i, pen_i = xs
    jp1 = (j + 1).astype(jnp.float32)
    free_cpu = 1.0 - (slot[:, 1] + jp1 * 0.5) / jnp.maximum(slot[:, 3], 1e-9)
    free_mem = 1.0 - (slot[:, 2] + jp1 * 0.5) / jnp.maximum(slot[:, 4], 1e-9)
    final = 18.0 - jnp.exp2(-10.0 * free_cpu) - jnp.exp2(-10.0 * free_mem)
    oh_w = arangeB == (i % B)
    j2 = j + oh_w.astype(jnp.int32) + (final > 17.0).astype(jnp.int32)
    return (j2, slot, cursor), (i % B, final[0], i)


def step_argmax_only(carry, xs, compact_l):
    """Minimal elementwise + argmax reduction."""
    j, slot, cursor = carry
    i, pen_i = xs
    eff = slot[:, 0] - j.astype(jnp.float32)
    w = jnp.argmax(eff)
    oh_w = arangeB == w
    j2 = j + oh_w.astype(jnp.int32)
    return (j2, slot, cursor), (w, jnp.max(eff), i)


def step_argmax_noout(carry, xs, compact_l):
    """argmax chain with SCALAR-free outputs (no per-step ys writes)."""
    j, slot, cursor = carry
    i, pen_i = xs
    eff = slot[:, 0] - j.astype(jnp.float32)
    w = jnp.argmax(eff)
    oh_w = arangeB == w
    j2 = j + oh_w.astype(jnp.int32)
    return (j2, slot, cursor), None


def scan_noout(step):
    def one_lane(compact_l, pen_l):
        slot0 = compact_l[:B]
        carry0 = (jnp.zeros(B, jnp.int32), slot0, jnp.int32(B))
        out, _ = jax.lax.scan(
            functools.partial(step, compact_l=compact_l), carry0,
            (jnp.arange(P, dtype=jnp.int32), pen_l), unroll=UNROLL)
        return out[0]
    return jax.vmap(one_lane)


timeit("ew-score only (no reduce)", scan_over(step_ew_only), compact, pen)
timeit("argmax only", scan_over(step_argmax_only), compact, pen)
timeit("argmax, no ys outputs", scan_noout(step_argmax_noout), compact, pen)

# --- E scaling at fixed P (latency-bound => ~flat) ---
for e2 in (64, 128, 256):
    k2 = jax.random.PRNGKey(e2)
    c2 = jax.random.uniform(k2, (e2, C, 12), dtype=jnp.float32) + 1.0
    p2 = jnp.zeros((e2, P), dtype=jnp.int32) - 1
    med = timeit(f"full @ E={e2}", scan_over(step_full), c2, p2)
    print(f"   -> {e2*P/med/1e6:.2f}M placements/s", flush=True)


# --- in-dispatch repeat: amortize the dispatch RTT out of the measurement
# (one jit call runs the kernel R times, chained through a data dep) ---
def chained(step, R):
    def run(compact_b, pen_b):
        def once(x, _):
            c2 = compact_b + x * 1e-12
            ys = scan_over(step)(c2, pen_b)
            # fold outputs to a scalar that feeds the next iteration
            s = ys[1].sum()
            return s, s
        out, _ = jax.lax.scan(once, jnp.float32(0), None, length=R)
        return out
    return run


for R in (1, 4, 16):
    f = jax.jit(chained(step_full, R))
    _ = np.asarray(f(compact, pen))
    ts = []
    for _ in range(4):
        t0 = time.perf_counter()
        _ = np.asarray(f(compact, pen))
        ts.append(time.perf_counter() - t0)
    print(f"full kernel xR={R:<3} sync median {statistics.median(ts)*1000:8.2f}ms",
          flush=True)
