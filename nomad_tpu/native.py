"""ctypes bindings for the native tensorization kernels (native/
pack_kernels.cc), with pure-numpy fallbacks when no C++ compiler exists.

The native boundary mirrors where the reference keeps native code
(SURVEY.md section 2.4): performance-critical runtime components, here the
struct->tensor marshalling path of the TPU solver.

The library is built from the checked-out source on first use and lives
at a path named by a digest of that source and the compiler flags, so
what loads is always what git holds: a library left behind by other
source, another checkout or a hand build is simply never at that path.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

PORT_WORDS = 2048
MAX_PORTS_PER_ALLOC = 8

# Bumped whenever the C ABI changes shape; load() refuses a library that
# answers otherwise instead of corrupting memory.
ABI_VERSION = 4

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
SOURCE = os.path.join(_NATIVE_DIR, "pack_kernels.cc")
# no -march=native: the tree is copied between machines as it stands on
# disk, and a library tuned to the CPU it was built on may not run on
# the next one. No contraction into fused multiply-adds either: the wave
# precompute must round as numpy does, operation by operation.
CXXFLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-ffp-contract=off")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_load_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    """native/pack_kernels.cc could not be compiled on this machine."""


def native_cp_enabled() -> bool:
    """Kill switch for the native control-plane hot paths (plan verify,
    delta-advanced snapshots, lazy alloc materialization). Default on;
    ``NOMAD_TPU_NATIVE_CP=0`` restores the pre-native Python paths
    bit-for-bit (the parity oracle)."""
    return os.environ.get("NOMAD_TPU_NATIVE_CP", "") != "0"


def library_path() -> str:
    """Where the library for THIS source and these flags lives."""
    h = hashlib.sha256(" ".join(CXXFLAGS).encode())
    with open(SOURCE, "rb") as f:
        h.update(f.read())
    return os.path.join(_NATIVE_DIR, "build",
                        f"libnomad_tpu_native-{h.hexdigest()[:16]}.so")


def build(timeout_s: int = 120) -> str:
    """Compile the library from source, replacing whatever sits at
    ``library_path()``; returns that path. The rename is atomic, so
    concurrent builders and loaders only ever see a whole file."""
    out = library_path()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = f"{out}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        subprocess.run(["g++", *CXXFLAGS, "-o", tmp, SOURCE],
                       check=True, capture_output=True, timeout=timeout_s)
        os.replace(tmp, out)
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", b"") or b""
        raise NativeBuildError(
            f"g++ {' '.join(CXXFLAGS)} {SOURCE}: {e}\n"
            f"{detail.decode(errors='replace')[-2000:]}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return out


def load() -> Optional[ctypes.CDLL]:
    """The library for the checked-out source, built now if it is not
    there yet. None (the numpy/Python paths take over, and say so in the
    log) only when it cannot be built or loaded here."""
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    with _load_lock:
        if not _load_attempted:
            try:
                _lib = _load_locked()
            except (NativeBuildError, OSError) as e:
                from .server.logbroker import log
                log("warn", "native",
                    f"native kernels unavailable, Python paths in use: {e}")
            _load_attempted = True
    return _lib


def _load_locked() -> ctypes.CDLL:
    path = library_path()
    if not os.path.exists(path):
        build()
    lib = ctypes.CDLL(path)
    if lib.nt_abi_version() != ABI_VERSION:
        raise OSError(f"{path} reports ABI {lib.nt_abi_version()}, "
                      f"bindings expect {ABI_VERSION}")
    d = ctypes.POINTER(ctypes.c_double)
    i32 = ctypes.POINTER(ctypes.c_int32)
    i64 = ctypes.POINTER(ctypes.c_int64)
    i8 = ctypes.POINTER(ctypes.c_int8)
    u8 = ctypes.POINTER(ctypes.c_uint8)
    u32 = ctypes.POINTER(ctypes.c_uint32)
    u64 = ctypes.POINTER(ctypes.c_uint64)
    lib.nt_pack_usage.argtypes = [
        i32, d, d, d, u8, i32, ctypes.c_int64, ctypes.c_int32,
        i32, i32, d, d, d, i32, u32, ctypes.c_int64]
    lib.nt_count_placed.argtypes = [
        i32, u64, u64, u8, ctypes.c_int64, ctypes.c_uint64,
        ctypes.c_uint64, i32, i32, ctypes.c_int64]
    lib.nt_static_ports_free.argtypes = [
        u32, ctypes.c_int64, i32, ctypes.c_int32, u8]
    lib.nt_verify_fit.argtypes = [d, d, d, d, d, d, d, d, d,
                                  ctypes.c_int64, i32]
    lib.nt_verify_plan.argtypes = [
        d, d, d, u8,                          # table columns
        i64, i32, i8, ctypes.c_int64,         # row deltas
        i32, d, d, d, i8, ctypes.c_int64,     # direct ask entries
        d, d, d,                              # caps
        d, d, d, d, d, d,                     # used/ask accumulators
        ctypes.c_int64, i32]
    lib.nt_solve_eval.argtypes = [
        ctypes.c_int32, d, d, d, d, d, d, i32, u8,
        ctypes.c_uint64, ctypes.c_double, ctypes.c_double,
        ctypes.c_double, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_double, ctypes.c_int32,
        ctypes.c_int32, i32, i32]
    lib.nt_shuffled_order.argtypes = [ctypes.c_uint64, ctypes.c_int32,
                                      i32]
    v = ctypes.c_void_p
    lib.nt_wave_compact.argtypes = [
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_double, ctypes.c_double, ctypes.c_double,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        *[v] * 14,                            # node arrays
        ctypes.c_int64, *[v] * 7,             # the patch
        v]
    lib.nt_wave_compact.restype = ctypes.c_int64
    return lib


def available() -> bool:
    return load() is not None


def shuffled_order(seed: int, n: int) -> Optional[np.ndarray]:
    """The deterministic per-eval Fisher-Yates permutation (identical to
    scheduler/util.py shuffled_order) computed natively; None when the
    library is absent."""
    lib = load()
    if lib is None:
        return None
    out = np.empty(n, dtype=np.int32)
    lib.nt_shuffled_order(seed, n, _ptr(out, ctypes.c_int32))
    return out


def solve_eval(cpu_cap: np.ndarray, mem_cap: np.ndarray, disk_cap: np.ndarray,
               used_cpu: np.ndarray, used_mem: np.ndarray,
               used_disk: np.ndarray, placed_jobtg: np.ndarray,
               eligible: np.ndarray, shuffle_seed: int,
               ask_cpu: float, ask_mem: float, ask_disk: float,
               desired_count: int, limit: int, n_placements: int,
               spread_alg: bool = False, max_skip: int = 3,
               skip_threshold: float = 0.0) -> Optional[np.ndarray]:
    """Run the compiled host-baseline oracle: n_placements sequential
    window-limited binpack selections with usage carry (the reference's
    per-eval inner loop, scheduler/rank.go:205 + stack.go:82-95). Mutates
    used_* and placed_jobtg in place; returns chosen node index per
    placement (-1 = no placement), or None when the library is absent."""
    lib = load()
    if lib is None:
        return None
    n = len(cpu_cap)
    for arr, dt in ((cpu_cap, np.float64), (mem_cap, np.float64),
                    (disk_cap, np.float64), (used_cpu, np.float64),
                    (used_mem, np.float64), (used_disk, np.float64),
                    (placed_jobtg, np.int32), (eligible, np.uint8)):
        if arr.dtype != dt or not arr.flags["C_CONTIGUOUS"]:
            raise ValueError("solve_eval requires contiguous typed arrays")
    order = np.empty(n, dtype=np.int32)
    out_choice = np.empty(n_placements, dtype=np.int32)
    lib.nt_solve_eval(
        n, _ptr(cpu_cap, ctypes.c_double), _ptr(mem_cap, ctypes.c_double),
        _ptr(disk_cap, ctypes.c_double), _ptr(used_cpu, ctypes.c_double),
        _ptr(used_mem, ctypes.c_double), _ptr(used_disk, ctypes.c_double),
        _ptr(placed_jobtg, ctypes.c_int32), _ptr(eligible, ctypes.c_uint8),
        shuffle_seed, float(ask_cpu), float(ask_mem), float(ask_disk),
        desired_count, limit, max_skip, skip_threshold, n_placements,
        1 if spread_alg else 0, _ptr(order, ctypes.c_int32),
        _ptr(out_choice, ctypes.c_int32))
    return out_choice


def wave_compact(dtype, p: int, rows: int, ask, n_dyn: int,
                 has_static: bool, distinct: bool, distinct_job_level: bool,
                 caps, used, dyn_avail, static_free, placed, placed_job,
                 feasible, affinity, cap_extra, spread_vidx, patch=None
                 ) -> Optional[Tuple[np.ndarray, int]]:
    """One wave lane's compact candidate table, (rows, 8 + S) of
    ``dtype`` (float32 or float64), exactly as
    solver/binpack.py wavefront_compact_host builds it, in one call that
    stops at the rows-th node with room; returns it with the number of
    nodes walked, or None when the library is absent. ``caps`` and
    ``used`` are (cpu, mem, disk) node arrays, ``ask`` the lane's three
    asks; ``affinity`` and ``cap_extra`` (a further per-node capacity)
    may be None. ``patch`` (ascending positions, then used cpu, mem,
    disk, dynamic ports, placed, placed_job there) overrides the node
    arrays at those positions."""
    lib = load()
    if lib is None:
        return None
    dt = np.dtype(dtype)
    if dt not in (np.float32, np.float64):
        raise ValueError(f"wave_compact: no kernel for {dt}")
    f = [np.ascontiguousarray(a, dtype=dt) for a in (*caps, *used)]
    i32 = [np.ascontiguousarray(a, dtype=np.int32)
           for a in (dyn_avail, placed, placed_job)]
    b8 = [np.ascontiguousarray(a, dtype=np.bool_).view(np.uint8)
          for a in (static_free, feasible)]
    aff = (None if affinity is None
           else np.ascontiguousarray(affinity, dtype=dt))
    extra = (None if cap_extra is None
             else np.ascontiguousarray(cap_extra, dtype=np.int64))
    vidx = np.ascontiguousarray(spread_vidx, dtype=np.int32)
    n, s = f[0].shape[0], vidx.shape[0]
    if patch is None:
        n_patch, pt = 0, [None] * 7
    else:
        n_patch = len(patch[0])
        pt = [np.ascontiguousarray(patch[0], dtype=np.int64),
              *[np.ascontiguousarray(a, dtype=dt) for a in patch[1:4]],
              *[np.ascontiguousarray(a, dtype=np.int32)
                for a in patch[4:7]]]
    node_arrays = [*f, *i32, *b8] + [a for a in (aff, extra) if a is not None]
    if (any(a.shape != (n,) for a in node_arrays)
            or vidx.shape != (s, n)
            or any(a.shape != (n_patch,) for a in pt if a is not None)):
        raise ValueError("wave_compact: arrays disagree on their lengths")
    compact = np.empty((rows, 8 + s), dtype=dt)

    def addr(a):
        return None if a is None else a.ctypes.data
    scanned = lib.nt_wave_compact(
        dt.itemsize, n, p, rows, s, float(ask[0]),
        float(ask[1]), float(ask[2]), n_dyn, int(has_static),
        int(distinct), int(distinct_job_level),
        *[addr(a) for a in f], addr(i32[0]), addr(b8[0]), addr(i32[1]),
        addr(i32[2]), addr(b8[1]), addr(aff), addr(extra), addr(vidx),
        n_patch, *[addr(a) for a in pt], addr(compact))
    return compact, int(scanned)


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def pack_usage(node_slot: np.ndarray, cpu: np.ndarray, mem: np.ndarray,
               disk: np.ndarray, live: np.ndarray,
               ports: Optional[np.ndarray],
               dyn_lo: np.ndarray, dyn_hi: np.ndarray, n_pad: int,
               port_words_seed: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, ...]:
    """Fold the alloc table into node-axis usage tensors. All row arrays are
    length n_rows; ports is (n_rows, MAX_PORTS_PER_ALLOC) int32 (-1 empty)
    or None to skip port folding entirely.
    Returns (used_cpu, used_mem, used_disk, dyn_used, port_words);
    port_words is None when no port state exists."""
    n_rows = len(node_slot)
    used_cpu = np.zeros(n_pad, dtype=np.float64)
    used_mem = np.zeros(n_pad, dtype=np.float64)
    used_disk = np.zeros(n_pad, dtype=np.float64)
    dyn_used = np.zeros(n_pad, dtype=np.int32)
    # The bitmap is 80MB at 10K nodes; only materialize when port state
    # exists (seed present or any row carries ports).
    has_ports = (ports is not None and n_rows
                 and bool((ports[:, 0] >= 0).any()))
    if port_words_seed is None and not has_ports:
        port_words = None
    else:
        port_words = (port_words_seed.copy() if port_words_seed is not None
                      else np.zeros((n_pad, PORT_WORDS), dtype=np.uint32))
    max_ports = MAX_PORTS_PER_ALLOC if ports is not None else 0
    lib = load()
    if lib is not None and n_rows:
        node_slot = np.ascontiguousarray(node_slot, dtype=np.int32)
        cpu = np.ascontiguousarray(cpu, dtype=np.float64)
        mem = np.ascontiguousarray(mem, dtype=np.float64)
        disk = np.ascontiguousarray(disk, dtype=np.float64)
        live = np.ascontiguousarray(live, dtype=np.uint8)
        if ports is not None:
            ports = np.ascontiguousarray(ports, dtype=np.int32)
        dyn_lo = np.ascontiguousarray(dyn_lo, dtype=np.int32)
        dyn_hi = np.ascontiguousarray(dyn_hi, dtype=np.int32)
        lib.nt_pack_usage(
            _ptr(node_slot, ctypes.c_int32), _ptr(cpu, ctypes.c_double),
            _ptr(mem, ctypes.c_double), _ptr(disk, ctypes.c_double),
            _ptr(live, ctypes.c_uint8),
            (_ptr(ports, ctypes.c_int32) if ports is not None else None),
            n_rows, max_ports,
            _ptr(dyn_lo, ctypes.c_int32), _ptr(dyn_hi, ctypes.c_int32),
            _ptr(used_cpu, ctypes.c_double), _ptr(used_mem, ctypes.c_double),
            _ptr(used_disk, ctypes.c_double), _ptr(dyn_used, ctypes.c_int32),
            (_ptr(port_words, ctypes.c_uint32)
             if port_words is not None else None), n_pad)
        return used_cpu, used_mem, used_disk, dyn_used, port_words

    # numpy fallback
    mask = (live != 0) & (node_slot >= 0) & (node_slot < n_pad)
    slots = node_slot[mask]
    np.add.at(used_cpu, slots, cpu[mask])
    np.add.at(used_mem, slots, mem[mask])
    np.add.at(used_disk, slots, disk[mask])
    if port_words is not None and ports is not None:
        for i in np.nonzero(mask)[0]:
            slot = node_slot[i]
            for p in ports[i]:
                if p < 0:
                    break
                if p >= 65536:
                    continue
                word, bit = p >> 5, np.uint32(1 << (p & 31))
                if not port_words[slot, word] & bit:
                    port_words[slot, word] |= bit
                    if dyn_lo[slot] <= p <= dyn_hi[slot]:
                        dyn_used[slot] += 1
    return used_cpu, used_mem, used_disk, dyn_used, port_words


def count_placed(node_slot: np.ndarray, job_hash: np.ndarray,
                 jobtg_hash: np.ndarray, live: np.ndarray,
                 want_job: int, want_jobtg: int, n_pad: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    placed = np.zeros(n_pad, dtype=np.int32)
    placed_job = np.zeros(n_pad, dtype=np.int32)
    n_rows = len(node_slot)
    lib = load()
    if lib is not None and n_rows:
        node_slot = np.ascontiguousarray(node_slot, dtype=np.int32)
        job_hash = np.ascontiguousarray(job_hash, dtype=np.uint64)
        jobtg_hash = np.ascontiguousarray(jobtg_hash, dtype=np.uint64)
        live = np.ascontiguousarray(live, dtype=np.uint8)
        lib.nt_count_placed(
            _ptr(node_slot, ctypes.c_int32), _ptr(job_hash, ctypes.c_uint64),
            _ptr(jobtg_hash, ctypes.c_uint64), _ptr(live, ctypes.c_uint8),
            n_rows, want_job, want_jobtg,
            _ptr(placed, ctypes.c_int32), _ptr(placed_job, ctypes.c_int32),
            n_pad)
        return placed, placed_job
    mask = (live != 0) & (node_slot >= 0) & (node_slot < n_pad) & \
        (job_hash == want_job)
    np.add.at(placed_job, node_slot[mask], 1)
    mask_tg = mask & (jobtg_hash == want_jobtg)
    np.add.at(placed, node_slot[mask_tg], 1)
    return placed, placed_job


def static_ports_free(port_words: np.ndarray,
                      check_ports: np.ndarray) -> np.ndarray:
    n_pad = port_words.shape[0]
    out = np.ones(n_pad, dtype=np.uint8)
    n_ports = len(check_ports)
    if n_ports == 0:
        return out.astype(bool)
    lib = load()
    if lib is not None:
        pw = np.ascontiguousarray(port_words, dtype=np.uint32)
        cp = np.ascontiguousarray(check_ports, dtype=np.int32)
        lib.nt_static_ports_free(
            _ptr(pw, ctypes.c_uint32), n_pad,
            _ptr(cp, ctypes.c_int32), n_ports, _ptr(out, ctypes.c_uint8))
        return out.astype(bool)
    for p in check_ports:
        if p < 0 or p >= 65536:
            continue
        word, bit = int(p) >> 5, np.uint32(1 << (int(p) & 31))
        out &= ((port_words[:, word] & bit) == 0).astype(np.uint8)
    return out.astype(bool)


def verify_fit(cpu_cap, mem_cap, disk_cap, used_cpu, used_mem, used_disk,
               ask_cpu, ask_mem, ask_disk) -> np.ndarray:
    """Batch node-axis fit verification. Returns failing dim per node
    (0 ok, 1 cpu, 2 memory, 3 disk)."""
    n = len(cpu_cap)
    out = np.zeros(n, dtype=np.int32)
    lib = load()
    if lib is not None and n:
        args = [np.ascontiguousarray(a, dtype=np.float64) for a in
                (cpu_cap, mem_cap, disk_cap, used_cpu, used_mem, used_disk,
                 ask_cpu, ask_mem, ask_disk)]
        lib.nt_verify_fit(*[_ptr(a, ctypes.c_double) for a in args],
                          n, _ptr(out, ctypes.c_int32))
        return out
    out = np.where(used_cpu + ask_cpu > cpu_cap, 1,
                   np.where(used_mem + ask_mem > mem_cap, 2,
                            np.where(used_disk + ask_disk > disk_cap, 3, 0)))
    return out.astype(np.int32)


def verify_plan(tbl_cpu, tbl_mem, tbl_disk, tbl_live_strict,
                d_row, d_pos, d_sign, a_pos, a_cpu, a_mem, a_disk,
                a_into_used, cpu_cap, mem_cap, disk_cap,
                used_cpu, used_mem, used_disk) -> np.ndarray:
    """Whole-group plan verification: apply a plan group's row-backed
    deltas (``used[d_pos] += d_sign * tbl[d_row]`` where the row is still
    live_strict) and direct value entries (into used for in-flight overlay
    adds, into ask for this group's placements), then compare
    ``used + ask`` against caps per node. Entries apply strictly in order,
    so float accumulation matches the Python oracle's traversal order.
    Mutates used_* in place; returns failing dim per node (0 ok, 1 cpu,
    2 memory, 3 disk). The GIL is released for the whole call when the
    library is loaded; the fallback applies the same entries in the same
    order in Python, bitwise-identical."""
    n = len(cpu_cap)
    n_delta, n_ask = len(d_row), len(a_pos)
    out = np.zeros(n, dtype=np.int32)
    ask_c = np.zeros(n, dtype=np.float64)
    ask_m = np.zeros(n, dtype=np.float64)
    ask_d = np.zeros(n, dtype=np.float64)
    lib = load()
    if lib is not None and n:
        tbl = [np.ascontiguousarray(a, dtype=np.float64)
               for a in (tbl_cpu, tbl_mem, tbl_disk)]
        ls = np.ascontiguousarray(tbl_live_strict, dtype=np.uint8)
        d_row = np.ascontiguousarray(d_row, dtype=np.int64)
        d_pos = np.ascontiguousarray(d_pos, dtype=np.int32)
        d_sign = np.ascontiguousarray(d_sign, dtype=np.int8)
        a_pos = np.ascontiguousarray(a_pos, dtype=np.int32)
        a_c, a_m, a_d = [np.ascontiguousarray(a, dtype=np.float64)
                         for a in (a_cpu, a_mem, a_disk)]
        a_iu = np.ascontiguousarray(a_into_used, dtype=np.int8)
        caps = [np.ascontiguousarray(a, dtype=np.float64)
                for a in (cpu_cap, mem_cap, disk_cap)]
        lib.nt_verify_plan(
            *[_ptr(a, ctypes.c_double) for a in tbl],
            _ptr(ls, ctypes.c_uint8),
            _ptr(d_row, ctypes.c_int64), _ptr(d_pos, ctypes.c_int32),
            _ptr(d_sign, ctypes.c_int8), n_delta,
            _ptr(a_pos, ctypes.c_int32),
            _ptr(a_c, ctypes.c_double), _ptr(a_m, ctypes.c_double),
            _ptr(a_d, ctypes.c_double), _ptr(a_iu, ctypes.c_int8), n_ask,
            *[_ptr(a, ctypes.c_double) for a in caps],
            _ptr(used_cpu, ctypes.c_double), _ptr(used_mem, ctypes.c_double),
            _ptr(used_disk, ctypes.c_double),
            _ptr(ask_c, ctypes.c_double), _ptr(ask_m, ctypes.c_double),
            _ptr(ask_d, ctypes.c_double), n, _ptr(out, ctypes.c_int32))
        return out

    # numpy fallback: entries apply one at a time in order, so the float
    # accumulation order is identical to the C loop (bitwise parity)
    for e in range(n_delta):
        row = int(d_row[e])
        if not tbl_live_strict[row]:
            continue
        k, s = int(d_pos[e]), float(d_sign[e])
        used_cpu[k] += s * tbl_cpu[row]
        used_mem[k] += s * tbl_mem[row]
        used_disk[k] += s * tbl_disk[row]
    for e in range(n_ask):
        k = int(a_pos[e])
        if a_into_used[e]:
            used_cpu[k] += a_cpu[e]
            used_mem[k] += a_mem[e]
            used_disk[k] += a_disk[e]
        else:
            ask_c[k] += a_cpu[e]
            ask_m[k] += a_mem[e]
            ask_d[k] += a_disk[e]
    return verify_fit(cpu_cap, mem_cap, disk_cap, used_cpu, used_mem,
                      used_disk, ask_c, ask_m, ask_d)
