#!/usr/bin/env python
"""Benchmark: the scheduler's placement inner loop, TPU solver vs host oracle.

Measures the north-star hot loop (BASELINE.json): per-placement feasibility +
bin-pack scoring + selection over a 10K-node fleet (config tier 3/4 shape:
cpu+mem+disk constraints), comparing
  - host oracle: the faithful reimplementation of Nomad's iterator stack
    (scheduler/rank.go BinPackIterator + selection), one Stack.Select per
    placement -- the reference algorithm at reference semantics;
  - TPU solver: the same placements solved as one dense lax.scan dispatch
    (nomad_tpu/solver/binpack.py), verified to produce IDENTICAL placements.

Both paths run the SAME number of placements from the same initial world, so
vs_baseline compares equal, parity-verified work. Parity is GATING: any
placement mismatch prints the JSON line (for the record) and exits non-zero.

Platform selection is JAX's own: the bench runs on whatever
``jax.devices()`` gives it and says which in its first log line and in
the JSON (``platform``). To insist on the chip, run it with
``JAX_PLATFORMS=tpu`` -- JAX then fails at start-up when it finds none,
and so does the bench; there is no CPU fallback. A leg that raises is
logged, the JSON line still goes out with the other legs' numbers and
``failed_legs`` naming it, and the exit code is non-zero.

Prints ONE JSON line {"metric","value","unit","vs_baseline",...} on stdout;
all diagnostics go to stderr.
"""
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Quiet XLA's native C++ logging: persistent-cache AOT loads print a
# screenful of benign machine-feature diffs at ERROR level per entry
# (cpu_aot_loader.cc ignores TF_CPP_MIN_LOG_LEVEL), which would crowd
# the driver-captured log tail out of useful content. Filter them out at
# the fd level so native writes are caught too.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")


def _filter_native_stderr():
    import atexit
    import threading
    real = os.dup(2)
    r, w = os.pipe()
    os.dup2(w, 2)
    os.close(w)

    def emit(data: bytes) -> None:
        try:
            os.write(real, data)
        except OSError:
            pass        # real stderr gone; keep draining so fd 2 never
                        # fills and blocks the bench

    def pump():
        buf = b""
        while True:
            try:
                chunk = os.read(r, 65536)
            except OSError:
                break
            if not chunk:
                break
            buf += chunk
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                if b"cpu_aot_loader" not in line:
                    emit(line + b"\n")
        if buf:
            emit(buf)

    t = threading.Thread(target=pump, daemon=True)
    t.start()

    def restore():
        # point fd 2 back at the real stderr; dropping the pipe's last
        # write end EOFs the pump so it drains the tail (incl. any final
        # parity-failure lines) before interpreter teardown
        sys.stderr.flush()
        os.dup2(real, 2)
        t.join(timeout=5.0)

    atexit.register(restore)


_filter_native_stderr()

N_NODES = int(os.environ.get("BENCH_NODES", "10000"))
N_PLACEMENTS = int(os.environ.get("BENCH_PLACEMENTS", "2000"))
N_REPEATS = max(1, int(os.environ.get("BENCH_REPEATS", "5")))
N_ORACLE_RUNS = max(1, int(os.environ.get("BENCH_ORACLE_RUNS", "2")))

def log(*args):
    print(*args, file=sys.stderr, flush=True)


# legs that raised: the JSON line still goes out, the exit code says so
_FAILED_LEGS: list = []


def leg_failed(leg: str, e: BaseException) -> None:
    _FAILED_LEGS.append(leg)
    log(f"bench: {leg} failed: {e!r}")


def report_platform() -> str:
    """The platform JAX came up on, logged before anything is timed."""
    import jax
    devs = jax.devices()
    log(f"bench: running on {devs[0].platform} ({devs[0].device_kind}, "
        f"{len(devs)} device(s))")
    return devs[0].platform


def build_world():
    from nomad_tpu import mock
    from nomad_tpu.scheduler import Harness

    h = Harness()
    nodes = []
    for i in range(N_NODES):
        n = mock.node()
        n.id = f"bench-node-{i:06d}"
        n.node_resources.cpu.cpu_shares = (2000, 4000, 8000)[i % 3]
        n.node_resources.memory.memory_mb = (4096, 8192, 16384)[i % 3]
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    job = mock.job(id="bench-job")
    job.task_groups[0].count = N_PLACEMENTS
    h.state.upsert_job(job)
    return h, job, nodes


def time_host_inner_loop(h, job, nodes, n_placements):
    """One Stack.Select per placement, usage carried via the plan --
    exactly the reference's per-eval inner loop."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.stack import GenericStack, SelectOptions
    from nomad_tpu.structs import (
        AllocatedResources, AllocatedSharedResources, Allocation, Plan,
        generate_uuid)

    plan = Plan(eval_id="bench-eval-0000000000000001", priority=50, job=job)
    snap = h.state.snapshot()
    ctx = EvalContext(snap, plan)
    stack = GenericStack(False, ctx)
    stack.set_job(job)
    stack.set_nodes(list(nodes))
    tg = job.task_groups[0]

    t0 = time.perf_counter()
    placed = {}
    for i in range(n_placements):
        name = f"{job.id}.{tg.name}[{i}]"
        option = stack.select(tg, SelectOptions(alloc_name=name))
        if option is None:
            placed[name] = None
            continue
        alloc = Allocation(
            id=generate_uuid(), name=name, job_id=job.id, job=job,
            task_group=tg.name, node_id=option.node.id,
            allocated_resources=AllocatedResources(
                tasks=dict(option.task_resources),
                shared=AllocatedSharedResources(
                    disk_mb=tg.ephemeral_disk.size_mb)))
        plan.append_alloc(alloc)
        placed[name] = option.node.id
    dt = time.perf_counter() - t0
    return dt, placed


def time_native_oracle(h, job, nodes, n_placements, runs=5):
    """The compiled-host baseline: the same inner loop as
    time_host_inner_loop but as C++ over packed arrays (native/
    pack_kernels.cc nt_solve_eval) -- the strongest plausible host
    implementation of the reference algorithm (a lower bound on what the
    Go BinPackIterator costs; the real reference walks structs/maps per
    candidate). Packing is untimed: the Go path starts from structs
    already resident in memory. Returns (best_dt, placed) or (None, None)
    when the native library can't be built."""
    from nomad_tpu import native
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.native_oracle import PackedWorld, solve
    from nomad_tpu.structs import Plan

    if not native.available():
        return None, None
    import numpy as np

    tg = job.task_groups[0]
    plan = Plan(eval_id="bench-eval-0000000000000001", priority=50, job=job)
    snap = h.state.snapshot()
    ctx = EvalContext(snap, plan)
    world = PackedWorld(nodes, ctx, job, tg)
    base = {k: (v.copy() if isinstance(v, np.ndarray) else v)
            for k, v in world.__dict__.items()}
    best = None
    placed_idx = None
    for _ in range(runs):
        w = PackedWorld.__new__(PackedWorld)
        w.__dict__.update({k: (v.copy() if isinstance(v, np.ndarray) else v)
                           for k, v in base.items()})
        t0 = time.perf_counter()
        placed_idx = solve(w, plan.eval_id, snap.latest_index(),
                           n_placements, tg.count)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    placed = {f"{job.id}.{tg.name}[{i}]": nid
              for i, nid in placed_idx.items()}
    return best, placed


def time_batched_path(n_nodes, e_evals, per_eval):
    """The production batched path (the designed TPU win): E distinct jobs
    -> E evals coalesced by the BatchWorker, their dense solves fused into
    one device dispatch at the SolveBarrier, plans serially verified by the
    applier. Measures wall time for a full warmed round. Returns
    (dt, n_evals, n_placed)."""
    from nomad_tpu import mock
    from nomad_tpu.server import Server
    from nomad_tpu.structs import SchedulerConfiguration

    server = Server(num_workers=e_evals, heartbeat_ttl=3600.0,
                    eval_batching=True, batch_width=e_evals)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    server.start()
    try:
        for i in range(n_nodes):
            n = mock.node()
            n.id = f"bbench-node-{i:06d}"
            n.node_resources.cpu.cpu_shares = (2000, 4000, 8000)[i % 3]
            n.node_resources.memory.memory_mb = (4096, 8192, 16384)[i % 3]
            n.compute_class()
            server.register_node(n)

        def run_round(tag):
            jobs = []
            for i in range(e_evals):
                job = mock.job(id=f"bbench-{tag}-{i}")
                job.task_groups[0].count = per_eval
                jobs.append(job)
            t0 = time.perf_counter()
            for job in jobs:
                server.register_job(job)
            want = e_evals * per_eval
            deadline = time.time() + 600
            while time.time() < deadline:
                # O(1) index counts while waiting: the full object-list
                # scan (64K allocs at headline shape) 50x/s from this
                # thread was stealing GIL time from the pipeline it
                # measures; the exact desired_status check runs once the
                # cheap count says the round might be done
                approx = sum(
                    server.state.num_allocs_by_job(job.namespace, job.id)
                    for job in jobs)
                if approx >= want:
                    placed = sum(
                        1 for job in jobs
                        for a in server.state.allocs_by_job(
                            job.namespace, job.id)
                        if a.desired_status == "run")
                    if placed >= want:
                        break
                time.sleep(0.02)
            else:
                placed = sum(
                    1 for job in jobs
                    for a in server.state.allocs_by_job(job.namespace, job.id)
                    if a.desired_status == "run")
            return time.perf_counter() - t0, placed, jobs

        def drain_round(jobs):
            """Free a round's capacity before the next one: at headline
            shape (32x2000x500MHz = 32M shares) one round consumes ~70% of
            the 10K-node cluster, so a measured round after an undrained
            warm round runs into capacity exhaustion and blocks forever
            (that was BENCH_r04's TRUNCATED 29,328/64,000). Matching the
            reference's semantics, capacity frees only when the CLIENT
            acknowledges the stop (ProposedAllocs filters client-terminal
            only, context.go:200); this bench has no client agents, so
            acknowledge the server-side stops here the way a fleet of
            clients would (node_endpoint.go:1322 UpdateAlloc)."""
            for job in jobs:
                server.deregister_job(job.namespace, job.id)
            deadline = time.time() + 120
            live = -1
            while time.time() < deadline:
                live = sum(
                    1 for job in jobs
                    for a in server.state.allocs_by_job(job.namespace, job.id)
                    if a.desired_status == "run")
                if live == 0:
                    break
                time.sleep(0.25)   # full-scan poll; unmeasured, keep rare
            if live:
                # warm-round deregister plans are still in flight; a round
                # measured now would share the applier with them, so it
                # must not be published as a clean number (and acking
                # allocs the scheduler hasn't stopped yet would only
                # muddy a post-mortem of the wedged state)
                log(f"bench: WARNING warm-round drain incomplete "
                    f"({live} live); measured round would be contaminated")
                return False
            import copy
            acks = []
            for job in jobs:
                for a in server.state.allocs_by_job(job.namespace, job.id):
                    if not a.client_terminal_status():
                        ack = copy.copy(a)
                        ack.client_status = "complete"
                        acks.append(ack)
            server.update_allocs_from_client(acks)
            return True

        warm_dt, warm_placed, warm_jobs = run_round("warm")
        log(f"bench: batched warmup (incl. compile) {warm_dt:.3f}s "
            f"({warm_placed} placed)")
        if not drain_round(warm_jobs):
            # dt=0 sentinel: the measured round never ran (drain failed)
            return 0.0, e_evals, 0
        dt, placed, _ = run_round("run")
        log(f"bench: applier over the run: "
            f"applied={server.planner.plans_applied} "
            f"rejected={server.planner.plans_rejected} "
            f"group_commits={server.planner.batches_committed}")
        time_batched_path.last_planner_stats = {
            "rejected": server.planner.plans_rejected,
            "group_commits": server.planner.batches_committed,
        }
        # quality + saturation fields captured while this server (the
        # e2e measurement the ROADMAP's next bets are judged by) still
        # owns the observatory -- shutdown detaches it
        from nomad_tpu.benchkit import quality_stamp
        time_batched_path.last_quality = quality_stamp()
        return dt, e_evals, placed
    finally:
        server.shutdown()


def time_lpq(n_nodes, e_evals, per_eval):
    """The whole-queue LP-relaxation tier (ISSUE 8) end to end: E
    distinct jobs coalesced by the LPQ batch worker into joint
    alloc x node solves, rounded + repaired, committed through the
    group applier. Returns a dict of lpq_* artifact fields or None."""
    from nomad_tpu import mock
    from nomad_tpu.server import Server
    from nomad_tpu.solver import lpq as lpq_mod
    from nomad_tpu.structs import SchedulerConfiguration

    env_overrides = {
        # gather the whole registration burst into one joint solve
        "NOMAD_TPU_LPQ_BATCH": os.environ.get(
            "NOMAD_TPU_LPQ_BATCH", str(e_evals)),
        "NOMAD_TPU_LPQ_GATHER_MS": os.environ.get(
            "NOMAD_TPU_LPQ_GATHER_MS", "400"),
    }
    saved = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)
    server = Server(num_workers=e_evals, heartbeat_ttl=3600.0,
                    eval_batching=True, batch_width=e_evals)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-lpq"))
    server.start()
    try:
        for i in range(n_nodes):
            n = mock.node()
            n.id = f"lpq-node-{i:06d}"
            n.node_resources.cpu.cpu_shares = (2000, 4000, 8000)[i % 3]
            n.node_resources.memory.memory_mb = (4096, 8192, 16384)[i % 3]
            n.compute_class()
            server.register_node(n)
        jobs = []
        for i in range(e_evals):
            job = mock.job(id=f"lpq-bench-{i}")
            job.task_groups[0].count = per_eval
            jobs.append(job)
        lpq_mod._reset_for_tests()
        t0 = time.perf_counter()
        for job in jobs:
            server.register_job(job)
        want = e_evals * per_eval
        deadline = time.time() + 600
        placed = 0
        while time.time() < deadline:
            approx = sum(
                server.state.num_allocs_by_job(job.namespace, job.id)
                for job in jobs)
            if approx >= want:
                placed = sum(
                    1 for job in jobs
                    for a in server.state.allocs_by_job(job.namespace,
                                                        job.id)
                    if a.desired_status == "run")
                if placed >= want:
                    break
            time.sleep(0.02)
        dt = time.perf_counter() - t0
        stats = lpq_mod.lpq_stats()
        if placed < want:
            log(f"bench: lpq TRUNCATED ({placed}/{want} placed); "
                f"dropping metric")
            return None
        # zero capacity violations is an acceptance invariant: the
        # repair pass must keep the applier from ever rejecting an
        # LP-tier plan on capacity
        rejected = server.planner.plans_rejected
        log(f"bench: lpq {e_evals} evals x {per_eval} in {dt:.3f}s "
            f"({placed} placed, {placed / dt:.0f} placements/s, "
            f"{stats['evals_per_solve']:.1f} evals/solve, "
            f"repair_rate={stats['repair_rate']:.4f}, "
            f"quality_delta={stats['quality_delta']}, "
            f"applier_rejected={rejected})")
        return {
            "lpq_placements_per_sec": round(placed / dt, 2),
            "lpq_evals_per_solve": round(stats["evals_per_solve"], 2),
            "lpq_repair_rate": round(stats["repair_rate"], 5),
            "lpq_quality_delta": stats["quality_delta"],
            "lpq_frag_delta": stats["frag_delta"],
            "lpq_solves": stats["solves"],
            "lpq_greedy_lanes": stats["greedy_lanes"],
            "lpq_planner_rejected": rejected,
        }
    finally:
        server.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def pack_fused_lanes(h, nodes, e_evals, per_eval, tag="fused-bench"):
    """E distinct jobs' lanes packed from one snapshot -- the input shape
    of the production SolveBarrier solve point. Returns None when any
    lane is solver-ineligible."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService
    from nomad_tpu.structs import Plan

    snap = h.state.snapshot()
    lanes = []
    for i in range(e_evals):
        job = mock.job(id=f"{tag}-{i}")
        job.task_groups[0].count = per_eval
        tg = job.task_groups[0]
        plan = Plan(eval_id=f"{tag}-eval-{i:016d}"[-36:], priority=50,
                    job=job)
        ctx = EvalContext(snap, plan)
        places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                                   task_group=tg)
                  for k in range(per_eval)]
        service = TpuPlacementService(ctx, job, batch_mode=False,
                                      spread_alg=False)
        lane = service.pack(tg, places, nodes)
        if lane is None:
            return None
        lanes.append(lane)
    return lanes


def time_fused_solver(h, nodes, e_evals, per_eval, repeats=3):
    """Solver-only fused throughput: E distinct jobs' lanes packed from one
    snapshot, solved as ONE coalesced dispatch (the production BatchWorker
    solve point, minus the Python control plane that time_batched_path
    includes). Gated: the fused results must equal each lane's solo
    dispatch. Returns (median_dt, n_placed_per_round, mismatch)."""
    from nomad_tpu.solver.batch import fuse_and_solve
    from nomad_tpu.solver.service import dispatch_lane

    lanes = pack_fused_lanes(h, nodes, e_evals, per_eval)
    if lanes is None:
        return None, 0, 0, None

    fused = fuse_and_solve(lanes)           # warmup (incl. compile)
    mismatch = 0
    for lane, res in zip(lanes, fused):
        solo = dispatch_lane(lane)
        if not (res[0] == solo[0]).all():
            mismatch += int((res[0] != solo[0]).sum())
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fused = fuse_and_solve(lanes)
        times.append(time.perf_counter() - t0)
    placed = sum(int((res[0] >= 0).sum()) for res in fused)

    # compute-only: same fused program with device-RESIDENT inputs.
    # Separates chip capability from the host<->device link.
    compute_info = None
    try:
        blocking_dt, marginal_dt, pipelined_dt = _fused_compute_only(
            lanes, repeats)
        compute_info = {"blocking": blocking_dt, "marginal": marginal_dt,
                        "pipelined": pipelined_dt}
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("fused compute-only probe", e)
    return statistics.median(times), placed, mismatch, compute_info


@functools.lru_cache(maxsize=1)
def _mesh_single_device_fn():
    """One single-device jit of the fused greedy program, shared across
    the mesh leg's sweep shapes (jit's own trace cache buckets by shape;
    a fresh jit per call would defeat it). It runs where its inputs
    are: the caller places them on the first device."""
    import jax

    from nomad_tpu.solver.binpack import solve_eval_batch

    return jax.jit(
        functools.partial(solve_eval_batch, spread_alg=False,
                          dtype_name="float32"))


def _per_shard_actual_by_device():
    """Cumulative per-device actual bytes off the xferobs per_shard
    ledger (rows accumulate; callers diff snapshots)."""
    from nomad_tpu.solver import xferobs
    by_dev = {}
    for rows in (xferobs.state().get("per_shard") or {}).values():
        for dev, row in rows.items():
            by_dev[dev] = by_dev.get(dev, 0) + \
                int(row.get("actual_bytes", 0))
    return by_dev


def time_mesh_leg(repeats=3):
    """Multi-chip mesh solve leg (ISSUE 19): the fused greedy program
    through the registered 2D (evals, nodes) mesh factories vs the
    single-device jit of the SAME program, swept over node counts.
    Guarded on >1 attached device AND the NOMAD_TPU_MESH knob -- the
    rollback lever disables this leg exactly as it disables production
    mesh dispatch.  Parity is gating (bit-exact by construction: the
    cross-shard max/argmax is order-insensitive); per-shard shipped
    bytes come off the xferobs per_shard ledger (max over devices for
    the largest sweep shape -- the per-chip HBM ship budget).  On the
    CPU virtual mesh collectives are intra-host copies, so the
    collective overhead reads positive there by design; the walls are
    the headline only on real chips (see OPERATIONS.md "Mesh
    execution")."""
    import jax
    import numpy as np

    from nomad_tpu.parallel import mesh as meshmod

    if not meshmod.mesh_enabled() or jax.device_count() < 2:
        return None

    import __graft_entry__ as graft

    e_evals, per_eval = 8, 16
    mismatch = 0
    sweep = []
    for n_nodes in (256, 512):
        rng = np.random.default_rng(n_nodes)
        lanes = [graft._varied_inputs(rng, n_nodes, per_eval)
                 for _ in range(e_evals)]
        const, init, batch = (
            jax.tree.map(lambda *xs: np.stack(xs),
                         *[lane[i] for lane in lanes])
            for i in range(3))

        ref_fn = _mesh_single_device_fn()
        dev0 = jax.devices()[0]

        def single():
            return jax.block_until_ready(ref_fn(
                *jax.device_put((const, init, batch), dev0)))

        ref = single()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            single()
            times.append(time.perf_counter() - t0)
        single_dt = statistics.median(times)

        mesh = meshmod.make_mesh(min(8, jax.device_count()))
        if mesh is None:
            return None
        shard0 = _per_shard_actual_by_device()
        with mesh:
            s_const, s_init, s_batch = meshmod.shard_solver_inputs(
                mesh, const, init, batch)
            fn = meshmod.mesh_solve_fn(mesh, False, "float32")
            out = jax.block_until_ready(fn(s_const, s_init, s_batch))
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(s_const, s_init, s_batch))
                times.append(time.perf_counter() - t0)
        mesh_dt = statistics.median(times)
        shard1 = _per_shard_actual_by_device()
        shard_bytes = max(
            (shard1.get(d, 0) - shard0.get(d, 0) for d in shard1),
            default=0)

        for i in range(2):
            mismatch += int((np.asarray(out[i])
                             != np.asarray(ref[i])).sum())
        sweep.append({
            "nodes": n_nodes,
            "single_ms": round(single_dt * 1e3, 3),
            "mesh_ms": round(mesh_dt * 1e3, 3),
            "shard_bytes": shard_bytes,
        })

    head = sweep[-1]
    placements = e_evals * per_eval
    return {
        "mesh_pps": round(placements / (head["mesh_ms"] / 1e3), 2)
        if head["mesh_ms"] else 0.0,
        "mesh_shard_bytes": head["shard_bytes"],
        "mesh_collective_ms": round(
            max(0.0, head["mesh_ms"] - head["single_ms"]), 3),
        "mesh_parity_mismatch": mismatch,
        "mesh_grid": [int(x) for x in
                      meshmod.make_mesh(
                          min(8, jax.device_count())).devices.shape],
        "mesh_sweep": sweep,
    }


def _dispatch_rtt():
    """Round-trip latency of a trivial dispatch+fetch (median of 5): the
    floor under ANY blocking per-call timing. Reporting it separately
    lets every other metric be read as (RTT + real work)."""
    import jax
    import numpy as np
    # nomadlint: waive=no-callsite-jit -- one-shot RTT probe program,
    # constructed once per bench run (not a steady-state call site)
    fn = jax.jit(lambda x: x + 1.0)
    x = jax.device_put(np.zeros(8, dtype=np.float32))
    np.asarray(fn(x))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(fn(x))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _fused_compute_only(lanes, repeats=3):
    """On-device cost of the fused wavefront program over E
    pre-transferred lanes.
    Returns (blocking_dt, marginal_dt, pipelined_dt): blocking_dt is
    the classic per-call median (includes one dispatch round trip);
    marginal_dt chains R executions inside ONE dispatch (each feeding a
    data-dependent no-op perturbation to the next, so XLA cannot elide
    them) and takes (t(R) - t(1)) / (R - 1) -- the true steady-state
    per-execution compute, what a pipelined or local-attached
    deployment pays; pipelined_dt is the median per-round cost of a
    depth-R burst of full dispatches (transfer + execute + fetch,
    fetches deferred) -- it still includes one un-overlapped round trip
    amortized over the burst, so it upper-bounds the streaming cost."""
    import functools

    import jax
    import numpy as np
    from nomad_tpu.solver.binpack import (
        _solve_wave_block_impl, _solve_wave_compact_impl,
        _wave_block_enabled, _wave_p_bucket, wavefront_compact_host)

    if not all(lane.ptab is None and lane.wavefront_ok()
               for lane in lanes):
        return None, None, None  # ineligible lane shape: clean skip
    if lanes[0].const.spread_vidx.shape[0]:
        return None, None, None  # spread lanes carry extra tables
    B = lanes[0].wavefront_B()
    p_pad = _wave_p_bucket(max(
        lane.batch.ask_cpu.shape[0] for lane in lanes))
    packs = [wavefront_compact_host(l.const, l.init, l.batch,
                                    l.dtype_name, p_pad=p_pad, B=B)
             for l in lanes]
    compact = np.stack([p[0] for p in packs])
    scal_f = np.stack([p[1] for p in packs])
    scal_i = np.stack([p[2] for p in packs])
    pen = np.stack([p[3] for p in packs])
    # mirror the production kernel choice (solve_lane_wave's gate): the
    # run-block kernel on penalty-free no-spread lanes, else the
    # per-placement compact scan
    use_block = _wave_block_enabled() and bool((pen < 0).all())
    impl = (_solve_wave_block_impl if use_block
            else functools.partial(_solve_wave_compact_impl, sp=None))
    inner = jax.vmap(functools.partial(
        impl, B=B, spread_alg=lanes[0].spread_alg,
        dtype_name=lanes[0].dtype_name))
    # nomadlint: waive=no-callsite-jit -- one-shot bench kernel for this
    # run's fixed shapes; constructed once, timed across its warm calls
    fn = jax.jit(inner)
    dev = jax.device_put((compact, scal_f, scal_i, pen))
    out = fn(*dev)
    out[0].block_until_ready()              # compile + settle
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*dev)
        out[0].block_until_ready()
        times.append(time.perf_counter() - t0)
    blocking_dt = statistics.median(times)

    # marginal: chain R kernel executions inside one dispatch, linked
    # by a scores.sum() * 1e-12 input perturbation -- a real data
    # dependency, so the compiler runs every execution. The perturbation
    # can flip exact-zero columns (affinity, pos) in later iterations,
    # so chained results are NOT parity-grade; the op graph and
    # therefore the timing are identical, which is all this probe uses.
    import jax.numpy as jnp

    def chained(R):
        def run(cm, sf, si, pn):
            def once(x, _):
                ch, sc, ny = inner(cm + x * 1e-12, sf, si, pn)
                # finite fold: padded/unyielded steps emit -inf scores
                s = jnp.where(jnp.isfinite(sc), sc, 0.0).sum()
                return s, None
            last, _ = jax.lax.scan(once, jnp.float32(0), None, length=R)
            return last
        # nomadlint: waive=no-callsite-jit -- one-shot streaming-bench
        # program, built once per (R, shapes) measurement
        return jax.jit(run)

    # pipelined dispatch: R rounds of device_put + execute + fetch
    # submitted back-to-back (fetches deferred), the shape of a
    # production server streaming barrier generations. The dispatch
    # round trip overlaps across rounds, so per-round cost approaches
    # transfer + execute + fetch instead of RTT + everything.
    pipelined_dt = None
    try:
        R = 6
        copies = [tuple(np.array(a, copy=True)
                        for a in (compact, scal_f, scal_i, pen))
                  for _ in range(R)]
        bursts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            outs = [fn(*jax.device_put(cp)) for cp in copies]
            for o in outs:
                np.asarray(o[0])
            bursts.append((time.perf_counter() - t0) / R)
        pipelined_dt = statistics.median(bursts)
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("pipelined dispatch probe", e)

    marginal_dt = None
    try:
        # a 32-exec delta: dispatch-latency jitter lands on the
        # difference, so the wider the chain the tighter the per-exec
        # figure
        f1, f33 = chained(1), chained(33)
        np.asarray(f1(*dev)), np.asarray(f33(*dev))    # compile both
        t1s, t33s = [], []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(f1(*dev))
            t1s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            np.asarray(f33(*dev))
            t33s.append(time.perf_counter() - t0)
        marginal_dt = max(
            (statistics.median(t33s) - statistics.median(t1s)) / 32,
            1e-9)
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("chained compute probe", e)
    return blocking_dt, marginal_dt, pipelined_dt


def time_streaming_solver(h, nodes, e_evals, per_eval, depth, rounds=6):
    """Steady-state STREAMING dispatch through the production fused path
    (solver/batch.py fuse_and_solve -> device-resident const cache,
    solver/constcache.py): the same lane batch dispatched ``rounds``
    times, first strictly sequentially (the blocking baseline), then
    with ``depth`` dispatches in flight -- the shape a pipelined
    SolveBarrier (NOMAD_TPU_DISPATCH_DEPTH > 1) drives in production,
    where round trips and host packing overlap device compute.

    Also measures the transfer cut: host->device bytes of the COLD
    first dispatch (const cache empty) vs a WARM dispatch (tables
    resident), read from the nomad.solver.dispatch_bytes counters the
    dispatch layer maintains. Returns a dict or None."""
    import threading

    from nomad_tpu.server.telemetry import metrics
    from nomad_tpu.solver import constcache
    from nomad_tpu.solver.batch import fuse_and_solve

    lanes = pack_fused_lanes(h, nodes, e_evals, per_eval,
                             tag="stream-bench")
    if lanes is None:
        return None

    def bytes_total():
        return metrics.snapshot()["counters"].get(
            "nomad.solver.dispatch_bytes_total", 0)

    constcache.invalidate_all()           # honest cold measurement
    b0 = bytes_total()
    ref = fuse_and_solve(lanes)           # cold: compile + full upload
    cold_bytes = bytes_total() - b0
    b0 = bytes_total()
    fuse_and_solve(lanes)                 # warm: const tables resident
    warm_bytes = bytes_total() - b0
    placed = sum(int((res[0] >= 0).sum()) for res in ref)

    # blocking baseline: one dispatch fully fetched before the next
    t0 = time.perf_counter()
    for _ in range(rounds):
        fuse_and_solve(lanes)
    sync_dt = (time.perf_counter() - t0) / rounds

    # pipelined: `depth` submitters keep up to depth dispatches in
    # flight (each worker's fetch overlaps the others' transfers and
    # device execution -- what the async SolveBarrier does with real
    # eval generations)
    n_rounds = rounds * max(depth, 1)   # longer window: steadier number
    todo = list(range(n_rounds))
    lock = threading.Lock()
    mism = [0]

    def pull():
        while True:
            with lock:
                if not todo:
                    return
                todo.pop()
            out = fuse_and_solve(lanes)
            if any((a[0] != b[0]).any() for a, b in zip(out, ref)):
                with lock:
                    mism[0] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=pull, daemon=True)
               for _ in range(depth)]
    for t in threads:
        t.start()
    for t in threads:
        # bounded join (nomadlint join-with-timeout): a wedged solver
        # pull must not hang the bench invisibly
        while t.is_alive():
            t.join(timeout=30.0)
    pipe_dt = (time.perf_counter() - t0) / max(n_rounds, 1)

    snap = metrics.snapshot()["counters"]
    hits = snap.get("nomad.solver.const_cache_hit", 0)
    misses = snap.get("nomad.solver.const_cache_miss", 0)
    return {
        "placed": placed,
        "depth": depth,
        "sync_dt": sync_dt,
        "pipe_dt": pipe_dt,
        "cold_bytes": cold_bytes,
        "warm_bytes": warm_bytes,
        "mismatch": mism[0],
        "const_cache_hit_rate": round(hits / max(hits + misses, 1), 4),
    }


def time_pack_tax(h, nodes, n_placements, repeats=3):
    """Host-side packing tax (ISSUE 4): cold service.pack (every pack
    cache dropped -- node matrix, feasibility/spread/affinity memos,
    usage base) vs warm (snapshot caches resident) at the headline
    shape, plus the kill-switch parity gate: NOMAD_TPU_PACK_CACHE=0
    must produce identical placements. Returns a dict or None."""
    import numpy as np

    from nomad_tpu import mock
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService, dispatch_lane
    from nomad_tpu.structs import Plan
    from nomad_tpu.tensor import pack as tpack

    snap = h.state.snapshot()

    def one_pack(tag):
        job = mock.job(id=f"packbench-{tag}")
        job.task_groups[0].count = n_placements
        tg = job.task_groups[0]
        plan = Plan(eval_id=f"packbench-eval-{tag}", priority=50, job=job)
        ctx = EvalContext(snap, plan)
        places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                                   task_group=tg)
                  for k in range(n_placements)]
        svc = TpuPlacementService(ctx, job, batch_mode=False,
                                  spread_alg=False)
        t0 = time.perf_counter()
        lane = svc.pack(tg, places, nodes)
        return time.perf_counter() - t0, lane

    tpack.invalidate_pack_caches("bench cold measurement")
    cold_dt, lane = one_pack("cold")
    if lane is None:
        return None
    warm_dt = None
    for r in range(repeats):
        dt, lane = one_pack("warm")     # same eval id: identical work
        warm_dt = dt if warm_dt is None else min(warm_dt, dt)

    # parity: the cached lane vs a NOMAD_TPU_PACK_CACHE=0 repack of the
    # SAME eval must place identically
    prev = os.environ.get("NOMAD_TPU_PACK_CACHE")
    os.environ["NOMAD_TPU_PACK_CACHE"] = "0"
    try:
        _, lane_off = one_pack("warm")
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_PACK_CACHE", None)
        else:
            os.environ["NOMAD_TPU_PACK_CACHE"] = prev
    on = dispatch_lane(lane)
    off = dispatch_lane(lane_off)
    mismatch = int((np.asarray(on[0]) != np.asarray(off[0])).sum())
    return {
        "cold_ms": cold_dt * 1e3,
        "warm_ms": warm_dt * 1e3,
        "cut": (cold_dt / warm_dt) if warm_dt else 0.0,
        "mismatch": mismatch,
    }


def time_scale_northstar(mismatch):
    """BENCH_SCALE_ALLOCS (default ~2.05M) live allocations through the
    full batched pipeline via benchkit.run_scale_northstar; skipped on
    BENCH_SKIP_SCALE=1 or an earlier parity failure (a scale number on
    top of a broken round would be noise). Returns the result dict or
    None."""
    if mismatch or os.environ.get("BENCH_SKIP_SCALE", "") == "1":
        return None
    from nomad_tpu.benchkit import run_scale_northstar

    target = int(os.environ.get("BENCH_SCALE_ALLOCS", "2048000"))
    e_evals = int(os.environ.get("BENCH_FUSED_EVALS", "32"))
    try:
        out = run_scale_northstar(
            target, n_nodes=N_NODES, e_evals=e_evals,
            per_eval=N_PLACEMENTS, log=log)
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("north-star scale run", e)
        return None
    log(f"bench: north-star scale {out['allocs']} live allocs in "
        f"{out['wall_s']:.1f}s ({out['placements_per_sec']:.0f} "
        f"placements/s, rss {out['rss_mb']:.0f}MB"
        f"{', TRUNCATED' if out['truncated'] else ''})")
    return out


def time_scale_churn(mismatch):
    """Sustained-churn north star (ISSUE 6): hold BENCH_CHURN_LIVE live
    allocations (default ~2.05M) while absorbing arrivals, completions
    and node flaps at steady state via benchkit.run_scale_churn --
    p50/p99 submit->commit latency, per-round RSS (bounded, not
    monotonic), and the incremental-memo fold parity gate. Skipped on
    BENCH_SKIP_CHURN=1 or an earlier parity failure. Returns the result
    dict or None."""
    if mismatch or os.environ.get("BENCH_SKIP_CHURN", "") == "1":
        return None
    from nomad_tpu.benchkit import run_scale_churn

    target = int(os.environ.get("BENCH_CHURN_LIVE", "2048000"))
    rounds = int(os.environ.get("BENCH_CHURN_ROUNDS", "6"))
    e_evals = int(os.environ.get("BENCH_FUSED_EVALS", "32"))
    try:
        out = run_scale_churn(
            target, n_nodes=N_NODES, e_evals=e_evals,
            per_eval=N_PLACEMENTS, rounds=rounds, log=log)
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("sustained-churn run", e)
        return None
    log(f"bench: sustained churn held {out['live_allocs']} live over "
        f"{out['rounds']} rounds ({out['arrivals']} arrivals, "
        f"{out['completions']} completions, {out['flaps']} flaps); "
        f"submit->commit p50 {out['submit_commit_p50_ms']:.0f}ms / "
        f"p99 {out['submit_commit_p99_ms']:.0f}ms, rss growth "
        f"{out['rss_growth_mb']:+.0f}MB, "
        f"parity_mismatch={out['parity_mismatch']}"
        f"{', TRUNCATED' if out['truncated'] else ''}")
    log(f"bench: churn delta stream "
        f"{'ON' if out['delta_stream_enabled'] else 'OFF'}: "
        f"{out['delta_promotions']} promotions / "
        f"{out['delta_reuses']} reuses / "
        f"{out['delta_fallbacks']} fallbacks, "
        f"{out['delta_bytes_per_dispatch']:.0f}B delta + "
        f"{out['shipped_bytes_per_dispatch']:.0f}B shipped per "
        f"dispatch, ledger_parity={out['xfer_ledger_parity']}")
    return out


def time_worker_scaling(mismatch):
    """Crash-safe N-worker control plane scaling (ISSUE 16): e2e
    placements/s through the supervised PLAIN worker pool for each
    size in BENCH_WSCALE_POOLS (default 1,2,4,8) at fold parity 0 via
    benchkit.run_worker_scaling -- the proof number for ROADMAP 2a's
    multi-worker scheduling. Skipped on BENCH_SKIP_WORKER_SCALING=1 or
    an earlier parity failure. Returns the result dict or None."""
    if mismatch or os.environ.get("BENCH_SKIP_WORKER_SCALING",
                                  "") == "1":
        return None
    from nomad_tpu.benchkit import run_worker_scaling

    pools = tuple(
        int(s) for s in os.environ.get(
            "BENCH_WSCALE_POOLS", "1,2,4,8").split(",") if s.strip())
    n_nodes = int(os.environ.get("BENCH_WSCALE_NODES", "2000"))
    jobs = int(os.environ.get("BENCH_WSCALE_JOBS", "16"))
    per_eval = int(os.environ.get("BENCH_WSCALE_PER_EVAL", "250"))
    try:
        out = run_worker_scaling(
            pool_sizes=pools, n_nodes=n_nodes, jobs=jobs,
            per_eval=per_eval, log=log)
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("worker-scaling run", e)
        return None
    summary = ", ".join(
        f"N={n}: {v:.0f}/s"
        for n, v in sorted(out["placements_per_sec"].items()))
    log(f"bench: worker scaling ({out['placed_per_size']} placements "
        f"per size) {summary}; best vs 1 worker "
        f"{out['speedup_best_vs_1']:.2f}x, "
        f"parity_mismatch={out['parity_mismatch']}"
        f"{', TRUNCATED' if out['truncated'] else ''}")
    return out


def time_worker_scaling_ab(mismatch):
    """NOMAD_TPU_NATIVE_CP=0 leg of the worker-scaling readout
    (ISSUE 17): the same e2e pool harness with the native control
    plane killed, at reduced pool sizes (BENCH_WSCALE_AB_POOLS,
    default "1,4") -- the A/B showing what the native hot paths buy
    the N-worker pool. Skipped on BENCH_SKIP_WORKER_SCALING=1 /
    BENCH_SKIP_WSCALE_AB=1 or an earlier parity failure."""
    if mismatch or os.environ.get("BENCH_SKIP_WORKER_SCALING",
                                  "") == "1" \
            or os.environ.get("BENCH_SKIP_WSCALE_AB", "") == "1":
        return None
    from nomad_tpu.benchkit import run_worker_scaling

    pools = tuple(
        int(s) for s in os.environ.get(
            "BENCH_WSCALE_AB_POOLS", "1,4").split(",") if s.strip())
    n_nodes = int(os.environ.get("BENCH_WSCALE_NODES", "2000"))
    jobs = int(os.environ.get("BENCH_WSCALE_JOBS", "16"))
    per_eval = int(os.environ.get("BENCH_WSCALE_PER_EVAL", "250"))
    prev = os.environ.get("NOMAD_TPU_NATIVE_CP")
    os.environ["NOMAD_TPU_NATIVE_CP"] = "0"
    try:
        out = run_worker_scaling(
            pool_sizes=pools, n_nodes=n_nodes, jobs=jobs,
            per_eval=per_eval, log=log)
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("worker-scaling A/B (native CP off)", e)
        return None
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_NATIVE_CP", None)
        else:
            os.environ["NOMAD_TPU_NATIVE_CP"] = prev
    summary = ", ".join(
        f"N={n}: {v:.0f}/s"
        for n, v in sorted(out["placements_per_sec"].items()))
    log(f"bench: worker scaling A/B (NOMAD_TPU_NATIVE_CP=0) {summary}, "
        f"parity_mismatch={out['parity_mismatch']}"
        f"{', TRUNCATED' if out['truncated'] else ''}")
    return out


def time_eval_fixed(h, job, nodes, repeats=40):
    """Per-eval FIXED-cost microbench (ISSUE 17): the control-plane
    work an eval pays no matter how fast the solver is -- advance and
    build a state snapshot, verify a plan's asks against the columnar
    fold state, commit and materialize the result -- with the solver
    entirely out of the loop (the plan's allocs are prebuilt). The
    table is seeded to BENCH_EVAL_FIXED_SEED live allocs first: the
    wholesale snapshot copy this microbench exists to expose is
    O(live allocs), invisible on a near-empty table. Both arms run in
    the SAME process/world -- ``eval_fixed_ms`` with the native control
    plane, ``eval_fixed_nocp_ms`` with NOMAD_TPU_NATIVE_CP=0 -- so the
    step is read within-round, immune to cross-round box noise. Each
    iteration's commit advances the alloc journal, so the NEXT
    iteration's snapshot exercises the real delta-advance path.
    Returns the result dict or None; BENCH_SKIP_EVAL_FIXED=1 skips."""
    if os.environ.get("BENCH_SKIP_EVAL_FIXED", "") == "1":
        return None
    from nomad_tpu import mock
    from nomad_tpu.server.plan_apply import Planner

    from nomad_tpu.structs import Plan

    per_plan = int(os.environ.get("BENCH_EVAL_FIXED_ALLOCS", "50"))
    seed = int(os.environ.get("BENCH_EVAL_FIXED_SEED", "50000"))
    live = len(h.state.snapshot()._allocs)
    if live < seed:
        batch = []
        for i in range(seed - live):
            a = mock.alloc_for(job, nodes[i % len(nodes)], 0)
            tr = a.allocated_resources.tasks["web"]
            tr.cpu_shares = 1
            tr.memory_mb = 1
            batch.append(a)
            if len(batch) >= 5000:
                h.state.upsert_allocs(batch)
                batch = []
        if batch:
            h.state.upsert_allocs(batch)

    def one_arm(arm, native_cp):
        prev = os.environ.get("NOMAD_TPU_NATIVE_CP")
        if native_cp:
            os.environ.pop("NOMAD_TPU_NATIVE_CP", None)
        else:
            os.environ["NOMAD_TPU_NATIVE_CP"] = "0"
        planner = Planner(h.state)
        times = []
        rejected = 0
        try:
            for r in range(repeats):
                # prebuild outside the timed window: alloc CONSTRUCTION
                # is the scheduler's cost, not the control plane's
                allocs = []
                for i in range(per_plan):
                    a = mock.alloc_for(
                        job, nodes[(r * per_plan + i) % len(nodes)], 0)
                    tr = a.allocated_resources.tasks["web"]
                    tr.cpu_shares = 1
                    tr.memory_mb = 1
                    allocs.append(a)
                t0 = time.perf_counter()
                plan = Plan(eval_id=f"bench-fixed-{arm}{r:026d}",
                            priority=50, job=job)
                for a in allocs:
                    plan.append_alloc(a)
                result = planner.apply(plan)
                times.append(time.perf_counter() - t0)
                rejected += len(result.rejected_nodes)
        finally:
            planner.shutdown()
            if prev is None:
                os.environ.pop("NOMAD_TPU_NATIVE_CP", None)
            else:
                os.environ["NOMAD_TPU_NATIVE_CP"] = prev
        return statistics.median(times), rejected

    p50, rejected = one_arm("a", True)
    p50_nocp, rejected_nocp = one_arm("b", False)
    cut = p50_nocp / p50 if p50 else 0.0
    log(f"bench: eval fixed cost {p50 * 1e3:.2f}ms p50 native vs "
        f"{p50_nocp * 1e3:.2f}ms NOMAD_TPU_NATIVE_CP=0 ({cut:.2f}x) "
        f"over {repeats} evals x {per_plan} asks on a "
        f"{max(live, seed)}-alloc table "
        f"(rejected_nodes={rejected + rejected_nocp})")
    return {"eval_fixed_ms": round(p50 * 1e3, 3),
            "eval_fixed_nocp_ms": round(p50_nocp * 1e3, 3),
            "per_plan": per_plan, "seed": max(live, seed),
            "rejected": rejected + rejected_nocp}


def solve_once(h, job, nodes, n_placements):
    """One full TPU-path eval: host-side packing + one dense solver dispatch
    + the single device->host result fetch -- the complete per-eval latency
    path a production worker pays."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService
    from nomad_tpu.structs import Plan

    plan = Plan(eval_id="bench-eval-0000000000000001", priority=50, job=job)
    snap = h.state.snapshot()
    ctx = EvalContext(snap, plan)
    tg = job.task_groups[0]
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{i}]", task_group=tg)
              for i in range(n_placements)]
    service = TpuPlacementService(ctx, job, batch_mode=False,
                                  spread_alg=False)
    t0 = time.perf_counter()
    solved = service.solve(tg, places, nodes)
    dt = time.perf_counter() - t0
    placed = {sp.place.name: (sp.node.id if sp.node is not None else None)
              for sp in solved}
    return dt, placed


def main_tier(platform: str, tier: int):
    """BENCH_TIER mode: run the BASELINE tier shape end-to-end (full
    scheduler pipeline via the harness) host vs tpu with gating parity --
    the same nomad_tpu/benchkit generators tests/test_parity_scale.py
    gates at CI scale."""
    from nomad_tpu.benchkit import run_tier_placements

    n_nodes = N_NODES
    count = N_PLACEMENTS
    if tier == 1:
        # BASELINE tier 1 is a fixed dev-cluster shape: 3-TG service
        # job on 5 nodes (the TG counts come from the job itself)
        n_nodes, count = 5, 3
    t0 = time.time()
    host, host_ev = run_tier_placements(tier, n_nodes, count, seed=1,
                                        alg="binpack", with_evictions=True)
    host_dt = time.time() - t0
    log(f"bench[tier{tier}]: host {len(host)} placements in {host_dt:.2f}s")
    run_tier_placements(tier, n_nodes, count, seed=1, alg="tpu-binpack")
    t0 = time.time()
    tpu, tpu_ev = run_tier_placements(tier, n_nodes, count, seed=1,
                                      alg="tpu-binpack",
                                      with_evictions=True)
    tpu_dt = time.time() - t0
    log(f"bench[tier{tier}]: tpu {len(tpu)} placements in {tpu_dt:.2f}s")
    # bidirectional placement parity + eviction-set parity (tier 5 exists
    # to exercise preemption)
    keys = set(host) | set(tpu)
    mismatch = sum(1 for k in keys if host.get(k) != tpu.get(k))
    mismatch += sum(1 for k in keys if host_ev.get(k) != tpu_ev.get(k))
    if tier == 2:
        # BASELINE tier 2 is "binpack vs spread": gate the worst-fit
        # scheduler-algorithm pair too
        host_s, host_s_ev = run_tier_placements(
            tier, n_nodes, count, seed=2, alg="spread",
            with_evictions=True)
        tpu_s, tpu_s_ev = run_tier_placements(
            tier, n_nodes, count, seed=2, alg="tpu-spread",
            with_evictions=True)
        keys_s = set(host_s) | set(tpu_s)
        sp_mism = sum(1 for k in keys_s
                      if host_s.get(k) != tpu_s.get(k))
        sp_mism += sum(1 for k in keys_s
                       if host_s_ev.get(k) != tpu_s_ev.get(k))
        log(f"bench[tier2]: spread-algorithm variant "
            f"{len(tpu_s)} placements, parity_mismatch={sp_mism}")
        mismatch += sp_mism
    placements_per_sec = len(tpu) / tpu_dt if tpu_dt else 0.0
    out = {
        "metric": f"tier{tier}_eval_placements_per_sec",
        "value": round(placements_per_sec, 2),
        "unit": (f"placements/s ({n_nodes} nodes end-to-end eval, "
                 f"platform={platform}, parity_mismatch={mismatch})"),
        "vs_baseline": round(host_dt / tpu_dt, 2) if tpu_dt else 0.0,
        "platform": platform,
        "parity_mismatch": mismatch,
    }
    # explicit degraded verdict + breaker/dispatch state: a wedged
    # device or tripped breaker must never read as a chip result
    from nomad_tpu.benchkit import (
        artifact_stamp, delta_stream_stamp, dispatch_health_stamp,
        jitcheck_stamp, shardcheck_stamp, statecheck_stamp,
        xferobs_stamp)
    out.update(dispatch_health_stamp(platform))
    out.update(jitcheck_stamp())
    out.update(statecheck_stamp())
    out.update(shardcheck_stamp())
    # transfer ledger + link-model fields (ISSUE 13): byte parity and
    # per-dispatch payload are gated per round like the sanitizers
    out.update(xferobs_stamp())
    # delta streaming (ISSUE 20): chain promotions vs wholesale
    # fallbacks + cumulative delta payload, regress-gated
    out.update(delta_stream_stamp())
    # ISSUE 19: mesh-route fields ride the tier tails too (self-guarded
    # on device count + the NOMAD_TPU_MESH knob; parity is gating)
    if os.environ.get("BENCH_SKIP_MESH", "") != "1":
        try:
            mesh_leg = time_mesh_leg()
        except Exception as e:  # noqa: BLE001 -- the JSON still goes out
            leg_failed(f"tier{tier} mesh leg", e)
            mesh_leg = None
        if mesh_leg is not None:
            mismatch += mesh_leg["mesh_parity_mismatch"]
            out["parity_mismatch"] = mismatch
            out.update(mesh_leg)
    out.update(artifact_stamp())
    out["trace_artifact"] = _export_trace_artifact(
        default=f"BENCH_trace_tier{tier}.json")
    out["failed_legs"] = list(_FAILED_LEGS)
    print(json.dumps(out), flush=True)
    sys.exit(1 if mismatch or _FAILED_LEGS else 0)


def _export_trace_artifact(default: str):
    """Ship the eval-span flight recorder next to the BENCH_*.json
    line (Perfetto/chrome://tracing JSON; BENCH_TRACE_OUT overrides
    the path, empty disables)."""
    path = os.environ.get("BENCH_TRACE_OUT", default)
    if not path:
        return None
    from nomad_tpu.benchkit import export_chrome_trace
    written = export_chrome_trace(path)
    if written:
        log(f"bench: eval trace artifact -> {written}")
    return written


def main():
    platform = report_platform()
    tier = os.environ.get("BENCH_TIER", "").strip()
    if tier:
        main_tier(platform, int(tier))
        return
    t0 = time.time()
    h, job, nodes = build_world()
    log(f"bench: world built ({N_NODES} nodes) in {time.time() - t0:.1f}s")

    # --- host oracle: full workload, equal work to the solver path.
    # min over N_ORACLE_RUNS filters one-off GC/cold-cache noise from the
    # baseline side the same way median-of-repeats does for the solver.
    oracle_dt = None
    for _ in range(N_ORACLE_RUNS):
        run_dt, oracle_placed = time_host_inner_loop(
            h, job, nodes, N_PLACEMENTS)
        oracle_dt = run_dt if oracle_dt is None else min(oracle_dt, run_dt)
    n_oracle_ok = sum(1 for v in oracle_placed.values() if v is not None)
    log(f"bench: oracle placed {n_oracle_ok}/{N_PLACEMENTS} "
        f"in {oracle_dt:.3f}s ({oracle_dt / max(n_oracle_ok, 1) * 1e3:.3f} "
        f"ms/placement, min of {N_ORACLE_RUNS})")

    # --- compiled-host baseline (C++): parity-gated against the oracle
    native_dt, native_placed = time_native_oracle(
        h, job, nodes, N_PLACEMENTS)
    native_mismatch = 0
    if native_dt is not None:
        native_mismatch = sum(
            1 for k, v in oracle_placed.items()
            if native_placed.get(k) != v)
        log(f"bench: native C++ baseline {native_dt * 1e3:.3f} ms/eval "
            f"({native_dt / max(n_oracle_ok, 1) * 1e6:.2f} us/placement, "
            f"parity_mismatch={native_mismatch})")
    else:
        log("bench: native C++ baseline unavailable (build failed)")

    # --- TPU solver: warmup (compile) then repeated timed evals for p50
    warm_dt, tpu_placed = solve_once(h, job, nodes, N_PLACEMENTS)
    log(f"bench: solver warmup (incl. compile) {warm_dt:.3f}s")
    rtt = None
    try:
        rtt = _dispatch_rtt()
        log(f"bench: dispatch round-trip (trivial program) "
            f"{rtt * 1e3:.1f}ms -- every blocking per-call timing below "
            f"includes this as pure host<->device latency")
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("dispatch round-trip probe", e)
    times = []
    for r in range(N_REPEATS):
        dt, rep_placed = solve_once(h, job, nodes, N_PLACEMENTS)
        times.append(dt)
        if rep_placed != tpu_placed:
            log("bench: FATAL: solver output unstable across repeats")
            _emit(platform, 0.0, -1, oracle_dt)
            sys.exit(1)
    p50 = statistics.median(times)
    n_tpu_ok = sum(1 for v in tpu_placed.values() if v is not None)
    log(f"bench: solver p50 {p50 * 1e3:.1f}ms over {N_REPEATS} evals "
        f"(placed {n_tpu_ok}/{N_PLACEMENTS})")

    # --- GATING parity over the FULL workload: same keys, same nodes
    mismatch = sum(
        1 for k, v in oracle_placed.items() if tpu_placed.get(k) != v)
    mismatch += sum(1 for k in tpu_placed if k not in oracle_placed)
    if mismatch:
        for k, v in list(oracle_placed.items()):
            tv = tpu_placed.get(k)
            if tv != v:
                log(f"bench: PARITY MISMATCH {k}: oracle={v} tpu={tv}")
                break
    mismatch += native_mismatch

    # --- host packing tax: cold vs warm service.pack at the headline
    #     shape (the snapshot-scoped pack caches' claim), parity-gated
    #     against the NOMAD_TPU_PACK_CACHE=0 kill switch
    pack_tax = None
    if os.environ.get("BENCH_SKIP_PACK", "") != "1":
        try:
            pack_tax = time_pack_tax(h, nodes, N_PLACEMENTS)
        except Exception as e:  # noqa: BLE001 -- the JSON still goes out
            leg_failed("pack tax probe", e)
        if pack_tax is not None:
            mismatch += pack_tax["mismatch"]
            log(f"bench: host pack cold {pack_tax['cold_ms']:.1f}ms -> "
                f"warm {pack_tax['warm_ms']:.1f}ms "
                f"({pack_tax['cut']:.1f}x cut, "
                f"killswitch_mismatch={pack_tax['mismatch']})")

    # --- fused solver throughput: E evals, one dispatch (the headline)
    fused = None
    if not mismatch and os.environ.get("BENCH_SKIP_FUSED", "") != "1":
        e_evals = int(os.environ.get("BENCH_FUSED_EVALS", "32"))
        try:
            fdt, fplaced, fmis, fcompute = time_fused_solver(
                h, nodes, e_evals, N_PLACEMENTS)
            if fdt is not None:
                mismatch += fmis
                fused = (fdt, e_evals, fplaced, fcompute)
                log(f"bench: fused solver {e_evals} evals x "
                    f"{N_PLACEMENTS} in {fdt:.3f}s ({fplaced} placed, "
                    f"{fplaced / fdt:.0f} placements/s, "
                    f"fused_mismatch={fmis})")
                if fcompute and fcompute.get("blocking"):
                    log(f"bench: fused compute-only "
                        f"{fcompute['blocking'] * 1e3:.1f}ms blocking "
                        f"({fplaced / fcompute['blocking']:.0f} "
                        f"placements/s incl. 1 dispatch RTT)")
                if fcompute and fcompute.get("marginal"):
                    log(f"bench: fused compute MARGINAL "
                        f"{fcompute['marginal'] * 1e3:.2f}ms/exec "
                        f"({fplaced / fcompute['marginal']:.0f} "
                        f"placements/s steady-state on-chip)")
                if fcompute and fcompute.get("pipelined"):
                    log(f"bench: fused PIPELINED dispatch "
                        f"{fcompute['pipelined'] * 1e3:.1f}ms/round "
                        f"({fplaced / fcompute['pipelined']:.0f} "
                        f"placements/s, depth-6 transfer+exec+fetch)")
        except Exception as e:  # noqa: BLE001 -- the JSON still goes out
            leg_failed("fused solver", e)

    # --- end-to-end batched pipeline through BatchWorker (control plane
    #     included: broker, schedulers, plan applier, state store), at
    #     two shapes: the historical 16-way split of N_PLACEMENTS, and
    #     the HEADLINE shape (E full-size evals -- the same total work as
    #     the fused measurement, so batched_full vs fused is an
    #     apples-to-apples control-plane-tax readout)
    def run_batched(tag, e_evals, per_eval):
        # opt-in best-of-N (BENCH_BATCHED_BEST_OF): the pipeline is
        # multi-threaded, so single draws on a contended/1-core box swing
        # 2-4x on scheduler luck (r07/r08 notes); max throughput over a
        # couple of complete rounds de-noises the readout. Default stays
        # 1 -- extra rounds also inflate the cumulative xfer ledger's
        # dispatch mix, so stamped rounds keep single-draw parity with
        # prior artifacts unless the operator opts in.
        best_of = max(1, int(os.environ.get("BENCH_BATCHED_BEST_OF",
                                            "1")))
        try:
            bdt, bevals, bplaced = time_batched_path(
                N_NODES, e_evals, per_eval)
            for _ in range(best_of - 1):
                dt2, ev2, pl2 = time_batched_path(
                    N_NODES, e_evals, per_eval)
                if dt2 > 0.0 and (bdt == 0.0 or pl2 / dt2 > bplaced / bdt):
                    bdt, bevals, bplaced = dt2, ev2, pl2
        except Exception as e:  # noqa: BLE001 -- the JSON still goes out
            leg_failed(f"e2e pipeline ({tag})", e)
            return None
        if bdt == 0.0:
            # drain-failure sentinel: the measured round never ran
            log(f"bench: e2e pipeline ({tag}) DRAIN FAILED; "
                f"dropping metric")
            return None
        log(f"bench: e2e pipeline ({tag}) {bevals} evals x {per_eval} in "
            f"{bdt:.3f}s ({bplaced} placed, "
            f"{bplaced / bdt:.0f} placements/s)")
        if bplaced < e_evals * per_eval:
            # run_round's 600s deadline expired mid-round: a truncated
            # round must not be published as a complete measurement
            log(f"bench: e2e pipeline ({tag}) TRUNCATED "
                f"({bplaced}/{e_evals * per_eval} placed); dropping metric")
            return None
        return (bdt, bevals, bplaced)

    # --- streaming dispatch: sync vs depth-D pipelined, const cache warm
    streaming = None
    if not mismatch and os.environ.get("BENCH_SKIP_STREAMING", "") != "1":
        depth = int(os.environ.get(
            "BENCH_STREAM_DEPTH",
            os.environ.get("NOMAD_TPU_DISPATCH_DEPTH", "4")))
        depth = max(2, depth)
        e_evals = int(os.environ.get("BENCH_FUSED_EVALS", "32"))
        try:
            streaming = time_streaming_solver(h, nodes, e_evals,
                                              N_PLACEMENTS, depth)
        except Exception as e:  # noqa: BLE001 -- the JSON still goes out
            leg_failed("streaming solver", e)
        if streaming is not None:
            mismatch += streaming["mismatch"]
            log(f"bench: streaming sync {streaming['sync_dt'] * 1e3:.1f}"
                f"ms/round ({streaming['placed'] / streaming['sync_dt']:.0f}"
                f" placements/s), depth-{depth} pipelined "
                f"{streaming['pipe_dt'] * 1e3:.1f}ms/round "
                f"({streaming['placed'] / streaming['pipe_dt']:.0f} "
                f"placements/s); dispatch bytes cold "
                f"{streaming['cold_bytes']} -> warm "
                f"{streaming['warm_bytes']} "
                f"(hit rate {streaming['const_cache_hit_rate']})")

    batched = None
    if not mismatch and os.environ.get("BENCH_SKIP_BATCHED", "") != "1":
        e_evals = int(os.environ.get("BENCH_BATCH_EVALS", "16"))
        batched = run_batched("split", e_evals,
                              max(1, N_PLACEMENTS // e_evals))
    batched_full = None
    if not mismatch and os.environ.get("BENCH_SKIP_BATCHED_FULL", "") != "1":
        e_evals = int(os.environ.get("BENCH_FUSED_EVALS", "32"))
        batched_full = run_batched("headline shape", e_evals, N_PLACEMENTS)

    # --- whole-queue LP tier: the same e2e pipeline with tpu-lpq
    #     selected -- evals/solve amortization + quality delta vs the
    #     greedy replay of the same queue (ISSUE 8)
    lpq = None
    if not mismatch and os.environ.get("BENCH_SKIP_LPQ", "") != "1":
        lpq_evals = int(os.environ.get("BENCH_LPQ_EVALS", "128"))
        lpq_per = int(os.environ.get("BENCH_LPQ_PER_EVAL", "8"))
        try:
            lpq = time_lpq(N_NODES, lpq_evals, lpq_per)
        except Exception as e:  # noqa: BLE001 -- the JSON still goes out
            leg_failed("lpq tier", e)

    # --- north-star scale: ~2M LIVE allocs through the batched pipeline
    #     (accumulating, never drained) -- the ROADMAP number measured
    #     instead of extrapolated. AllocTable preallocated, per-placement
    #     metric stubs pruned, peak RSS recorded in the artifact.
    scale = time_scale_northstar(mismatch)

    # --- sustained churn: hold the north-star live count while the
    #     pipeline absorbs arrivals/completions/flaps at steady state
    #     (the regime production traffic actually is)
    churn = time_scale_churn(mismatch)

    # --- N-worker control plane scaling: e2e placements/s through the
    #     supervised plain worker pool for N in {1,2,4,8} (ISSUE 16)
    wscale = time_worker_scaling(mismatch)

    # --- same harness, native control plane KILLED (ISSUE 17 A/B):
    #     what the GIL-free verify/fold/materialize path buys the pool
    wscale_ab = time_worker_scaling_ab(mismatch)

    # --- multi-chip mesh solve: mesh vs single-device walls + per-shard
    #     ship bytes over a node-count sweep (ISSUE 19); self-guarded on
    #     device count and the NOMAD_TPU_MESH rollback knob
    mesh_leg = None
    if os.environ.get("BENCH_SKIP_MESH", "") != "1":
        try:
            mesh_leg = time_mesh_leg()
        except Exception as e:  # noqa: BLE001 -- the JSON still goes out
            leg_failed("mesh leg", e)
        if mesh_leg is not None:
            mismatch += mesh_leg["mesh_parity_mismatch"]
            log(f"bench: mesh leg grid={mesh_leg['mesh_grid']} "
                f"{mesh_leg['mesh_pps']:.0f} placements/s, "
                f"shard bytes {mesh_leg['mesh_shard_bytes']}, "
                f"collective overhead "
                f"{mesh_leg['mesh_collective_ms']:.1f}ms, "
                f"parity_mismatch={mesh_leg['mesh_parity_mismatch']}")

    # --- per-eval fixed cost: snapshot+verify+commit with the solver
    #     out of the loop (ISSUE 17 headline microbench); runs LAST
    #     because it accumulates allocs into the bench world
    eval_fixed = None
    try:
        eval_fixed = time_eval_fixed(h, job, nodes)
    except Exception as e:  # noqa: BLE001 -- the JSON still goes out
        leg_failed("eval fixed-cost probe", e)

    _emit(platform, p50, mismatch, oracle_dt, native_dt, batched,
          n_placed=n_tpu_ok, fused=fused, batched_full=batched_full,
          rtt=rtt, streaming=streaming, pack_tax=pack_tax, scale=scale,
          churn=churn, lpq=lpq, wscale=wscale, wscale_ab=wscale_ab,
          eval_fixed=eval_fixed, mesh=mesh_leg)
    if mismatch:
        log(f"bench: FAILED parity gate: {mismatch} mismatches")
    if _FAILED_LEGS:
        log(f"bench: FAILED legs: {', '.join(_FAILED_LEGS)}")
    if mismatch or _FAILED_LEGS:
        sys.exit(1)


def _emit(platform, p50, mismatch, oracle_total, native_total=None,
          batched=None, n_placed=0, fused=None, batched_full=None,
          rtt=None, streaming=None, pack_tax=None, scale=None,
          churn=None, lpq=None, wscale=None, wscale_ab=None,
          eval_fixed=None, mesh=None):
    placements_per_sec = (n_placed / p50) if p50 > 0 else 0.0
    per_place_tpu = p50 / n_placed if n_placed else 0.0
    per_place_host = oracle_total / max(n_placed, 1)
    speedup = (per_place_host / per_place_tpu) if per_place_tpu else 0.0
    per_place_native = (native_total / max(n_placed, 1)
                        if native_total is not None else None)
    out = {
        # headline (overwritten below when the fused measurement landed):
        # single-eval latency path
        "metric": "placements_per_sec_10k_nodes",
        "value": round(placements_per_sec, 2),
        "unit": (f"placements/s ({N_NODES} nodes, {n_placed} placed, "
                 f"platform={platform}, parity_mismatch={mismatch})"),
        "vs_baseline": round(speedup, 2),
        "p50_eval_ms": round(p50 * 1e3, 2),
        "host_oracle_eval_ms": round(oracle_total * 1e3, 2),
        "vs_python_host": round(speedup, 2),
        "platform": platform,
        "parity_mismatch": mismatch,
    }
    if rtt is not None:
        out["dispatch_rtt_ms"] = round(rtt * 1e3, 2)
    if native_total is not None:
        vs_native = (per_place_native / per_place_tpu) if per_place_tpu \
            else 0.0
        out["native_host_eval_ms"] = round(native_total * 1e3, 3)
        out["vs_native_host"] = round(vs_native, 4)
        out["vs_baseline"] = round(vs_native, 4)
    if fused is not None:
        # THE HEADLINE: solver throughput with E evals per dispatch (the
        # designed TPU win -- amortize dispatch over a coalesced batch),
        # vs the compiled C++ host baseline doing the same work
        # sequentially on one core. Parity is gated per-lane. The
        # compute-only variant excludes host<->device transfer.
        fdt, fevals, fplaced, fcompute = fused
        out["metric"] = "fused_placements_per_sec_10k_nodes"
        out["value"] = round(fplaced / fdt, 2)
        out["unit"] = (f"placements/s ({fevals} evals/dispatch, "
                       f"{N_NODES} nodes, platform={platform}, "
                       f"parity_mismatch={mismatch})")
        out["fused_evals_per_dispatch"] = fevals
        out["fused_placements_per_sec"] = round(fplaced / fdt, 2)
        if per_place_native is not None and fplaced:
            out["fused_vs_native_host"] = round(
                per_place_native / (fdt / fplaced), 4)
            out["vs_baseline"] = out["fused_vs_native_host"]
        blocking = fcompute.get("blocking") if fcompute else None
        marginal = fcompute.get("marginal") if fcompute else None
        if blocking:
            out["fused_compute_ms"] = round(blocking * 1e3, 3)
            out["fused_compute_placements_per_sec"] = round(
                fplaced / blocking, 2)
            if per_place_native is not None:
                out["fused_compute_vs_native_host"] = round(
                    per_place_native / (blocking / fplaced), 4)
        pipelined = fcompute.get("pipelined") if fcompute else None
        if pipelined:
            # streaming dispatch path: transfer + execute + fetch with
            # round trips overlapped across in-flight rounds -- the
            # per-dispatch cost a production server pays once the
            # link latency is pipelined away
            out["fused_pipelined_ms"] = round(pipelined * 1e3, 3)
            out["fused_pipelined_placements_per_sec"] = round(
                fplaced / pipelined, 2)
            if per_place_native is not None:
                out["fused_pipelined_vs_native_host"] = round(
                    per_place_native / (pipelined / fplaced), 4)
        if marginal:
            # steady-state on-chip rate (chained in-dispatch repeats):
            # the dispatch round trip (rtt_ms) amortizes away under
            # pipelining, so THIS is the kernel's own throughput; the
            # blocking metrics above keep the round trip visible
            # rather than hiding it.
            out["fused_compute_marginal_ms"] = round(marginal * 1e3, 3)
            out["fused_compute_marginal_placements_per_sec"] = round(
                fplaced / marginal, 2)
            if per_place_native is not None:
                out["fused_compute_marginal_vs_native_host"] = round(
                    per_place_native / (marginal / fplaced), 4)
    if streaming is not None:
        # steady-state streaming: the SAME fused workload dispatched
        # round after round with the const cache warm -- blocking
        # baseline kept alongside the depth-D pipelined number for
        # honesty, plus the per-dispatch transfer cut (cold = full
        # upload, warm = deltas only)
        placed = streaming["placed"]
        out["streaming_sync_placements_per_sec"] = round(
            placed / streaming["sync_dt"], 2) if streaming["sync_dt"] \
            else 0.0
        out["streaming_pipelined_placements_per_sec"] = round(
            placed / streaming["pipe_dt"], 2) if streaming["pipe_dt"] \
            else 0.0
        out["streaming_depth"] = streaming["depth"]
        out["dispatch_bytes_cold"] = streaming["cold_bytes"]
        out["dispatch_bytes_warm"] = streaming["warm_bytes"]
        if streaming["warm_bytes"]:
            out["dispatch_bytes_cut"] = round(
                streaming["cold_bytes"] / streaming["warm_bytes"], 2)
        out["const_cache_hit_rate"] = streaming["const_cache_hit_rate"]
        if native_total is not None and placed:
            out["streaming_pipelined_vs_native_host"] = round(
                per_place_native / (streaming["pipe_dt"] / placed), 4)
    if pack_tax is not None:
        # host packing tax, next to the transfer cut: cold = every pack
        # cache dropped, warm = snapshot caches resident; the warm cut
        # is the amortization the pack layer buys each steady-state eval
        out["pack_ms_cold"] = round(pack_tax["cold_ms"], 2)
        out["pack_ms_warm"] = round(pack_tax["warm_ms"], 2)
        out["pack_warm_cut"] = round(pack_tax["cut"], 2)
        out["pack_killswitch_mismatch"] = pack_tax["mismatch"]
    if batched is not None:
        bdt, bevals, bplaced = batched
        out["batched_evals_per_sec"] = round(bevals / bdt, 2)
        out["batched_placements_per_sec"] = round(bplaced / bdt, 2)
        if native_total is not None and bplaced:
            per_place_batched = bdt / bplaced
            out["batched_vs_native_host"] = round(
                per_place_native / per_place_batched, 4)
    if batched_full is not None:
        bdt, bevals, bplaced = batched_full
        out["batched_full_placements_per_sec"] = round(bplaced / bdt, 2)
        if native_total is not None and bplaced:
            out["batched_full_vs_native_host"] = round(
                per_place_native / (bdt / bplaced), 4)
        stats = getattr(time_batched_path, "last_planner_stats", None)
        if stats is not None:
            # the acceptance contract: the speedup must not come from
            # the applier silently rejecting work -- rejected stays 0
            out["batched_full_planner_rejected"] = stats["rejected"]
            out["plan_group_commits"] = stats["group_commits"]
        if fused is not None and fused[0] and bplaced:
            # control-plane tax: fused throughput / e2e throughput at the
            # SAME workload shape (1.0 = no tax)
            out["control_plane_tax"] = round(
                (fused[2] / fused[0]) / (bplaced / bdt), 2)
    if lpq is not None:
        # whole-queue LP tier: dispatch amortization (evals per joint
        # solve), throughput, and quality vs a greedy replay of the
        # SAME queue -- repair_rate is the rounding-health signal
        # (docs/OPERATIONS.md "LP queue tier")
        out.update(lpq)
    if scale is not None:
        # north-star scale: live-alloc count actually placed, steady
        # throughput across the accumulating run, and the memory
        # ceiling -- a truncated run is flagged, never silently
        # published as complete
        out["scale_allocs"] = scale["allocs"]
        out["scale_placements_per_sec"] = scale["placements_per_sec"]
        out["scale_rss_mb"] = scale["rss_mb"]
        out["scale_truncated"] = scale["truncated"]
        out["scale_wall_s"] = scale["wall_s"]
    if churn is not None:
        # sustained churn: live count HELD (not accumulated), latency
        # percentiles under steady arrivals/completions/flaps, per-round
        # RSS (growth = leak signal), and the incremental-memo parity
        # gate -- parity_mismatch must be 0 for the run to count
        out["churn_live_allocs"] = churn["live_allocs"]
        out["churn_rounds"] = churn["rounds"]
        out["churn_p50_ms"] = churn["submit_commit_p50_ms"]
        out["churn_p99_ms"] = churn["submit_commit_p99_ms"]
        out["churn_rss_growth_mb"] = churn["rss_growth_mb"]
        out["churn_rss_mb_rounds"] = churn["rss_mb_rounds"]
        out["churn_flaps"] = churn["flaps"]
        out["churn_quarantine_deferrals"] = churn["quarantine_deferrals"]
        out["churn_parity_mismatch"] = churn["parity_mismatch"]
        out["churn_truncated"] = churn["truncated"]
        # delta streaming (ISSUE 20): warm steady-state payload per
        # dispatch (journal deltas scattered on device instead of
        # re-shipped tables) + fallback count; ledger parity must be 0
        out["churn_delta_stream_enabled"] = \
            churn["delta_stream_enabled"]
        out["churn_delta_promotions"] = churn["delta_promotions"]
        out["churn_delta_reuses"] = churn["delta_reuses"]
        out["churn_delta_fallbacks"] = churn["delta_fallbacks"]
        out["churn_delta_bytes_per_dispatch"] = \
            churn["delta_bytes_per_dispatch"]
        out["churn_shipped_bytes_per_dispatch"] = \
            churn["shipped_bytes_per_dispatch"]
        out["churn_xfer_ledger_parity"] = churn["xfer_ledger_parity"]
    if wscale is not None:
        # N-worker control plane scaling (ISSUE 16): e2e placements/s
        # through the supervised plain pool per size, at fold parity 0
        # -- flat per-size fields so the regress gate can trend each N
        out["worker_scaling_pools"] = wscale["pool_sizes"]
        for n, v in wscale["placements_per_sec"].items():
            out[f"worker_scaling_pps_n{n}"] = v
        out["worker_scaling_speedup"] = wscale["speedup_best_vs_1"]
        out["worker_scaling_parity_mismatch"] = \
            wscale["parity_mismatch"]
        out["worker_scaling_truncated"] = wscale["truncated"]
    if wscale_ab is not None:
        # ISSUE 17 A/B: the same pool harness with NOMAD_TPU_NATIVE_CP=0
        # -- the native-control-plane win read directly off the artifact
        for n, v in wscale_ab["placements_per_sec"].items():
            out[f"worker_scaling_pps_n{n}_nocp"] = v
        out["worker_scaling_ab_parity_mismatch"] = \
            wscale_ab["parity_mismatch"]
    if eval_fixed is not None:
        # ISSUE 17 headline: per-eval fixed cost (snapshot + plan verify
        # + commit, solver out of the loop), regress-gated lower-better
        out["eval_fixed_ms"] = eval_fixed["eval_fixed_ms"]
        out["eval_fixed_nocp_ms"] = eval_fixed["eval_fixed_nocp_ms"]
        out["eval_fixed_allocs_per_plan"] = eval_fixed["per_plan"]
        out["eval_fixed_table_allocs"] = eval_fixed["seed"]
    if mesh is not None:
        # ISSUE 19: mesh-route throughput, per-shard ship bytes and
        # collective overhead over the node-count sweep; the parity
        # field already rode into the gating mismatch upstream
        out.update(mesh)
    # a CPU-backend / breaker-degraded artifact must never read as a
    # healthy TPU round: stamp the explicit degraded verdict +
    # dispatch-layer state
    from nomad_tpu.benchkit import (
        artifact_stamp, delta_stream_stamp, dispatch_health_stamp,
        jitcheck_stamp, shardcheck_stamp, statecheck_stamp,
        xferobs_stamp)
    out.update(dispatch_health_stamp(platform))
    # dispatch discipline (ISSUE 10): retraces/host syncs/x64 leaks
    # observed this run, gated by scripts/check_bench_regress.py
    out.update(jitcheck_stamp())
    out.update(statecheck_stamp())
    # sharding discipline (ISSUE 15): spec drift / implicit transfers /
    # collective excess observed this run, zero-tolerance gated
    out.update(shardcheck_stamp())
    # transfer ledger + link-model fields (ISSUE 13): payload bytes
    # decomposed per dispatch, byte parity vs dispatch_bytes_total
    # (must be 0), and the live rtt/bandwidth fit of the host<->device
    # link as a standing, regress-gated readout
    out.update(xferobs_stamp())
    # delta streaming (ISSUE 20): version-chain promotions vs wholesale
    # fallbacks + cumulative delta payload, regress-gated
    out.update(delta_stream_stamp())
    # quality scoreboard + per-stage saturation from the headline e2e
    # server (ISSUE 7): quality_fragmentation / quality_drift /
    # stage_busy_pct_* so solver changes are judged on placement
    # QUALITY, not just throughput
    quality = getattr(time_batched_path, "last_quality", None)
    if quality is not None:
        out.update(quality)
    # provenance: round/run ids + git SHA so trend tooling (and
    # scripts/check_bench_regress.py) can line artifacts up
    out.update(artifact_stamp())
    out["trace_artifact"] = _export_trace_artifact(
        default="BENCH_trace.json")
    out["failed_legs"] = list(_FAILED_LEGS)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
