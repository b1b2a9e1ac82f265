import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
if ROOT not in sys.path:     # the program, for the tier-5 worlds
    sys.path.append(ROOT)


def run_cell(workload, *extra, root=ROOT, env=None, seconds="2",
             rehearse="0.03", seed="2147483700"):
    """One run of the one command, on the CPU; returns (exit code, last
    stdout line decoded or None, stderr)."""
    cmd = [sys.executable, os.path.join(root, "perfbench", "run.py"),
           "--workload", workload, "--seed", seed, "--seconds", seconds]
    if rehearse:
        cmd += ["--rehearse", rehearse]
    cmd += list(extra)
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    p = subprocess.run(cmd, cwd=root, env=full_env, capture_output=True,
                       text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


def copy_bench(root):
    """The benchmark and the program under `root`: (the copy's
    perfbench/, load(*parts), dump(obj, *parts)) there."""
    shutil.copytree(BENCH, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for part in ("nomad_tpu", "native"):
        os.symlink(os.path.join(ROOT, part), os.path.join(root, part))
    bench = os.path.join(root, "perfbench")

    def load(*parts):
        with open(os.path.join(bench, *parts)) as f:
            return json.load(f)

    def dump(obj, *parts):
        with open(os.path.join(bench, *parts), "w") as f:
            json.dump(obj, f)
    return bench, load, dump


def load_data(*parts):
    with open(os.path.join(HERE, "data", *parts)) as f:
        return json.load(f)


def preempt_tree(root, manifest, reference=None):
    """A copy of the benchmark under `root` to which the stand-in
    preemption deployment (data/preempt/config.json: 256 nodes of four
    classes, four priority tiers filled to 95%, priority-70 jobs that
    evict) has been added as the model_config PR would add it, by new
    files and appended entries alone: its configuration, a mix of four
    submitters and a cell. Returns the grown manifest. With `reference`
    (`host_departures`), the stand-in is compared with that module of
    data/preempt/ instead of the one its settings call for."""
    bench, load, dump = copy_bench(root)
    config = load_data("preempt", "config.json")
    if reference:
        shutil.copy(os.path.join(HERE, "data", "preempt", f"{reference}.py"),
                    os.path.join(bench, "reference", f"{reference}.py"))
        config["reference"] = reference
    dump(config, "configs", "standin-preempt.json")
    mix = load("traffic", "drain.json")
    # the fleet holds its 95% only until the first evictions are
    # acknowledged (no follow-up evals re-place the victims: PERF.md 7,
    # item 0), so the window opens early and compares most of its jobs
    mix.update(submitters=4, lead_in_s=1.0, check_jobs=24)
    dump(mix, "traffic", "standin-preempt.json")
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({
        "name": "standin-preempt",
        "source": "https://github.com/hashicorp/nomad/blob/v1.7.0/scheduler/preemption.go",
        "file": "perfbench/configs/standin-preempt.json",
        "reduced": ["nodes"], "why": "the preemption fleet's stand-in"})
    grown["workloads"].append({
        "name": "standin-preempt", "config": "standin-preempt",
        "traffic": "standin-preempt", "chips": 1, "why": "four submitters"})
    grown["end_to_end"][0]["workloads"].append("standin-preempt")
    dump(load("layer_metrics", "attempts_per_eval.drain.json"),
         "layer_metrics", "attempts_per_eval.preempt.json")
    grown["per_layer"].append(dict(
        next(m for m in manifest["per_layer"]
             if m["name"] == "attempts_per_eval.drain"),
        name="attempts_per_eval.preempt", workloads=["standin-preempt"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(grown, f)
    return grown


def standin_tree(root, manifest):
    """A copy of the benchmark under `root` to which a deployment has
    been added as a later PR adds one, by new files and appended entries
    alone: a stand-in for the sweep's spread corner (data/standin/: its
    configuration and its kernel's work model, under names no real
    deployment will take), a mix, a cell and two per-layer metrics.
    Returns the grown manifest, which `root`/BENCHMARK.json holds."""
    bench, load, dump = copy_bench(root)
    for src, dst in (
            ("config.json", ("configs", "standin-spread.json")),
            ("work_model.json", ("work_models", "standin_scan.json"))):
        shutil.copy(os.path.join(HERE, "data", "standin", src),
                    os.path.join(bench, *dst))
    mix = load("traffic", "drain.json")
    mix["submitters"] = 4
    dump(mix, "traffic", "standin-trickle.json")
    dump({"reader": "counter_ratio",
          "args": {"counters": ["nomad.plan.rejected_allocs"],
                   "per": ["nomad.scheduler.placements_tpu"], "scale": 100.0}},
         "layer_metrics", "plan_rejected_share.standin.json")
    dump(load("layer_metrics", "solve_roofline.drain.json"),
         "layer_metrics", "solve_roofline.standin.json")
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({
        "name": "standin-spread",
        "source": manifest["configs"][0]["source"],
        "file": "perfbench/configs/standin-spread.json",
        "reduced": [], "why": "the sweep's largest point"})
    grown["workloads"].append({
        "name": "standin-trickle", "config": "standin-spread",
        "traffic": "standin-trickle", "chips": 1, "why": "four submitters"})
    grown["end_to_end"][0]["workloads"].append("standin-trickle")
    for name, unit, source, layer in (
            ("plan_rejected_share.standin", "%", "program_counter",
             "Verify + commit"),
            ("solve_roofline.standin", "%", "device_trace", "Kernels")):
        grown["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "placements_per_s",
            "workloads": ["standin-trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(grown, f)
    return grown


def tier5_world(n_nodes, count, seed, cpu_only, alg="binpack", util=0.95,
                gpu_holders=False):
    """nomad_tpu.benchkit.run_tier_placements(5, ...) with its state
    kept: a fleet of three machine classes filled to `util` of its cpu
    (BASELINE's 95%) by priority 10-40 fillers and one priority-70 job
    (without its GPU ask where `cpu_only`), preemption on. With
    `gpu_holders`, the fillers hold every GPU instance (`hold_gpus`).
    Returns (the world as the API's JSON: (node id -> (node, its
    allocs), ready node ids, job, eval id, state index), the host's plan:
    (placement number -> node, -> sorted evicted alloc ids, -> score),
    the host's state store). Placing nothing, with `alg` None."""
    import itertools
    import random
    from nomad_tpu import mock
    rng = random.Random(seed)
    # ids from a counter of their own, and the process's put back after
    saved, mock._counter = mock._counter, itertools.count()
    try:
        return _tier5_world(rng, n_nodes, count, seed, cpu_only, alg, util,
                            gpu_holders)
    finally:
        mock._counter = saved


def hold_gpus(rng, h, nodes):
    """Every GPU instance of the fleet held by the fillers on its node, 1
    or 2 instances a holder, holders drawn from the seed; each holder's
    job asks for what it holds. So a GPU ask finds no free instance, and
    device preemption decides where it goes."""
    from nomad_tpu.structs.resources import (AllocatedDeviceResource,
                                             DeviceRequest)
    for node in nodes:
        for group in node.node_resources.devices:
            free = list(group.instance_ids)
            allocs = list(h.state.allocs_by_node(node.id))
            rng.shuffle(allocs)
            held = []
            for a in allocs:
                if not free:
                    break
                k = min(len(free), rng.choice([1, 2]))
                a = a.copy_skip_job()
                take, free = free[:k], free[k:]
                task = next(iter(a.allocated_resources.tasks.values()))
                task.devices = [AllocatedDeviceResource(
                    vendor=group.vendor, type=group.type, name=group.name,
                    device_ids=take)]
                a.job.task_groups[0].tasks[0].resources.devices = [
                    DeviceRequest(name="nvidia/gpu", count=k)]
                held.append(a)
            h.state.upsert_allocs(held)


def _tier5_world(rng, n_nodes, count, seed, cpu_only, alg, util,
                 gpu_holders):
    from nomad_tpu import benchkit, mock
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.structs import codec
    from nomad_tpu.structs.config import (PreemptionConfig,
                                          SchedulerConfiguration)
    h = Harness()
    cfg = SchedulerConfiguration(scheduler_algorithm=alg or "binpack")
    cfg.preemption_config = PreemptionConfig(
        service_scheduler_enabled=True, batch_scheduler_enabled=True)
    h.state.set_scheduler_config(cfg)
    nodes = benchkit.make_fleet(rng, h, n_nodes, gpus=True)
    benchkit.seed_utilization(rng, h, nodes, util, priorities=(10, 20, 30, 40))
    if gpu_holders:
        hold_gpus(rng, h, nodes)
    job = benchkit.tier_job(5, rng, count)
    if cpu_only:
        job.task_groups[0].tasks[0].resources.devices = []
    job.id = f"tier5-job-{seed}"
    h.state.upsert_job(job)
    ev = mock.evaluation(job_id=job.id, type=job.type, priority=job.priority)
    ev.id = f"tier5-eval-{seed:08d}"
    index = h.state.latest_index()
    fleet = {n.id: (codec.encode(n),
                    [codec.encode(a) for a in h.state.allocs_by_node(n.id)])
             for n in nodes}
    # the scheduler's base: ready nodes of the job's datacenters
    base = [n.id for n in h.state.ready_nodes_in_pool(job.node_pool)
            if n.datacenter in job.datacenters]
    world = (fleet, base, codec.encode(job), ev.id, index)
    placed, evicted, scores = {}, {}, {}
    if alg is None:
        return world, (placed, evicted, scores), h.state
    assert h.process(job.type, ev) is None
    for plan in h.plans:
        by = {}
        for allocs in plan.node_preemptions.values():
            for a in allocs:
                by.setdefault(a.preempted_by_allocation, []).append(a.id)
        for node_id, allocs in plan.node_allocation.items():
            for a in allocs:
                k = a.index()
                placed[k] = node_id
                evicted[k] = sorted(by.get(a.id, []))
                scores[k] = a.metrics.scores.get(f"{node_id}.normalized-score")
    return world, (placed, evicted, scores), h.state


def preemptor_pairs(world, state):
    """On every node of a tier-5 world that the job's ask does not fit:
    the classes (check.eviction_class) of what the host's Preemptor
    evicts for it, of what reference/preemption.py's does, and the two
    preemption scores of those sets."""
    import check
    from nomad_tpu.scheduler.preemption import Preemptor
    from nomad_tpu.scheduler.rank import net_priority, preemption_score
    from nomad_tpu.structs import (AllocatedResources,
                                   AllocatedSharedResources,
                                   AllocatedTaskResources)
    from reference import committed as cs
    from reference import preemption as ref
    fleet, _base, job, _eval_id, index = world
    group = job["task_groups"][0]
    count, ask = cs.plan_ask(job, group["name"])
    total = AllocatedResources(
        tasks={t["name"]: AllocatedTaskResources(
            cpu_shares=t["resources"]["cpu"],
            memory_mb=t["resources"]["memory_mb"]) for t in group["tasks"]},
        shared=AllocatedSharedResources(disk_mb=int(ask[2])))
    out = []
    for node_id, (node, on_node) in sorted(fleet.items()):
        cap = cs.node_capacity(node)
        proposed = ref.node_allocs(on_node, index, job["id"])
        if all(sum(a.res[i] for a in proposed) + ask[i] <= cap[i]
               for i in range(3)):
            continue
        by_id = {b["id"]: b for b in on_node}
        mine = ref.preempt_for_task_group(proposed, cap, ask,
                                          int(job["priority"]), {})
        host = Preemptor(int(job["priority"]), None,
                         (job["namespace"], job["id"]))
        host.set_node(state.node_by_id(node_id))
        host.set_preemptions([])
        host.set_candidates([a for a in state.allocs_by_node(node_id)
                             if not a.terminal_status()])
        theirs = host.preempt_for_task_group(total)
        out.append((
            sorted(check.eviction_class(by_id[a.id]) for a in theirs),
            sorted(check.eviction_class(by_id[a.id]) for a in mine),
            preemption_score(net_priority(theirs)) if theirs else None,
            ref.preemption_score(ref.net_priority(mine)) if mine else None))
    return out


def device_pairs(world):
    """On every node of a tier-5 world whose matching device instances
    are all held (`hold_gpus`), the classes of what each evicts for the
    job's device ask: the host's Preemptor.preempt_for_device (on the
    node and allocs decoded from the world's JSON), reference/
    preemption.py's PreemptForDevice, and the host's as
    data/preempt/host_departures.py writes it; [] where one finds
    nothing. [(node id, host's, reference's, departure's)]."""
    import check
    from nomad_tpu.scheduler.preemption import Preemptor
    from nomad_tpu.scheduler.rank import DeviceAllocator
    from nomad_tpu.structs import Allocation, DeviceRequest, Node, codec
    from reference import preemption as ref
    fleet, _base, job, _eval_id, index = world
    [ask] = [d for t in job["task_groups"][0]["tasks"]
             for d in t["resources"].get("devices") or ()]
    count, priority = int(ask.get("count", 1)), int(job["priority"])
    host_of = host_reference().host_preempt_for_device
    out = []
    for node_id, (node, on_node) in sorted(fleet.items()):
        groups = ref.device_groups(node, ask["name"])
        proposed = ref.node_allocs(on_node, index, job["id"])
        if not groups or ref.assign_devices(groups, proposed, {}, count):
            continue
        by_id = {b["id"]: b for b in on_node}
        host_node = codec.decode(Node, node)
        live = [a for a in (codec.decode(Allocation, b) for b in on_node)
                if not a.terminal_status()]
        host = Preemptor(priority, None, (job["namespace"], job["id"]))
        host.set_node(host_node)
        host.set_preemptions([])
        host.set_candidates(live)
        allocator = DeviceAllocator(None, host_node)
        allocator.add_allocs(live)
        theirs = host.preempt_for_device(
            DeviceRequest(name=ask["name"], count=count), allocator) or []
        out.append((node_id, *(
            sorted(check.eviction_class(by_id[a.id]) for a in got)
            for got in (theirs, ref.preempt_for_device(
                groups, proposed, {}, count, priority), host_of(
                groups, proposed, {}, count, priority)))))
    return out


def host_reference():
    """data/preempt/host_departures.py: reference/preemption.py with the
    host stack's departures from Nomad 1.7 laid over it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "host_departures", os.path.join(HERE, "data", "preempt",
                                        "host_departures.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def against_host(world, host_plan, departures=False):
    """The reference's plan of a tier-5 world beside the host's: (the
    placements on the host's node, those of them that evict allocs of
    the host's classes, the widest score gap among them, placements).
    With `departures`, the reference has the host's laid over it."""
    import check
    from reference import preemption as ref
    fleet, base, job, eval_id, index = world
    placed, evicted, scores = host_plan
    seq = (host_reference() if departures else ref).replay(
        job, job["task_groups"][0]["name"],
        ref.shuffled(base, eval_id, index), index, fleet.__getitem__)

    def classes(node_id, ids):
        by_id = {b["id"]: b for b in fleet[node_id][1]}
        return sorted(check.eviction_class(by_id[i]) for i in ids)
    same = [k for k in placed if seq[k][0] == placed[k]]
    alike = [k for k in same if classes(placed[k], seq[k][3])
             == classes(placed[k], evicted[k])]
    gap = max((abs(seq[k][1] - scores[k]) for k in same), default=0.0)
    return len(same), len(alike), gap, len(seq)


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
