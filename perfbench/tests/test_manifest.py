"""The manifest is checked before it is sent (PR 22 was refused over one
arrow in it)."""
import copy
import json
import os

import check
import manifest as mf
from conftest import (BENCH, against_host, host_reference, preemptor_pairs,
                      tier5_world)


def test_committed_manifest_passes(manifest):
    assert mf.check(manifest) == []


def test_no_cell_asks_for_four_chips(manifest):
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_every_end_to_end_metric_lists_its_cells(manifest):
    for m in manifest["end_to_end"]:
        assert m["name"] == "setup_s" or m["workloads"]


def test_the_rule_that_refused_pr_22(manifest):
    bad = copy.deepcopy(manifest)
    late = next(m for m in bad["per_layer"]
                if m["name"] == "generator_late_p95_ms.paced")
    late["workloads"].append("binpack-drain")
    faults = mf.check(bad)
    assert any("generator_late_p95_ms.paced is reported on workload "
               "binpack-drain, where submit_commit_p50_ms" in f
               for f in faults), faults


def test_names_units_files_and_bounds(manifest):
    cases = {
        "unit": lambda m: m["per_layer"][0].update(unit="lanes per dispatch"),
        "not a name": lambda m: m["workloads"][0].update(name="drain binpack"),
        "no file": lambda m: m["per_layer"][0].update(name="nothing_here.drain"),
        "end_to_end/absent_ms.json": lambda m: m["end_to_end"][0].update(
            name="absent_ms"),
        "no traffic file": lambda m: m["workloads"][0].update(traffic="absent"),
        "bound": lambda m: m["end_to_end"][0].update(bound=0.4),
        "keys": lambda m: m["per_layer"][0].update(why="because"),
        "four chips": lambda m: [w.update(chips=4) for w in m["workloads"][:3]],
        "no cell uses it": lambda m: m["configs"].append(dict(
            m["configs"][0], name="unused",
            file="perfbench/configs/unused.json")),
    }
    for want, breakit in cases.items():
        bad = copy.deepcopy(manifest)
        breakit(bad)
        faults = mf.check(bad)
        assert any(want in f for f in faults), (want, faults)


def test_every_metric_file_names_a_reader(manifest):
    import readers
    for kind, section in (("layer_metrics", "per_layer"),
                          ("end_to_end", "end_to_end")):
        for m in manifest[section]:
            with open(os.path.join(BENCH, kind, f"{m['name']}.json")) as f:
                assert json.load(f)["reader"] in readers.READERS


def test_no_cell_configuration_or_metric_is_named_in_the_python(manifest):
    names = ([w["name"] for w in manifest["workloads"]]
             + [c["name"] for c in manifest["configs"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["per_layer"]]
             + [m["name"] for m in manifest["end_to_end"]
                if m["name"] != "setup_s"])      # the contract's own name
    for fn in os.listdir(BENCH):
        if fn.endswith(".py"):
            with open(os.path.join(BENCH, fn)) as f:
                text = f.read()
            for name in names:
                assert f'"{name}"' not in text and f"'{name}'" not in text, (
                    fn, name)


# -- the deployment as data (ISSUE 35): what a configuration file states

def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_the_accepted_deployments_are_the_parents_byte_for_byte():
    """Jobspecs, warm-round jobspecs, the base load, the scheduler's
    POST bodies and every node registration of the two accepted
    configurations, against what the parent's harness (f1f5378) made of
    them (data/golden_deployment.json, frozen from it)."""
    import hashlib
    import fleet
    import run
    golden = _load("tests", "data", "golden_deployment.json")["configs"]
    assert len(golden) == 2
    for name, want in golden.items():
        cfg = _load("configs", f"{name}.json")
        t = cfg["job"]

        def same(got, frozen):
            return json.dumps(got) == json.dumps(frozen)
        assert same(run.jobspec(t, "JOB"), want["jobspec"])
        assert same([run.jobspec(dict(t, count=int(r["count"])), "JOB")
                     for r in cfg["scheduler"]["warm_rounds"]],
                    want["warm_jobspecs"])
        assert same([[tag, run.jobspec(tier, "JOB"), int(tier["count"]), n,
                      int(cfg["scheduler"]["workers"])]
                     for tag, tier, n in run.base_tiers(cfg)], want["base"])
        assert same(list(run.scheduler_bodies(cfg)), want["bodies"])
        for seed, reg in want["registrations"].items():
            regs = fleet.registrations(cfg["fleet"], int(seed))
            assert all(r["devices"] == [] for r in regs)
            rows = [[r["id"], r["cpu_mhz"], r["memory_mb"], r["disk_mb"],
                     r["meta"]["rack"]] for r in regs]
            assert len(rows) == reg["nodes"] and rows[0] == reg["first"]
            assert hashlib.sha256(json.dumps(rows).encode()).hexdigest() \
                == reg["sha256"]


def test_an_evicted_alloc_is_charged_until_its_eviction_commits():
    """An evicted alloc is server-terminal: it stops counting at the
    index of the placement that evicted it, whatever its client reports
    later, in the verifier's view (`run.replay_overcommitted`) and in the
    scheduler's (`committed.usage_at`) alike."""
    import run
    from reference import committed

    def alloc(id, cpu, create, modify, desired="run", client="running",
              by=""):
        return {"id": id, "job_id": f"job-{id}", "task_group": "web",
                "create_index": create, "modify_index": modify,
                "desired_status": desired, "client_status": client,
                "preempted_by_allocation": by,
                "allocated_resources": {
                    "tasks": {"web": {"cpu_shares": cpu, "memory_mb": 10}},
                    "shared": {"disk_mb": 10}}}
    node = {"node_resources": {"cpu": {"cpu_shares": 1000},
                               "memory": {"memory_mb": 1000},
                               "disk": {"disk_mb": 1000}}}
    for client, modify in (("running", 20), ("complete", 35)):
        low = alloc("low", 600, 10, modify, "evict", client, by="high")
        on_node = [low, alloc("high", 600, 20, 20)]
        assert committed.evicted_at(low, on_node) == 20
        assert committed.released_at(low, on_node) == 20
        assert run.replay_overcommitted({"n": (node, on_node)}, {}) == 0
        for index, cpu in ((15, 600.0), (20, 600.0), (25, 600.0),
                           (40, 600.0)):
            assert committed.usage_at(on_node, index, "job-x", "web")[0] \
                == cpu
        # the history's own last write, where the evicting alloc is gone
        gone = dict(low, preempted_by_allocation="elsewhere")
        assert run.replay_overcommitted(
            {"n": (node, [gone, on_node[1]])}, {}) == (client == "complete")
    assert committed.evicted_at(alloc("x", 1, 1, 1), []) is None


def test_the_preemption_reference_against_the_host_stack():
    """Tier 1's world of perfbench/tests/test_preemption_reference.py
    (200 nodes at 95%, cpu only): the reference's PreemptForTaskGroup
    evicts what the host's Preemptor does on every full node, and the
    host's plan is the reference's with the departures PERF.md section
    7 lists laid over it (or the reference's, once the program follows
    Nomad)."""
    world, _plan, state = tier5_world(200, 24, 12, True, alg=None)
    pairs = preemptor_pairs(world, state)
    assert len(pairs) > 100
    assert all(mine and mine == theirs and abs(s1 - s2) < 1e-12
               for theirs, mine, s1, s2 in pairs)
    world, plan, _state = tier5_world(200, 24, 12, True)
    plain = against_host(world, plan)
    departed = against_host(world, plan, departures=True)
    assert any(got[:2] == (24, 24) and got[2] < 1e-9
               for got in (plain, departed)), (plain, departed)


# a hand-built history, as the API reads it back: 4,000 MHz nodes, and a
# priority-70 job placing `count` 1,000 MHz allocs
_NODE = {"node_resources": {"cpu": {"cpu_shares": 4000},
                            "memory": {"memory_mb": 10000},
                            "disk": {"disk_mb": 100000}}}


def _job(count):
    return {"id": "high", "priority": 70, "task_groups": [{
        "name": "web", "count": count, "ephemeral_disk": {"size_mb": 100},
        "tasks": [{"resources": {"cpu": 1000, "memory_mb": 100}}]}]}


def _alloc(id, prio, cpu, create, modify=None, desired="run",
           client="running", by=""):
    """An alloc of its own job, or of the job placing ("p<k>": its
    placement k, committed by the eval's plan)."""
    placing = id.startswith("p")
    job_id = "high" if placing else f"job-{id}"
    return {"id": id, "name": f"{job_id}.web[{id[1:] if placing else 0}]",
            "job_id": job_id, "task_group": "web",
            "eval_id": "eval-high" if placing else f"eval-{id}",
            "create_index": create,
            "modify_index": create if modify is None else modify,
            "desired_status": desired, "client_status": client,
            "preempted_by_allocation": by,
            "job": {"priority": prio, "task_groups": [{"name": "web"}]},
            "allocated_resources": {
                "tasks": {"web": {"cpu_shares": cpu, "memory_mb": 100}},
                "shared": {"disk_mb": 100}}}


def _history(program_evicts_l2):
    """Two nodes and one eval placing two allocs, snapshot at index 10,
    plan committed at 20. A: W (priority 65, 3,000 MHz) until its stop at
    12, V (priority 65, 1,000) from 12. N: X (priority 65, 1,500) until
    its client reports it at 14, L (priority 10) and L2 (priority 20),
    1,000 each. The program put placement 0 on A and placement 1 on N,
    evicting nothing there (it read N after 14), or L2 where
    `program_evicts_l2`. At 10 the replay puts both where the program did
    and evicts L on N; at 12 and 14 it puts a placement elsewhere."""
    l2 = (_alloc("l2", 20, 1000, 5, 20, "evict", by="p1")
          if program_evicts_l2 else _alloc("l2", 20, 1000, 5))
    fleet = {
        "A": (_NODE, [_alloc("w", 65, 3000, 5, 12, "stop", "complete"),
                      _alloc("v", 65, 1000, 12), _alloc("p0", 70, 1000, 20)]),
        "N": (_NODE, [_alloc("x", 65, 1500, 5, 14, client="complete"),
                      _alloc("l", 10, 1000, 5), l2,
                      _alloc("p1", 70, 1000, 20)])}
    evicted = {0: [], 1: check.evicted_classes(fleet["N"][1], "p1")}
    return fleet, _job(2), evicted


_IDS = {0: "p0", 1: "p1"}


def _compare(fleet, job, evicted):
    """compare_plan on `_history`, each placement with the binpack score
    of the state the program read: A at 10 (4,000 MHz with placement 0),
    N at 14 (3,000 MHz with placement 1)."""
    from reference import preemption
    served = {0: ("A", float(preemption.binpack_score(4000, 10000, 4000,
                                                      200))),
              1: ("N", float(preemption.binpack_score(4000, 10000, 3000,
                                                      300)))}
    return check.compare_plan(
        served, _IDS, job, "web", "eval-high", 20, [10], ["A", "N"],
        fleet.__getitem__, preemption, evicted)


def test_an_eviction_is_judged_at_its_own_nodes_states():
    """The replay that puts both placements where the program did (index
    10) has N full, and evicts L there; at N's own state 14 N fits the
    placement without eviction, which is what the program did: admitted
    and counted, not compared."""
    from reference import preemption
    fleet, job, evicted = _history(False)
    order = preemption.shuffled(["A", "N"], "eval-high", 10)
    assert [(s[0], s[3]) for s in preemption.replay(
        job, "web", order, 10, fleet.__getitem__)] == [("A", []),
                                                       ("N", ["l"])]
    got = _compare(fleet, job, evicted)
    assert got["eviction_mismatches"] == [] and got["mismatches"] == []
    assert got["evictions_at_own_state"] == 1 and got["reproduced"]
    assert (got["usage_index"], got["walked"], got["walk_capped"]) == \
        (10, 1, False)
    # the score gap of the admitted placement is taken at N's state 14
    assert max(got["gaps"]) < 1e-12


def test_an_eviction_no_state_of_its_node_gives_is_a_mismatch():
    """The same history with the program evicting L2 (priority 20) where
    N's states give L (priority 10) or nothing: one mismatch."""
    fleet, job, evicted = _history(True)
    got = _compare(fleet, job, evicted)
    assert [m[:2] for m in got["eviction_mismatches"]] == [[1, "N"]]
    assert got["evictions_at_own_state"] == 0 and got["usage_index"] == 10
    assert [c[0] for c in got["eviction_mismatches"][0][2]] == [10]
    assert [c[0] for c in got["eviction_mismatches"][0][3]] == [20]


def test_a_placement_off_a_full_node_no_other_eval_touched_is_a_mismatch():
    """B1, B2 and C are full: B1 and B2 each hold an alloc of priority 10
    beside 3,000 MHz of priority 65, C only priority 65. The reference
    evicts for placement 0 on B1 and for placement 1 on B2; the program
    put placement 0 on B1, evicting what the reference does, and
    placement 1 on C without evicting. No other eval committed on B2, so
    nothing explains the move: one mismatch (a full node once read as
    "filled", which excused every move off a node the reference evicts
    on)."""
    from reference import preemption
    fleet = {
        "B1": (_NODE, [_alloc("x1", 65, 3000, 5),
                       _alloc("l1", 10, 1000, 5, 20, "evict", by="p0"),
                       _alloc("p0", 70, 1000, 20)]),
        "B2": (_NODE, [_alloc("x2", 65, 3000, 5), _alloc("l2", 10, 1000, 5)]),
        "C": (_NODE, [_alloc("c", 65, 4000, 5), _alloc("p1", 70, 1000, 20)])}
    evicted = {0: check.evicted_classes(fleet["B1"][1], "p0"), 1: []}
    order = preemption.shuffled(["B1", "B2", "C"], "eval-high", 10)
    assert [s[0] for s in preemption.replay(
        _job(2), "web", order, 10, fleet.__getitem__)] == ["B1", "B2"]
    got = check.compare_plan(
        {0: ("B1", None), 1: ("C", None)}, _IDS, _job(2), "web", "eval-high",
        20, [10], ["B1", "B2", "C"], fleet.__getitem__, preemption, evicted)
    assert [m[:3] for m in got["mismatches"]] == [[1, "B2", "C"]]
    assert got["moved"] == {"filled": 0, "touched": 0}
    assert got["eviction_mismatches"] == [] and got["same"] == [0]


_STEPS = 2 * check.WALK_STATES


def _unreproducible():
    """A history that never reproduces: the program put its one placement
    on a full node C, the reference puts it on B, and D (full, read by
    every scan) changes at twice as many indexes as WALK_STATES."""
    return {"B": (_NODE, [_alloc("b", 65, 1000, 5)]),
            "C": (_NODE, [_alloc("c", 65, 4000, 5)]),
            "D": (_NODE, [_alloc("d", 65, 4000, 5)] + [
                _alloc(f"d{i}", 65, 1, 11 + i) for i in range(_STEPS)])}


def _verdict(tally):
    ok, _rows = check.verdict(
        {"unreproduced_evals": tally["unreproduced"],
         "choice_mismatches": len(tally["mismatches"])},
        check.load_limits("served_placements"))
    return ok


def test_the_usage_walk_stops_at_its_bound():
    """The unreproducible history: the walk stops at WALK_STATES states,
    the eval counts as capped, and `correct` is false."""
    import run
    fleet = _unreproducible()
    got = check.compare_plan({0: ("C", None)}, {0: "p0"}, _job(1), "web",
                             "eval-high", 20 + _STEPS, [10], ["B", "C", "D"],
                             fleet.__getitem__)
    assert got["walk_capped"] and got["walked"] == check.WALK_STATES
    assert got["mismatches"] and not got["reproduced"]
    tally = run.new_tally()
    run.add(tally, got, 0, 20 + _STEPS)
    assert tally["capped"] == 1
    assert _verdict(tally) is False


def test_the_runs_replays_stop_when_their_reads_are_spent():
    """Four evals of the unreproducible history drawing on one run's
    budget of 1, 40 or 300 node reads: the walk stops once the budget is
    spent, before its WALK_STATES states, the evals after it replay
    nothing, every eval counts as capped and unreproduced, and `correct`
    is false."""
    import run
    fleet = _unreproducible()
    for reads in (1, 40, 300):
        budget, tally, walked = {"reads": reads}, run.new_tally(), []
        for _ in range(4):
            got = check.compare_plan(
                {0: ("C", None)}, {0: "p0"}, _job(1), "web", "eval-high",
                20 + _STEPS, [10], ["B", "C", "D"], fleet.__getitem__,
                budget=budget)
            run.add(tally, got, 0, 20 + _STEPS)
            walked.append(got["walked"])
        assert budget["reads"] <= 0
        assert 1 <= walked[0] < check.WALK_STATES
        assert walked[1:] == [0, 0, 0]
        assert tally["capped"] == tally["unreproduced"] == 4
        assert _verdict(tally) is False


def test_device_preemption_where_the_low_tiers_hold_every_gpu():
    """Tier 1's device world of perfbench/tests/test_preemption_reference.py
    (200 nodes at 95%, the fillers holding every GPU, a priority-70 job
    asking for one): every placement evicts for its device, and the
    host's plan is the reference's with the departures PERF.md section 7
    lists laid over it, PreemptForDevice's among them (or the
    reference's, once the program follows Nomad): node, evicted classes
    and score. Node by node, the reference's PreemptForDevice and the
    host's as written there differ on some nodes."""
    from reference import preemption
    world, plan, _state = tier5_world(200, 12, 21, False, gpu_holders=True)
    assert len(plan[1]) == 12 and all(plan[1].values())
    plain = against_host(world, plan)
    departed = against_host(world, plan, departures=True)
    assert any(got[:3] == (12, 12, 0.0) for got in (plain, departed)), (
        plain, departed)
    fleet, _base, job, _eval_id, index = world
    host = host_reference().host_preempt_for_device
    differ = 0
    for node, on_node in fleet.values():
        groups = preemption.device_groups(node, "nvidia/gpu")
        proposed = preemption.node_allocs(on_node, index, job["id"])
        if groups:
            assert not preemption.assign_devices(groups, proposed, {}, 1)
            got = [fn(groups, proposed, {}, 1, 70)
                   for fn in (preemption.preempt_for_device, host)]
            assert all(got)
            differ += got[0] != got[1]
    assert 0 < differ < 100


def test_malformed_deployment_keys_are_refused():
    good = _load("tests", "data", "preempt", "config.json")
    assert mf.configuration_faults(good, os.path.dirname(BENCH)) == []
    cases = {
        "shares sum to": lambda c: c["fleet"]["classes"][0].update(
            share=0.5),
        "fleet.classes[1]: device": lambda c: c["fleet"]["classes"][1][
            "devices"][0].pop("instances"),
        "base_load[2]": lambda c: c["base_load"][2].pop("allocs"),
        "base_load[0].job: priority": lambda c: c["base_load"][0][
            "job"].update(priority=0),
        "job: device ask": lambda c: c["job"]["task"].update(
            devices=[{"name": "nvidia/gpu"}]),
        "job: constraint": lambda c: c["job"].update(
            constraints=[{"operand": "="}]),
        "scheduler.preemption": lambda c: c["scheduler"][
            "preemption"].update(service="yes"),
        "no module perfbench/reference/absent.py": lambda c: c.update(
            reference="absent"),
        "reference 'placement' against": lambda c: c.update(
            reference="placement"),
        "reference 'preemption' against": lambda c: c.update(
            reference="preemption") or c["scheduler"]["preemption"].update(
            service=False),
        "does not filter by": lambda c: c.update(reference="placement") or
        c["job"].update(constraints=[{"l_target": "${meta.rack}",
                                      "r_target": "rack-001"}]),
    }
    for want, breakit in cases.items():
        bad = copy.deepcopy(good)
        breakit(bad)
        faults = mf.configuration_faults(bad, os.path.dirname(BENCH))
        assert any(want in f for f in faults), (want, faults)
