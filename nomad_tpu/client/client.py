"""Client agent: registration, heartbeats, alloc watch loop, restore, GC.

Semantic parity with /root/reference/client/client.go (NewClient :350,
registerAndHeartbeat :1734, watchAllocations :2280 -- blocking
Node.GetClientAllocs pull, runAllocs :2538 -- diff desired vs running,
restoreState :1215 -- re-attach via driver handles, heartbeatstop.go --
stop_after_client_disconnect). The server boundary is the `ServerConn`
interface: in-process for the dev topology, HTTP for real deployments --
the client is pull-based either way, which is what makes 10K-node fleets
tractable (no server->client push).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from ..structs import (
    Allocation, Node,
    ALLOC_CLIENT_COMPLETE, ALLOC_CLIENT_FAILED, ALLOC_DESIRED_RUN,
)
from .alloc_runner import AllocRunner
from .drivers import DriverRegistry
from .fingerprint import FingerprintManager
from .state_db import StateDB


class ServerConn:
    """Client->server RPC surface (reference: client/rpc.go +
    servers manager client/servers/)."""

    def register_node(self, node: Node) -> None:
        raise NotImplementedError

    def heartbeat(self, node_id: str) -> float:
        raise NotImplementedError

    def pull_allocs(self, node_id: str, min_index: int,
                    timeout: float) -> tuple:
        """Blocking pull -> (allocs, index)
        (reference: Node.GetClientAllocs node_endpoint.go:1170)."""
        raise NotImplementedError

    def update_allocs(self, updates: List[Allocation]) -> None:
        raise NotImplementedError

    def get_alloc(self, alloc_id: str) -> Optional[Allocation]:
        raise NotImplementedError

    def register_services(self, regs) -> None:
        """(reference: ServiceRegistration.Upsert RPC)"""
        raise NotImplementedError

    def sign_identity(self, claims: dict) -> Optional[str]:
        """Mint a workload identity JWT (reference: the server-side
        signing the identity hook relies on). None = unsupported."""
        return None

    def workload_variable(self, jwt: str, path: str):
        """Fetch a decrypted Variable with a workload identity
        (reference analog: DeriveVaultToken -> native Variables)."""
        raise NotImplementedError

    def csi_volume(self, namespace: str, vol_id: str):
        """-> CSIVolume or None (volume hook attach path)."""
        raise NotImplementedError


class LocalServerConn(ServerConn):
    """In-process server (dev agent topology)."""

    def __init__(self, server):
        self.server = server

    def register_node(self, node: Node) -> None:
        self.server.register_node(node)

    def heartbeat(self, node_id: str) -> float:
        return self.server.heartbeat(node_id)

    def pull_allocs(self, node_id: str, min_index: int,
                    timeout: float) -> tuple:
        index = self.server.state.block_until(
            min_index, timeout=timeout, keys=(("node", node_id),))
        return self.server.state.allocs_by_node(node_id), index

    def update_allocs(self, updates: List[Allocation]) -> None:
        self.server.update_allocs_from_client(updates)

    def get_alloc(self, alloc_id: str) -> Optional[Allocation]:
        return self.server.state.alloc_by_id(alloc_id)

    def register_services(self, regs) -> None:
        self.server.upsert_services(regs)

    def sign_identity(self, claims: dict) -> Optional[str]:
        return self.server.sign_workload_identity(claims)

    def workload_variable(self, jwt: str, path: str):
        return self.server.workload_variable(jwt, path)

    def csi_volume(self, namespace: str, vol_id: str):
        return self.server.state.csi_volume_by_id(namespace, vol_id)


MAX_TERMINAL_RUNNERS = 50     # client GC watermark (reference: client/gc.go)


class Client:
    """(reference: client/client.go Client)"""

    def __init__(self, conn: ServerConn, data_dir: str,
                 node: Optional[Node] = None, name: str = "",
                 drivers: Optional[DriverRegistry] = None,
                 probe_jax: bool = False, identity_signer=None,
                 device_plugins=None, csi_plugins=None,
                 api_addr: str = "", serve_http: bool = False):
        self.conn = conn
        self.data_dir = data_dir
        # bridge networking (client/netns.py): the AllocRunner invokes
        # this factory only for bridge-mode groups, so host-network-only
        # clients never pay the netns capability probe
        self._network_manager = None
        self._network_lock = threading.Lock()
        self.drivers = drivers or DriverRegistry()
        # device plugins feed node devices (reference: devicemanager)
        self.device_manager = None
        if device_plugins:
            from ..plugins.device import DeviceManager
            self.device_manager = DeviceManager(device_plugins)
        # CSI plugins: per-plugin-id subprocesses; the node advertises
        # healthy node plugins for scheduler feasibility
        # (reference: client/pluginmanager/csimanager)
        self.csi_manager = None
        if csi_plugins:
            from ..plugins.csi import CSIManager
            self.csi_manager = CSIManager(data_dir, csi_plugins)
        self.state_db = StateDB(data_dir)
        if identity_signer is None:
            def identity_signer(claims, _c=conn):
                return _c.sign_identity(claims)
        self.identity_signer = identity_signer
        self.secrets_fetcher = conn.workload_variable
        fm = FingerprintManager(data_dir=data_dir, probe_jax=probe_jax)
        self.node = fm.fingerprint_node(node=node, name=name)
        if api_addr:
            # lets workloads reach the HTTP API via ${attr.nomad.api_addr}
            # (the connect sidecar's catalog resolution needs it)
            self.node.attributes["nomad.api_addr"] = api_addr
        # server->client forwarding channel (reference: client/rpc.go):
        # the node advertises its own listener so ANY server agent can
        # proxy fs/logs/stats for allocs it does not host in-process
        self.http = None
        if serve_http:
            from .http import ClientHttpServer
            self.http = ClientHttpServer(self)
            self.node.attributes["nomad.client_http"] = self.http.address
        # driver fingerprints -> node.drivers (reference: drivermanager)
        from ..structs import DriverInfo
        for dname, fp in self.drivers.fingerprints().items():
            self.node.drivers[dname] = DriverInfo(
                detected=bool(fp.get("detected")),
                healthy=bool(fp.get("healthy")))
        if self.device_manager is not None:
            self.node.node_resources.devices.extend(
                self.device_manager.all_devices())
        self._probe_csi_health()
        self.node.compute_class()
        # restore node identity across restarts
        prev = self.state_db.node_id()
        if prev:
            self.node.id = prev
        else:
            self.state_db.put_node_id(self.node.id)

        self.runners: Dict[str, AllocRunner] = {}
        self._services_registered: set = set()
        self._runner_lock = threading.Lock()
        self._last_index = 0
        self._last_ok_heartbeat = time.time()
        self._shutdown = threading.Event()
        self._frozen = threading.Event()    # fault injection: partition
        self._threads: List[threading.Thread] = []
        self.heartbeat_ttl = 10.0

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        self.restore()
        if self.http is not None:
            self.http.start()
        self.conn.register_node(self.node)
        loops = [(self._heartbeat_loop, "heartbeat"),
                 (self._watch_allocations, "alloc-watch"),
                 (self._health_loop, "health"),
                 (self._heartbeatstop_loop, "heartbeatstop")]
        if self.csi_manager is not None:
            loops.append((self._csi_fingerprint_loop, "csi-fingerprint"))
        for fn, label in loops:
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"client-{label}-{self.node.name}")
            t.start()
            self._threads.append(t)

    def _get_network_manager(self):
        from .netns import bridge_caps, shared_manager
        with self._network_lock:
            if self._network_manager is None and bridge_caps():
                # process-global: the bridge subnet is host-global state
                self._network_manager = shared_manager()
            return self._network_manager

    def shutdown(self) -> None:
        self._shutdown.set()
        if self.http is not None:
            self.http.shutdown()
        with self._runner_lock:
            runners = list(self.runners.values())
        for r in runners:
            r.stop(timeout=2.0)
        # plugin subprocesses must not outlive the client
        if self.device_manager is not None:
            self.device_manager.shutdown()
        if self._network_manager is not None:
            with self._runner_lock:
                ids = list(self.runners)
            for alloc_id in ids:
                try:
                    self._network_manager.destroy(alloc_id)
                except Exception:   # noqa: BLE001 -- best-effort
                    pass
        if self.csi_manager is not None:
            self.csi_manager.shutdown()
        self.drivers.shutdown()

    # -- fault injection (parity with SimClient for tests) -------------
    def freeze(self) -> None:
        self._frozen.set()

    def thaw(self) -> None:
        self._frozen.clear()

    # -- restore (reference: client.go:1215 restoreState) --------------
    def restore(self) -> None:
        for alloc_id in self.state_db.alloc_ids():
            alloc = self.conn.get_alloc(alloc_id)
            if alloc is None or alloc.terminal_status():
                self.state_db.delete_alloc(alloc_id)
                continue
            tasks = self.state_db.get_alloc_tasks(alloc_id)
            runner = AllocRunner(
                alloc, self.drivers, self.data_dir, node=self.node,
                on_update=self._on_runner_update,
                identity_signer=self.identity_signer,
                secrets_fetcher=self.secrets_fetcher,
                device_manager=self.device_manager,
                csi_manager=self.csi_manager,
                csi_volume_info=self.conn.csi_volume,
                network_manager=self._get_network_manager)
            with self._runner_lock:
                self.runners[alloc_id] = runner
            states = {name: st for name, (st, _h) in tasks.items()}
            handles = {name: h for name, (_st, h) in tasks.items()}
            runner.restore(states, handles)

    # -- heartbeats (reference: registerAndHeartbeat :1734) ------------
    def _probe_csi_health(self) -> bool:
        """Probe every CSI plugin's own readiness into
        node.csi_node_plugins; returns True when any health flag changed.
        Health comes from the plugin's probe, not blind optimism: an
        unready plugin must not attract placements -- and a plugin that
        becomes ready later must not leave the node ineligible forever,
        so the heartbeat loop re-probes (reference: csimanager's
        periodic fingerprint loop)."""
        if self.csi_manager is None:
            return False
        changed = False
        for pid in self.csi_manager.plugin_ids():
            try:
                ready = bool(self.csi_manager.plugins[pid]
                             .probe().get("ready", False))
            except Exception:  # noqa: BLE001 -- plugin failure
                ready = False
            prev = self.node.csi_node_plugins.get(pid, {}).get("healthy")
            if prev != ready:
                changed = True
            self.node.csi_node_plugins[pid] = {"healthy": ready}
        return changed

    def _csi_fingerprint_loop(self) -> None:
        """Periodic plugin health re-probe on its OWN thread (reference:
        csimanager's fingerprint loop): plugin RPCs are blocking pipe
        calls, and a wedged plugin subprocess must never stall the
        heartbeat thread into a server-side node-down sweep."""
        while not self._shutdown.is_set():
            if self._shutdown.wait(5.0):
                return
            if self._frozen.is_set():
                continue
            try:
                if self._probe_csi_health():
                    # changed plugin health must reach the scheduler's
                    # feasibility view
                    self.conn.register_node(self.node)
            except Exception:  # noqa: BLE001 -- server unreachable
                pass

    def _heartbeat_loop(self) -> None:
        while not self._shutdown.is_set():
            interval = max(self.heartbeat_ttl / 3.0, 0.05)
            if self._shutdown.wait(interval):
                return
            if self._frozen.is_set():
                continue
            try:
                ttl = self.conn.heartbeat(self.node.id)
                if ttl:
                    self.heartbeat_ttl = ttl
                    now = time.time()
                    if now - self._last_ok_heartbeat > self.heartbeat_ttl:
                        # we likely missed our TTL: the server may have
                        # swept our services on node-down -- re-register
                        self._services_registered.clear()
                    self._last_ok_heartbeat = now
                    self._reconcile_services()
                else:
                    # server doesn't know us (restart/state loss):
                    # re-register (reference: client retryRegisterNode on
                    # heartbeat 'node not found'); the server's node-down
                    # sweep dropped our services, so re-register them too
                    self.conn.register_node(self.node)
                    self._services_registered.clear()
            except Exception:   # noqa: BLE001 - server unreachable
                pass

    def _reconcile_services(self) -> None:
        """Register services for running allocs not yet in the catalog
        (idempotent ids; covers recovery after a node-down sweep)."""
        from .serviceregistration import build_registrations
        with self._runner_lock:
            runners = [r for r in self.runners.values()
                       if r.client_status == "running"
                       and r.alloc.id not in self._services_registered]
        for r in runners:
            regs = build_registrations(r.alloc, self.node)
            self._services_registered.add(r.alloc.id)
            if regs:
                try:
                    self.conn.register_services(regs)
                except Exception:   # noqa: BLE001
                    self._services_registered.discard(r.alloc.id)

    # -- fs + logs API (reference: client/fs_endpoint.go List/Stat/
    #    ReadAt + logs; served on the client, reached via agent HTTP) ---
    def _alloc_root(self, alloc_id: str) -> str:
        import os
        with self._runner_lock:
            runner = self.runners.get(alloc_id)
        if runner is None:
            raise KeyError(f"alloc {alloc_id} not found on this node")
        return os.path.normpath(runner.alloc_dir.alloc_dir)

    def _safe_path(self, alloc_id: str, rel: str) -> str:
        """Resolve rel against the alloc dir, refusing escapes -- both
        lexical (..) and via symlinks inside the alloc dir
        (reference: fs_endpoint.go path sandboxing)."""
        import os
        root = os.path.realpath(self._alloc_root(alloc_id))
        full = os.path.realpath(os.path.join(root, rel.lstrip("/")))
        if not (full == root or full.startswith(root + os.sep)):
            raise PermissionError(f"path escapes alloc dir: {rel}")
        return full

    def fs_list(self, alloc_id: str, path: str = "/") -> List[dict]:
        import os
        full = self._safe_path(alloc_id, path)
        out = []
        for name in sorted(os.listdir(full)):
            p = os.path.join(full, name)
            # lstat: a dangling symlink must not break the whole listing
            st = os.lstat(p)
            out.append({"name": name, "is_dir": os.path.isdir(p),
                        "size": st.st_size, "mod_time": st.st_mtime})
        return out

    def fs_stat(self, alloc_id: str, path: str) -> dict:
        import os
        full = self._safe_path(alloc_id, path)
        st = os.stat(full)
        return {"name": os.path.basename(full),
                "is_dir": os.path.isdir(full),
                "size": st.st_size, "mod_time": st.st_mtime}

    def fs_logs_total(self, alloc_id: str, task: str,
                      log_type: str = "stdout") -> int:
        """Total bytes across a task's rotated log frames -- the
        follow stream's cursor base."""
        import os
        if log_type not in ("stdout", "stderr"):
            raise ValueError(f"invalid log type {log_type!r}")
        log_dir = self._safe_path(alloc_id, "alloc/logs")
        return sum(os.path.getsize(os.path.join(log_dir, f))
                   for f in os.listdir(log_dir)
                   if f.startswith(f"{task}.{log_type}."))

    def fs_read(self, alloc_id: str, path: str, offset: int = 0,
                limit: int = 1 << 20) -> bytes:
        """A NEGATIVE offset tails the file (last |offset| bytes)."""
        import os as _os
        with open(self._safe_path(alloc_id, path), "rb") as f:
            if offset < 0:
                size = _os.fstat(f.fileno()).st_size
                offset = max(0, size + offset)
            f.seek(max(0, offset))
            return f.read(max(0, min(limit, 1 << 24)))

    def alloc_stats(self, alloc_id: str) -> dict:
        """Live per-task resource usage (reference: client
        allocations.Stats endpoint): cgroup stats for isolated tasks,
        /proc RSS for plain ones."""
        with self._runner_lock:
            runner = self.runners.get(alloc_id)
        if runner is None:
            raise KeyError(f"alloc {alloc_id} not running here")
        # the runner thread may still be inserting task runners; retry
        # the snapshot instead of racing the dict iteration
        items = []
        for _ in range(5):
            try:
                items = list(runner.task_runners.items())
                break
            except RuntimeError:
                continue
        tasks = {}
        for name, tr in items:
            tasks[name] = tr.stats()
        total_mem = sum(t.get("memory_bytes", 0) for t in tasks.values())
        total_cpu = sum(t.get("cpu_usec", 0) for t in tasks.values())
        return {"alloc_id": alloc_id, "tasks": tasks,
                "memory_bytes": total_mem, "cpu_usec": total_cpu}

    def alloc_restart(self, alloc_id: str, task: str = "") -> dict:
        """In-place restart of a live alloc's task(s) (reference:
        alloc_endpoint.go Restart via server->client forwarding)."""
        with self._runner_lock:
            runner = self.runners.get(alloc_id)
        if runner is None:
            raise KeyError(f"alloc {alloc_id} not running here")
        if task:
            targets = [task]
        else:
            # the runner thread may still be inserting task runners
            # (same race alloc_stats guards against)
            targets = []
            for _ in range(5):
                try:
                    targets = list(runner.task_runners.keys())
                    break
                except RuntimeError:
                    continue
        restarted = []
        for name in targets:
            tr = runner.task_runners.get(name)
            if tr is None:
                raise KeyError(f"task {name!r} not found in alloc")
            tr.restart()
            restarted.append(name)
        return {"restarted": restarted}

    def csi_create_volume(self, plugin_id: str, volume_id: str,
                          parameters=None) -> dict:
        """Dynamic provisioning through the controller plugin this node
        runs (reference: csi CreateVolume via a controller-capable
        client)."""
        if self.csi_manager is None:
            raise KeyError("no csi plugins on this node")
        plugin = self.csi_manager.plugins.get(plugin_id)
        if plugin is None:
            raise KeyError(f"no csi plugin {plugin_id!r} on this node")
        return plugin.create_volume(volume_id, parameters or {})

    def csi_delete_volume(self, plugin_id: str, volume_id: str) -> None:
        if self.csi_manager is None:
            raise KeyError("no csi plugins on this node")
        plugin = self.csi_manager.plugins.get(plugin_id)
        if plugin is None:
            raise KeyError(f"no csi plugin {plugin_id!r} on this node")
        plugin.delete_volume(volume_id)

    def alloc_signal(self, alloc_id: str, task: str,
                     sig: str = "SIGUSR1") -> dict:
        """Deliver a signal to a live task (reference: alloc_endpoint.go
        Signal via server->client forwarding)."""
        with self._runner_lock:
            runner = self.runners.get(alloc_id)
        if runner is None:
            raise KeyError(f"alloc {alloc_id} not running here")
        tr = runner.task_runners.get(task)
        if tr is None:
            raise KeyError(f"task {task!r} not found in alloc")
        if tr.handle is None or tr.driver is None:
            raise KeyError(f"task {task!r} has no live handle")
        tr.driver.signal_task(tr.handle, sig)
        return {"signalled": task, "signal": sig}

    def alloc_exec(self, alloc_id: str, task: str,
                   cmd: List[str], timeout: float = 10.0) -> dict:
        """One-shot command inside a live task's context (reference:
        `nomad alloc exec` / plugins/drivers ExecTask -- scoped to the
        non-interactive form: captured stdout/stderr + exit code)."""
        with self._runner_lock:
            runner = self.runners.get(alloc_id)
        if runner is None:
            raise KeyError(f"alloc {alloc_id} not running here")
        tr = runner.task_runners.get(task)
        if tr is None:
            raise KeyError(f"task {task!r} not found in alloc")
        if tr.handle is None or tr.driver is None:
            raise KeyError(f"task {task!r} has no live handle")
        return tr.driver.exec_task(tr.handle, tr.env, tr.task_dir, cmd,
                                   timeout=timeout)

    def fs_logs(self, alloc_id: str, task: str, log_type: str = "stdout",
                offset: int = 0, limit: int = 1 << 20) -> bytes:
        """Rotated log frames for a task, sliced WITHOUT loading the full
        history (reference: fs_endpoint.go logs path:
        alloc/logs/<task>.<type>.<index>). A NEGATIVE offset tails: the
        last |offset| bytes of the concatenated frames (the reference's
        origin="end" semantics), clamped by limit."""
        import os
        if log_type not in ("stdout", "stderr"):
            raise ValueError(f"invalid log type {log_type!r}")
        log_dir = self._safe_path(alloc_id, "alloc/logs")

        def frame_idx(name: str) -> int:
            try:
                return int(name.rsplit(".", 1)[1])
            except ValueError:
                return 0

        # numeric rotation order: .2 before .10 (lexicographic would
        # scramble content past ten frames)
        frames = sorted(
            (f for f in os.listdir(log_dir)
             if f.startswith(f"{task}.{log_type}.")),
            key=frame_idx)
        if offset < 0:
            total = sum(os.path.getsize(os.path.join(log_dir, f))
                        for f in frames)
            offset = max(0, total + offset)
        out = []
        pos, want = 0, max(0, limit)
        skip = max(0, offset)
        for frame in frames:
            path = os.path.join(log_dir, frame)
            size = os.path.getsize(path)
            if pos + size <= skip:
                pos += size
                continue
            with open(path, "rb") as f:
                f.seek(max(0, skip - pos))
                chunk = f.read(want)
            out.append(chunk)
            want -= len(chunk)
            pos += size
            skip = max(skip, pos)
            if want <= 0:
                break
        return b"".join(out)

    # -- host stats (reference: client/hoststats/) ---------------------
    def client_stats(self) -> dict:
        if not hasattr(self, "_hoststats"):
            from .hoststats import HostStatsCollector
            self._hoststats = HostStatsCollector(self.data_dir)
        stats = self._hoststats.collect()
        stats["node_id"] = self.node.id
        with self._runner_lock:
            stats["allocs_running"] = len([
                r for r in self.runners.values()
                if r.client_status == "running"])
        return stats

    # -- watch loop (reference: watchAllocations :2280) ----------------
    def _watch_allocations(self) -> None:
        while not self._shutdown.is_set():
            if self._frozen.is_set():
                time.sleep(0.05)
                continue
            try:
                allocs, index = self.conn.pull_allocs(
                    self.node.id, self._last_index, timeout=1.0)
            except Exception:   # noqa: BLE001
                time.sleep(0.2)
                continue
            self._last_index = index
            self._run_allocs(allocs)

    def _run_allocs(self, allocs: List[Allocation]) -> None:
        """Diff desired vs running (reference: runAllocs :2538)."""
        desired = {a.id: a for a in allocs}
        updates: List[Allocation] = []
        with self._runner_lock:
            known = dict(self.runners)
        # stop/evict + server-side removals
        for alloc_id, runner in known.items():
            a = desired.get(alloc_id)
            if a is None:
                # server no longer tracks it: destroy (reference: alloc GC)
                runner.destroy(timeout=2.0)
                with self._runner_lock:
                    self.runners.pop(alloc_id, None)
                self._services_registered.discard(alloc_id)
                self.state_db.delete_alloc(alloc_id)
            elif a.desired_status != ALLOC_DESIRED_RUN and \
                    runner.client_status not in (ALLOC_CLIENT_COMPLETE,
                                                 ALLOC_CLIENT_FAILED):
                runner.stop(timeout=5.0)
                updates.append(runner.client_update())
        # new allocations
        for alloc_id, a in desired.items():
            if alloc_id in known or a.terminal_status() or \
                    a.client_terminal_status():
                continue
            if a.desired_status != ALLOC_DESIRED_RUN:
                continue
            runner = AllocRunner(
                a, self.drivers, self.data_dir, node=self.node,
                on_update=self._on_runner_update,
                identity_signer=self.identity_signer,
                secrets_fetcher=self.secrets_fetcher,
                device_manager=self.device_manager,
                csi_manager=self.csi_manager,
                csi_volume_info=self.conn.csi_volume,
                network_manager=self._get_network_manager)
            with self._runner_lock:
                self.runners[alloc_id] = runner
            self.state_db.put_alloc(alloc_id, a.modify_index)
            runner.start()
        if updates:
            self._push_updates(updates)
        self._gc_terminal_runners()

    # -- runner callbacks ----------------------------------------------
    def _on_runner_update(self, runner: AllocRunner) -> None:
        for name, tr in runner.task_runners.items():
            self.state_db.put_task_state(runner.alloc.id, name,
                                         tr.state, tr.handle)
        # native service discovery: register once the alloc is running
        # (deregistration is the server's terminal-status sweep)
        self._reconcile_services()
        self._push_updates([runner.client_update()])

    def _push_updates(self, updates: List[Allocation]) -> None:
        if self._frozen.is_set():
            return
        try:
            self.conn.update_allocs(updates)
        except Exception:   # noqa: BLE001
            pass

    # -- deployment health (reference: health_hook + allochealth) ------
    def _health_loop(self) -> None:
        while not self._shutdown.wait(0.1):
            if self._frozen.is_set():
                continue
            with self._runner_lock:
                runners = list(self.runners.values())
            for r in runners:
                if not r.alloc.deployment_id or \
                        r.deployment_health is not None:
                    continue
                min_healthy = 0.05
                if r.alloc.job is not None:
                    tg = r.alloc.job.lookup_task_group(r.alloc.task_group)
                    upd = (tg.update if tg and tg.update
                           else r.alloc.job.update)
                    if upd is not None:
                        min_healthy = upd.min_healthy_time_s
                decided = r.check_health(min_healthy)
                if decided is not None:
                    self._push_updates([r.client_update()])

    # -- heartbeatstop (reference: client/heartbeatstop.go) ------------
    def _heartbeatstop_loop(self) -> None:
        while not self._shutdown.wait(0.2):
            lost_for = time.time() - self._last_ok_heartbeat
            with self._runner_lock:
                runners = list(self.runners.values())
            for r in runners:
                tg = (r.alloc.job.lookup_task_group(r.alloc.task_group)
                      if r.alloc.job else None)
                stop_after = (tg.stop_after_client_disconnect_s
                              if tg else None)
                if stop_after is not None and lost_for >= stop_after and \
                        r.client_status not in (ALLOC_CLIENT_COMPLETE,
                                                ALLOC_CLIENT_FAILED):
                    r.stop(timeout=5.0)

    # -- client GC (reference: client/gc.go AllocGarbageCollector) -----
    def _gc_terminal_runners(self) -> None:
        with self._runner_lock:
            terminal = [(aid, r) for aid, r in self.runners.items()
                        if r.client_status in (ALLOC_CLIENT_COMPLETE,
                                               ALLOC_CLIENT_FAILED)
                        and r.wait(timeout=0)]
            excess = len(terminal) - MAX_TERMINAL_RUNNERS
            victims = terminal[:excess] if excess > 0 else []
            for aid, _ in victims:
                self.runners.pop(aid, None)
                self._services_registered.discard(aid)
        for aid, runner in victims:
            runner.destroy(timeout=1.0)
            self.state_db.delete_alloc(aid)
