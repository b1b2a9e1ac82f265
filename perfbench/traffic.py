"""The one general load generator. A traffic mix is a data file
(traffic/<name>.json); nothing here knows a mix, a configuration or a
cell by name.

Every job goes through the same lifecycle, as an operator's
`nomad job run` then `nomad job stop` would drive it:
PUT /v1/jobs -> watch /v1/job/<id>/summary with blocking queries until
every alloc is run -> DELETE /v1/job/<id> -> tell the fleet stand-in,
which acknowledges the stops so the capacity frees.

- loop "closed": `submitters` threads, each sending its next job when
  its last one is placed and stopped;
- loop "open": jobs due at times fixed by the seed, each timed from the
  instant it was due, whenever it was really sent. The schedule is made
  stretch by stretch (the lead-in, then the window): each stretch gets
  the quantile gaps of its own length in an order drawn from the seed,
  so it holds the same number of jobs and the same gaps for every seed.
"""
from __future__ import annotations

import math
import queue
import random
import threading
import time

from httpc import ApiError


def arrival_times(rate_per_s: float, horizon_s: float, seed: int) -> list:
    """Due times in [0, horizon): the n = rate * horizon quantile gaps of
    the exponential distribution, shuffled by the seed, so every seed
    offers the same load in another order."""
    n = max(1, int(round(rate_per_s * horizon_s)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate_per_s for i in range(n)]
    random.Random(seed).shuffle(gaps)
    scale = horizon_s / sum(gaps)
    t, out = 0.0, []
    for g in gaps:
        out.append(t)
        t += g * scale
    return out


class Generator:
    def __init__(self, api, mix: dict, make_job, stand_in_ack, tag: str,
                 seed: int):
        self.api = api
        self.mix = mix
        self.make_job = make_job        # (job_id) -> (jobspec, count)
        self.ack = stand_in_ack         # (job_id) -> None
        self.tag = tag
        self.seed = seed
        self.records: list = []
        self.placed_seen = 0            # allocs seen run over the API
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: list = []
        self._seq = 0
        self.poll_floor_s = float(mix.get("poll_floor_ms", 10)) / 1e3
        self.place_timeout_s = float(mix.get("place_timeout_s", 120))

    # -- one job ----------------------------------------------------------
    def _next_id(self) -> str:
        with self._lock:
            self._seq += 1
            return f"{self.tag}-s{self.seed}-{self._seq:06d}"

    def lifecycle(self, due: float, stretch: int = 0) -> dict:
        job_id = self._next_id()
        spec, count = self.make_job(job_id)
        rec = {"id": job_id, "count": count, "due": due, "ok": False,
               "stretch": stretch, "seen": 0, "placed": None,
               "error": None}
        with self._lock:
            self.records.append(rec)
        try:
            rec["sent"] = time.monotonic()
            reply, index = self.api.call("PUT", "/v1/jobs", {"job": spec})
            rec["put_ms"] = (time.monotonic() - rec["sent"]) * 1e3
            rec["eval_id"] = reply["eval_id"]
            deadline = rec["sent"] + self.place_timeout_s
            while True:
                t_poll = time.monotonic()
                body, index = self.api.call(
                    "GET", f"/v1/job/{job_id}/summary?index={index}&wait=1s")
                now = time.monotonic()
                seen = sum(tg["starting"] + tg["running"]
                           for tg in body["summary"].values())
                if seen > rec["seen"]:
                    with self._lock:
                        self.placed_seen += seen - rec["seen"]
                    rec["seen"] = seen
                if seen >= count:
                    rec["placed"] = now
                    rec["index_placed"] = index
                    rec["ok"] = True
                    break
                if now > deadline:
                    rec["error"] = f"only {seen}/{count} run after " \
                                   f"{self.place_timeout_s:.0f}s"
                    break
                rest = self.poll_floor_s - (time.monotonic() - t_poll)
                if rest > 0:
                    time.sleep(rest)
            if self.mix.get("stop_when_placed", True):
                self.api.call("DELETE", f"/v1/job/{job_id}")
                rec["stopped"] = time.monotonic()
                self.ack(job_id)
        except (ApiError, OSError, KeyError, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    # -- the two loops ----------------------------------------------------
    def _closed(self) -> None:
        while not self._stop.is_set():
            self.lifecycle(time.monotonic())

    def _open(self, t0: float, stretches: list) -> None:
        rate = float(self.mix["rate_per_s"])
        due, at = [], t0
        for k, span_s in enumerate(stretches):
            due += [(at + t, k)
                    for t in arrival_times(rate, span_s, self.seed + k)]
            at += span_s
        todo: "queue.Queue" = queue.Queue()

        def worker():
            while True:
                job = todo.get()
                if job is None:
                    return
                self.lifecycle(*job)
        pool = [threading.Thread(target=worker, daemon=True,
                                 name=f"open-{i}")
                for i in range(int(self.mix.get("max_in_flight", 48)))]
        for th in pool:
            th.start()
        for job in due:
            wait = job[0] - time.monotonic()
            if wait > 0 and self._stop.wait(wait):
                break
            if self._stop.is_set():
                break
            todo.put(job)
        for _ in pool:
            todo.put(None)
        for th in pool:
            th.join()

    def start(self, t0: float, stretches: list) -> None:
        """Load from `t0` on; an open loop's schedule covers the
        `stretches` (seconds of each) in turn, and a job's record says
        which one it was due in."""
        if self.mix["loop"] == "closed":
            for i in range(int(self.mix["submitters"])):
                th = threading.Thread(target=self._closed, daemon=True,
                                      name=f"closed-{i}")
                self._threads.append(th)
        elif self.mix["loop"] == "open":
            self._threads.append(threading.Thread(
                target=self._open, args=(t0, stretches), daemon=True,
                name="open-dispatch"))
        else:
            raise ValueError(f"traffic loop {self.mix['loop']!r}")
        for th in self._threads:
            th.start()

    def stop(self, timeout_s: float) -> bool:
        """No new jobs; wait for the ones in flight. True when every
        thread ended."""
        self._stop.set()
        deadline = time.monotonic() + timeout_s
        for th in self._threads:
            th.join(max(0.0, deadline - time.monotonic()))
        return not any(th.is_alive() for th in self._threads)

    def snapshot(self) -> list:
        with self._lock:
            return [dict(r) for r in self.records]
