"""Coordinator half of the collection fleet (see ``fleet.py`` for the
architecture and the byte-identical-merge invariant).

Split out of ``fleet.py`` so the collector role never imports it: this
module pulls in ``loop.py`` and therefore the jax model stack, which a
per-cycle spawned I/O worker has no business paying for."""

from __future__ import annotations

import dataclasses
import os
import pathlib
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional

from ..data.campaign import completed_keys, load_records
from .fleet import (
    DEFAULT_FLEET_DIR,
    _configured_executor,
    collector_shard_path,
    run_collector,
)
from .loop import ContinuousTuningLoop, LoopConfig, _format_status, config_kwargs_from_args
from .state import FleetLog

__all__ = ["FleetConfig", "FleetCoordinator", "coordinator_main"]


@dataclasses.dataclass
class FleetConfig(LoopConfig):
    """LoopConfig plus the fleet's topology/supervision knobs."""

    collectors: int = 2              # worker processes == campaign shards
    heartbeat_timeout_s: float = 60.0  # silence after which a live worker is stale
    heartbeat_every_s: float = 5.0   # collector liveness-tick cadence
    poll_interval_s: float = 0.2     # coordinator supervision cadence
    max_leases: int = 3              # lease attempts per shard per cycle
    executor_kind: str = "real"      # "real" I/O or "synthetic" dry-run rows
    sleep_per_case: float = 0.0      # pacing sleep (scaling experiments/tests)

    def __post_init__(self):
        super().__post_init__()
        if self.collectors < 1:
            raise ValueError("collectors must be >= 1")
        if self.executor_kind not in ("real", "synthetic"):
            raise ValueError(f"unknown executor kind {self.executor_kind!r}")


def collector_env() -> dict:
    """Environment of a spawned collector process.

    An active fault plan rides along (``REPRO_FAULT_PLAN``), so collectors
    inject from the same seeded schedule as the coordinator.  Collectors do
    host I/O only and never import JAX; ``JAX_PLATFORMS=cpu`` makes that
    explicit, so the coordinator, which refits with JAX, stays the only
    process that can claim the accelerator (a chip serves one process)."""
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    env["JAX_PLATFORMS"] = "cpu"
    return env


class _SubprocessCollector:
    """Default collector handle: a real ``--role collector`` child process."""

    def __init__(self, argv: List[str], env: dict, log_path: pathlib.Path):
        log_path.parent.mkdir(parents=True, exist_ok=True)
        self._logf = open(log_path, "w")
        self._proc = subprocess.Popen(argv, env=env, stdout=self._logf,
                                      stderr=subprocess.STDOUT)
        self.pid = self._proc.pid

    def poll(self) -> Optional[int]:
        rc = self._proc.poll()
        if rc is not None and not self._logf.closed:
            self._logf.close()
        return rc

    def kill(self) -> None:
        try:
            self._proc.kill()
            self._proc.wait(timeout=10)
        except (OSError, subprocess.SubprocessError):
            pass
        if not self._logf.closed:
            self._logf.close()


@dataclasses.dataclass
class _Lease:
    shard: int
    attempt: int
    handle: object
    started: float  # wall clock, comparable with heartbeat timestamps


class FleetCoordinator(ContinuousTuningLoop):
    """Drives fleet cycles: lease -> supervise -> re-lease -> merge/refit.

    Only the *collect* step differs from :class:`ContinuousTuningLoop` —
    shards run in collector processes under lease supervision; merge, refit,
    re-recommend, resume, warm-start, and repair are all inherited.  ``spawn``
    overrides how a lease becomes a worker (tests inject in-process fakes);
    the default spawns ``python -m repro.service.fleet --role collector``
    subprocesses with per-worker log files under ``<out_dir>/logs/``."""

    def __init__(
        self,
        cfg: FleetConfig,
        executor: Optional[Callable] = None,
        progress: Optional[Callable[[str], None]] = None,
        spawn: Optional[Callable] = None,
    ):
        super().__init__(cfg, executor=_configured_executor(cfg, executor),
                         progress=progress)
        self.cfg: FleetConfig = cfg
        self.fleet_log = FleetLog(cfg.out_dir / "fleet_state.jsonl")
        self._spawn = spawn or self._spawn_subprocess

    # -- leasing -------------------------------------------------------
    def _cycle_collectors(self, cycle: int) -> int:
        """Collector count the cycle was actually collected with (from its
        state record) — a fleet resumed with a different ``--collectors``
        must repair old cycles under their original shard split, or shards
        beyond the new count would never heal."""
        for rec in self.state.cycles():
            if rec.get("cycle") == cycle:
                return int(rec.get("collectors", self.cfg.collectors))
        return self.cfg.collectors

    def _repair_specs(self, cycle: int) -> List[tuple]:
        n = self._cycle_collectors(cycle)
        return [(collector_shard_path(self.cfg.out_dir, i, cycle), (i, n))
                for i in range(n)]

    def _spawn_subprocess(self, shard: int, cycle: int, attempt: int):
        if not isinstance(self.cfg.campaign, str):
            raise ValueError(
                "subprocess collectors need a registered campaign name; "
                "pass spawn= to run ad-hoc Campaign objects in-process")
        argv = [
            sys.executable, "-m", "repro.service.fleet", "--role", "collector",
            "--campaign", self.cfg.campaign,
            "--out-dir", str(self.cfg.out_dir),
            "--cycle", str(cycle),
            "--shard", f"{shard}/{self.cfg.collectors}",
            "--seeds", *map(str, self._cycle_seeds(cycle)),
            "--attempt", str(attempt),
        ]
        if self.cfg.fast:
            argv.append("--fast")
        if self.cfg.executor_kind != "real":
            argv += ["--executor", self.cfg.executor_kind]
        if self.cfg.sleep_per_case:
            argv += ["--sleep-per-case", str(self.cfg.sleep_per_case)]
        argv += ["--heartbeat-every", str(self.cfg.heartbeat_every_s)]
        if self.cfg.case_deadline_s is not None:
            argv += ["--case-deadline", str(self.cfg.case_deadline_s)]
        argv += ["--max-retries", str(self.cfg.max_retries),
                 "--quarantine-after",
                 str(self.cfg.quarantine_after or 0)]
        log_path = (self.cfg.out_dir / "logs"
                    / f"collector_c{cycle:04d}_s{shard}_a{attempt}.log")
        return _SubprocessCollector(argv, collector_env(), log_path)

    def _lease(self, leases: Dict[int, _Lease], shard: int, cycle: int,
               attempt: int) -> None:
        handle = self._spawn(shard, cycle, attempt)
        self.fleet_log.append({
            "type": "lease", "cycle": cycle, "shard": shard,
            "attempt": attempt, "collectors": self.cfg.collectors,
            "worker_pid": getattr(handle, "pid", None),
        })
        leases[shard] = _Lease(shard, attempt, handle, time.time())
        self._log(f"cycle {cycle}: leased shard {shard}/{self.cfg.collectors}"
                  f" (attempt {attempt})")

    def _relet_or_fail(self, leases: Dict[int, _Lease], lease: _Lease,
                       cycle: int, why: str) -> int:
        """Handle a dead/stale lease: re-lease the shard or give up."""
        attempt = lease.attempt + 1
        if attempt >= self.cfg.max_leases:
            raise RuntimeError(
                f"cycle {cycle} shard {lease.shard}: {why}; giving up after "
                f"{self.cfg.max_leases} lease attempts (completed cases are "
                "kept — re-running the fleet resumes this cycle)")
        self._log(f"cycle {cycle}: shard {lease.shard} {why} -> re-leasing")
        self._lease(leases, lease.shard, cycle, attempt)
        return 1

    # -- the overridden collect step ----------------------------------
    def _collect(self, cycle: int, seeds: List[int]) -> dict:
        n = self.cfg.collectors
        hosts = {f"host_{i}": {"host": "", "n_executed": 0,
                               "n_failures": 0, "releases": 0}
                 for i in range(n)}
        executed: Dict[int, int] = {i: 0 for i in range(n)}
        releases = 0
        leases: Dict[int, _Lease] = {}
        try:
            for i in range(n):
                self._lease(leases, i, cycle, attempt=0)
            while leases:
                for shard, lease in list(leases.items()):
                    rc = lease.handle.poll()
                    if rc is None:
                        hb = self.fleet_log.last_heartbeat(cycle, shard)
                        alive_at = max(lease.started, hb or 0.0)
                        if time.time() - alive_at > self.cfg.heartbeat_timeout_s:
                            lease.handle.kill()
                            del leases[shard]
                            executed[shard] += self._attempt_progress(
                                cycle, shard, lease.attempt)
                            hosts[f"host_{shard}"]["releases"] += 1
                            releases += self._relet_or_fail(
                                leases, lease, cycle,
                                f"stale (no heartbeat for "
                                f">{self.cfg.heartbeat_timeout_s:g}s)")
                        continue
                    del leases[shard]
                    # completion = this attempt's shard_done record, NOT the
                    # exit code: a collector whose cases failed exits non-zero
                    # for human callers, but its failures are durable records
                    # that re-run via resume/repair — only a worker that died
                    # without reporting completion gets its shard re-leased
                    done_rec = self._shard_done(cycle, shard, lease.attempt)
                    if done_rec is not None:
                        executed[shard] += int(done_rec.get("n_executed", 0))
                        hosts[f"host_{shard}"]["host"] = done_rec.get("host", "")
                    else:
                        executed[shard] += self._attempt_progress(
                            cycle, shard, lease.attempt)
                        hosts[f"host_{shard}"]["releases"] += 1
                        releases += self._relet_or_fail(
                            leases, lease, cycle,
                            f"died without completing (exit code {rc})")
                if leases:
                    time.sleep(self.cfg.poll_interval_s)
        finally:
            for lease in leases.values():  # never leak workers on error
                lease.handle.kill()

        # per-shard outcome from the shard files themselves (ground truth:
        # error records never superseded by a successful re-run, retry/
        # quarantine provenance survives worker crashes)
        n_failures = 0
        retried = timeouts = quarantined = 0
        for i in range(n):
            records = load_records(collector_shard_path(self.cfg.out_dir, i, cycle))
            done = completed_keys(records)
            err = {(r.get("case_id"), r.get("rep", 0), r.get("seed", 0))
                   for r in records if r.get("status") == "error"} - done
            for r in records:
                error = r.get("error") or {}
                retried += int(r.get("retries", 0) or error.get("retries", 0))
                if error.get("category") == "timeout":
                    timeouts += 1
                if r.get("status") == "quarantined":
                    quarantined += 1
            slot = hosts[f"host_{i}"]
            slot["n_executed"] = executed[i]
            slot["n_failures"] = len(err)
            if not slot["host"]:
                hb = self.fleet_log.records(type="heartbeat", cycle=cycle, shard=i)
                slot["host"] = hb[-1].get("host", "") if hb else ""
            n_failures += len(err)
        write_retries = sum(
            int(r.get("write_retries", 0))
            for r in self.fleet_log.records(type="shard_done", cycle=cycle))
        return {
            "n_executed": sum(executed.values()),
            "n_failures": n_failures,
            "collectors": n,
            "releases": releases,
            "hosts": hosts,
            "faults": {"retried": retried, "timeouts": timeouts,
                       "quarantined": quarantined,
                       "write_retries": write_retries},
        }

    def _shard_done(self, cycle: int, shard: int, attempt: int) -> Optional[dict]:
        """This attempt's completion record, if the collector reported one."""
        for r in self.fleet_log.records(type="shard_done", cycle=cycle,
                                        shard=shard):
            if int(r.get("attempt", 0)) == attempt:
                return r
        return None

    def _attempt_progress(self, cycle: int, shard: int, attempt: int) -> int:
        """Cases a crashed/stale attempt completed before dying (its records
        are durable and will be skipped by the re-lease), per its own
        heartbeats — attempt-scoped so consecutive crashes don't double-count
        an earlier attempt's progress."""
        beats = [b for b in self.fleet_log.records(type="heartbeat",
                                                   cycle=cycle, shard=shard)
                 if int(b.get("attempt", 0)) == attempt]
        return max((int(b.get("n_done", 0)) for b in beats), default=0)


def coordinator_main(args) -> int:
    """The ``--role coordinator`` CLI body (parser lives in ``fleet.py``)."""
    from ._cli import chaos_plan_from_args
    chaos_plan_from_args(args)  # exports the plan for spawned collectors
    cfg = FleetConfig(
        **config_kwargs_from_args(args),
        collectors=args.collectors,
        heartbeat_timeout_s=args.heartbeat_timeout,
        heartbeat_every_s=args.heartbeat_every,
        poll_interval_s=args.poll_interval,
        max_leases=args.max_leases,
        executor_kind=args.executor,
        sleep_per_case=args.sleep_per_case,
    )
    fleet = FleetCoordinator(cfg, progress=lambda m: print(f"[fleet] {m}"))

    if args.status:
        cycles = fleet.state.cycles()
        print(_format_status(cycles, fleet.state.corrupt_lines))
        leases = fleet.fleet_log.records(type="lease")
        if leases:
            n_re = sum(1 for r in leases if r.get("attempt", 0) > 0)
            line = (f"fleet log: {len(leases)} lease(s), {n_re} re-lease(s), "
                    f"{len(fleet.fleet_log.records(type='heartbeat'))} heartbeat(s)")
            if fleet.fleet_log.corrupt_lines:
                line += f", {fleet.fleet_log.corrupt_lines} corrupt line(s) skipped"
            print(line)
        return 0

    if args.force:
        fleet.state.path.unlink(missing_ok=True)
        fleet.fleet_log.path.unlink(missing_ok=True)
        fleet.merged_path.unlink(missing_ok=True)
        for p in fleet._shard_files():
            p.unlink()

    start = fleet.state.next_cycle()
    if 0 < start < cfg.cycles:
        print(f"[fleet] resuming at cycle {start}/{cfg.cycles}")
    completed = fleet.run(max_cycles=args.max_cycles)
    if not completed and start >= cfg.cycles:
        print(f"[fleet] all {cfg.cycles} cycles already complete "
              f"(state: {fleet.state.path}); use --cycles to extend or "
              "--force to restart")
    cycles = fleet.state.cycles()
    print(_format_status(cycles, fleet.state.corrupt_lines))
    n_failures = sum(r["n_failures"] for r in completed)
    if n_failures:
        print(f"[fleet] {n_failures} case failure(s) recorded; they re-run "
              "on the next invocation", file=sys.stderr)
        return 1
    return 0
