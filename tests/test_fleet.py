"""Fleet collection (repro.service.fleet): the byte-identical-merge
invariant across collector counts, crash/stale re-leasing (including a real
``kill -9``), coordinator resume, state-schema migration, and per-host
provenance in ``--status``."""

import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from repro.core.autotune import ConfigSpace
from repro.data.campaign import load_records
from repro.data.registry import Campaign, matrix_cases
from repro.service.fleet import (
    FleetConfig,
    FleetCoordinator,
    run_collector,
    synthetic_executor,
)
from repro.service.fleet import main as fleet_main
from repro.service.loop import ContinuousTuningLoop, LoopConfig, _format_status
from repro.service.loop import main as loop_main
from repro.service.state import STATE_SCHEMA_VERSION, LoopState

# All in-process tests share one deterministic 6-case campaign and the
# synthetic executor: any collector topology must reproduce the exact same
# merged.jsonl bytes as an uninterrupted single-host run.


def _campaign():
    return Campaign(
        "fleet_fake", "test campaign",
        lambda fast=False: tuple(matrix_cases(
            "pipeline", id_prefix="ff", backend=["tmpfs"], format=["raw"],
            batch_size=[16, 32], num_workers=[0, 2, 4],
        )),
    )


def _space():
    return ConfigSpace(batch_size=(16, 32), num_workers=(0, 2, 4),
                       block_kb=(64,), n_threads=(1,), prefetch_depth=(1,))


def _fleet_cfg(out_dir, collectors, **kw):
    kw.setdefault("campaign", _campaign())
    kw.setdefault("cycles", 2)
    kw.setdefault("space", _space())
    kw.setdefault("min_observations", 6)
    kw.setdefault("refit_every", 6)
    kw.setdefault("poll_interval_s", 0.01)
    kw.setdefault("executor_kind", "synthetic")
    return FleetConfig(out_dir=out_dir, collectors=collectors, **kw)


def _single_host_bytes(tmp_path, cycles=2):
    """merged.jsonl bytes of the reference single-host loop run."""
    out = tmp_path / "single"
    cfg = LoopConfig(out_dir=out, campaign=_campaign(), cycles=cycles,
                     space=_space(), min_observations=6, refit_every=6)
    records = ContinuousTuningLoop(cfg, executor=synthetic_executor).run()
    return (out / "merged.jsonl").read_bytes(), records


def _decision_view(record):
    return {k: record[k] for k in
            ("cycle", "n_observations", "refit", "current_config", "top")} | {
            "decision": record["decision"]}


class _Handle:
    """In-process stand-in for a collector process that already exited."""

    def __init__(self, rc=0):
        self._rc = rc
        self.pid = os.getpid()

    def poll(self):
        return self._rc

    def kill(self):
        self._rc = -9


class _HangHandle:
    """A worker that stays alive but makes no progress (no heartbeats)."""

    def __init__(self):
        self._rc = None
        self.pid = 0

    def poll(self):
        return self._rc

    def kill(self):
        self._rc = -9


def _inline_spawn(cfg, fail_plan=None):
    """Spawn hook running the collector synchronously in this process.

    ``fail_plan`` maps (cycle, shard, attempt) -> max_cases: the attempt
    executes that many cases, writes no completion record, and its handle
    reports exit code -9 — exactly what a mid-shard ``kill -9`` leaves
    behind (durable partial records, no shard_done)."""
    plan = dict(fail_plan or {})

    def spawn(shard, cycle, attempt):
        max_cases = plan.get((cycle, shard, attempt))
        run_collector(cfg, cycle, shard, max_cases=max_cases, attempt=attempt)
        return _Handle(-9 if max_cases is not None else 0)

    return spawn


# ------------------------------------------------------- merge invariant


def test_fleet_merged_byte_identical_across_collector_counts(tmp_path):
    """The core fleet guarantee: merged.jsonl after every cycle is
    byte-identical for 1, 2, and 4 collectors — and identical to a plain
    single-host loop run — and so are the decisions taken on top of it."""
    ref_bytes, ref_records = _single_host_bytes(tmp_path)
    for n in (1, 2, 4):
        cfg = _fleet_cfg(tmp_path / f"fleet{n}", collectors=n)
        records = FleetCoordinator(cfg, spawn=_inline_spawn(cfg)).run()
        assert (cfg.out_dir / "merged.jsonl").read_bytes() == ref_bytes
        assert len(records) == len(ref_records) == 2
        for a, b in zip(ref_records, records):
            assert _decision_view(a) == _decision_view(b)
        assert records[0]["schema_version"] == STATE_SCHEMA_VERSION
        assert records[0]["collectors"] == n
        assert set(records[0]["hosts"]) == {f"host_{i}" for i in range(n)}
        assert records[0]["n_executed"] == 6  # disjoint + complete shards


def test_fleet_collector_crash_releases_and_dataset_matches(tmp_path):
    """Shard 1's first attempt dies after one case; the coordinator
    re-leases it, the replacement resumes the missing cases, and the final
    dataset is still byte-identical to the single-host run."""
    ref_bytes, _ = _single_host_bytes(tmp_path, cycles=1)
    cfg = _fleet_cfg(tmp_path / "crash", collectors=2, cycles=1)
    spawn = _inline_spawn(cfg, fail_plan={(0, 1, 0): 1})
    coord = FleetCoordinator(cfg, spawn=spawn)
    records = coord.run()
    assert records[0]["releases"] == 1
    assert records[0]["hosts"]["host_1"]["releases"] == 1
    assert records[0]["hosts"]["host_0"]["releases"] == 0
    leases = coord.fleet_log.records(type="lease", cycle=0, shard=1)
    assert [r["attempt"] for r in leases] == [0, 1]
    assert (cfg.out_dir / "merged.jsonl").read_bytes() == ref_bytes


def test_fleet_stale_collector_is_killed_and_released(tmp_path):
    """A worker that stays alive but stops heartbeating is declared stale,
    killed, and its shard re-leased."""
    cfg = _fleet_cfg(tmp_path / "stale", collectors=2, cycles=1,
                     heartbeat_timeout_s=0.2)
    hang = _HangHandle()
    state = {"hung_once": False}

    def spawn(shard, cycle, attempt):
        if shard == 0 and not state["hung_once"]:
            state["hung_once"] = True
            return hang
        run_collector(cfg, cycle, shard, attempt=attempt)
        return _Handle(0)

    records = FleetCoordinator(cfg, spawn=spawn).run()
    assert hang.poll() == -9  # the coordinator killed the stale worker
    assert records[0]["releases"] == 1
    keys = {(r["case_id"], r["rep"], r["seed"])
            for r in load_records(cfg.out_dir / "merged.jsonl")}
    assert len(keys) == 6  # dataset complete despite the hang


def test_fleet_case_failure_is_not_a_crash(tmp_path):
    """A collector whose *cases* fail still completes its shard: the failure
    is a durable error record (healed by the next invocation's repair pass),
    not a worker crash — the shard must NOT be re-leased."""
    cfg = _fleet_cfg(tmp_path / "flaky", collectors=2, cycles=1)

    def flaky(case, ctx, seed):
        if case.id == "ff-tmpfs-raw-b32-w4":
            raise RuntimeError("transient storage error")
        return synthetic_executor(case, ctx, seed)

    def spawn(shard, cycle, attempt):
        results = run_collector(cfg, cycle, shard, executor=flaky,
                                attempt=attempt)
        # mirror the subprocess contract: non-zero exit when cases failed
        return _Handle(1 if any(r.failures for r in results) else 0)

    coord = FleetCoordinator(cfg, spawn=spawn)
    records = coord.run()
    assert records[0]["n_failures"] == 1
    assert records[0]["releases"] == 0  # completed-with-failures != crashed
    assert all(r["attempt"] == 0
               for r in coord.fleet_log.records(type="lease", cycle=0))
    # next invocation's repair pass heals the dataset (inherited behavior)
    healed = FleetCoordinator(cfg, spawn=_inline_spawn(cfg))
    assert healed.run() == []  # all cycles complete; repair only
    keys = {(r["case_id"], r["rep"], r["seed"])
            for r in load_records(cfg.out_dir / "merged.jsonl")
            if r["status"] == "ok"}
    assert len(keys) == 6


def test_fleet_role_equals_collector_spelling(tmp_path):
    """`--role=collector` must run a collector, not a coordinator (regression:
    the light-path sniff only matched the space-separated form)."""
    out = tmp_path / "eq"
    rc = fleet_main(["--role=collector", "--campaign", "paper_concurrent",
                     "--fast", "--executor", "synthetic",
                     "--out-dir", str(out), "--cycle", "0", "--shard", "0/2",
                     "--seeds", "1000"])
    assert rc == 0
    from repro.service.fleet import collector_shard_path
    assert collector_shard_path(out, 0, 0).exists()
    assert not (out / "loop_state.jsonl").exists()  # no coordinator ran


def test_fleet_slow_case_is_not_declared_stale(tmp_path):
    """Liveness ticks keep a worker alive through a case slower than the
    heartbeat timeout (regression: per-case-only heartbeats made the
    coordinator kill healthy workers mid-long-I/O and loop on re-leases)."""
    cfg = FleetConfig(
        campaign="paper_concurrent", fast=True, cycles=1, collectors=2,
        out_dir=tmp_path / "slow", executor_kind="synthetic",
        sleep_per_case=5.0,          # one case >> heartbeat_timeout
        heartbeat_timeout_s=3.0, heartbeat_every_s=0.3,
        min_observations=99, poll_interval_s=0.05,
    )
    records = FleetCoordinator(cfg).run()
    assert records[0]["releases"] == 0  # nobody was killed as stale
    assert records[0]["n_executed"] == 2


def test_fleet_repair_uses_original_collector_count(tmp_path):
    """A fleet resumed with a different --collectors still repairs old
    cycles under the shard split they were collected with (regression:
    shards >= the new count were never scanned)."""
    cfg = _fleet_cfg(tmp_path / "resize", collectors=2, cycles=1)

    def flaky(case, ctx, seed):
        if case.id == "ff-tmpfs-raw-b32-w4":  # lands in shard 1 of 2
            raise RuntimeError("transient storage error")
        return synthetic_executor(case, ctx, seed)

    def spawn(shard, cycle, attempt):
        run_collector(cfg, cycle, shard, executor=flaky, attempt=attempt)
        return _Handle(0)

    first = FleetCoordinator(cfg, spawn=spawn).run()
    assert first[0]["n_failures"] == 1

    cfg2 = _fleet_cfg(tmp_path / "resize", collectors=1, cycles=1)
    healed = FleetCoordinator(cfg2, spawn=_inline_spawn(cfg2),
                              executor=synthetic_executor)
    assert healed.run() == []  # cycles complete; repair pass only
    ok = {(r["case_id"], r["rep"], r["seed"])
          for r in load_records(cfg.out_dir / "merged.jsonl")
          if r["status"] == "ok"}
    assert len(ok) == 6  # the shard-1 failure healed despite collectors=1


def test_fleet_gives_up_after_max_leases(tmp_path):
    """A shard that dies on every lease stops the cycle with a clear error
    instead of re-leasing forever; no cycle record is written."""
    cfg = _fleet_cfg(tmp_path / "doomed", collectors=2, cycles=1, max_leases=2)

    def spawn(shard, cycle, attempt):
        if shard == 0:
            return _Handle(1)  # dies instantly, every time
        run_collector(cfg, cycle, shard)
        return _Handle(0)

    coord = FleetCoordinator(cfg, spawn=spawn)
    with pytest.raises(RuntimeError, match="giving up"):
        coord.run()
    assert coord.state.next_cycle() == 0  # cycle not marked complete


def test_fleet_resume_between_cycles_matches_straight_run(tmp_path):
    """A coordinator killed between cycles resumes (warm-start over the
    per-host shard layout) and reaches the same decisions and bytes."""
    scfg = _fleet_cfg(tmp_path / "straight", collectors=2)
    straight = FleetCoordinator(scfg, spawn=_inline_spawn(scfg)).run()
    cfg = _fleet_cfg(tmp_path / "killed", collectors=2)
    FleetCoordinator(cfg, spawn=_inline_spawn(cfg)).run(max_cycles=1)
    rest = FleetCoordinator(cfg, spawn=_inline_spawn(cfg)).run()
    assert [r["cycle"] for r in rest] == [1]
    resumed = LoopState(cfg.out_dir / "loop_state.jsonl").cycles()
    assert len(resumed) == len(straight) == 2
    for a, b in zip(straight, resumed):
        assert _decision_view(a) == _decision_view(b)
    assert ((cfg.out_dir / "merged.jsonl").read_bytes()
            == (scfg.out_dir / "merged.jsonl").read_bytes())


# ------------------------------------------------------- real processes


def test_collector_process_never_imports_jax(tmp_path):
    """A collector process runs its shard with the real I/O executor and
    never imports JAX; its environment pins JAX to the CPU besides, so the
    coordinator stays the only process that can claim the accelerator."""
    from repro.service._coordinator import collector_env

    env = collector_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    argv = ["--role", "collector", "--campaign", "paper_concurrent", "--fast",
            "--out-dir", str(tmp_path / "c"), "--cycle", "0", "--shard", "0/2",
            "--seeds", "1000"]
    script = ("import sys\n"
              "from repro.service.fleet import main\n"
              f"rc = main({argv!r})\n"
              "assert 'jax' not in sys.modules, 'collector imported jax'\n"
              "sys.exit(rc)\n")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]


def test_fleet_kill9_subprocess_collector_recovers(tmp_path):
    """An actual ``kill -9`` of a collector *process* mid-cycle: the
    coordinator sees the death, re-leases the shard, and the merged dataset
    is byte-identical to an undisturbed 1-collector fleet run."""
    common = dict(campaign="paper_concurrent", fast=True, cycles=1,
                  seeds_per_cycle=2, min_observations=4, refit_every=4,
                  executor_kind="synthetic", poll_interval_s=0.05)
    ref_cfg = FleetConfig(out_dir=tmp_path / "ref", collectors=1, **common)
    FleetCoordinator(ref_cfg).run()

    cfg = FleetConfig(out_dir=tmp_path / "killed", collectors=2,
                      sleep_per_case=0.5, heartbeat_timeout_s=60.0, **common)
    coord = FleetCoordinator(cfg)
    killed = {}

    def killer():
        # SIGKILL shard 1's first worker as soon as its lease is logged —
        # python startup plus the per-case pacing sleep guarantees it is
        # still mid-shard (it can't even have finished importing).
        deadline = time.time() + 60
        while time.time() < deadline:
            for lease in coord.fleet_log.records(type="lease", cycle=0, shard=1):
                if lease.get("attempt") == 0 and lease.get("worker_pid"):
                    try:
                        os.kill(lease["worker_pid"], signal.SIGKILL)
                        killed["pid"] = lease["worker_pid"]
                    except ProcessLookupError:
                        pass
                    return
            time.sleep(0.02)

    t = threading.Thread(target=killer)
    t.start()
    records = coord.run()
    t.join()
    assert killed, "test harness never found a worker to kill"
    assert records[0]["releases"] >= 1
    assert ((cfg.out_dir / "merged.jsonl").read_bytes()
            == (ref_cfg.out_dir / "merged.jsonl").read_bytes())


def test_fleet_cli_end_to_end(tmp_path, capsys):
    """Coordinator CLI with real subprocess collectors: run, no-op resume,
    then --status with per-host provenance and the fleet log summary."""
    out = tmp_path / "fleet"
    args = ["--collectors", "2", "--executor", "synthetic",
            "--campaign", "paper_concurrent", "--fast", "--cycles", "1",
            "--min-observations", "4", "--refit-every", "2",
            "--out-dir", str(out)]
    assert fleet_main(args) == 0
    capsys.readouterr()
    assert fleet_main(args) == 0
    assert "already complete" in capsys.readouterr().out
    assert fleet_main(["--status", "--out-dir", str(out)]) == 0
    status = capsys.readouterr().out
    assert "per-host provenance:" in status
    assert "fleet log:" in status
    assert socket.gethostname() in status


def test_canonical_merge_success_beats_stale_error():
    """A success is never shadowed by a stale error record for the same key,
    regardless of input order (regression: after a mid-cycle --collectors
    resize, the old split's error file can sort *after* the new split's
    success file, and last-in-input-order would keep the error)."""
    from repro.data.campaign import canonical_records

    err = {"case_id": "c", "rep": 0, "seed": 1000, "status": "error",
           "row": None, "error": {"type": "RuntimeError"}}
    ok = {"case_id": "c", "rep": 0, "seed": 1000, "status": "ok",
          "row": {"target_throughput": 1.0}}
    index = {"c": 0}
    for order in ([ok, err], [err, ok]):
        [merged] = canonical_records(order, index)
        assert merged["status"] == "ok"
    # error vs error still resolves latest-wins
    err2 = dict(err, error={"type": "OSError"})
    [merged] = canonical_records([err, err2], index)
    assert merged["error"]["type"] == "OSError"


# ------------------------------------------------------- state & status


def test_loop_state_v1_migration_shim(tmp_path):
    """Pre-fleet (schema v1) loop_state.jsonl files load, resume, and render
    under the v2 readers via the upgrade shim."""
    st = LoopState(tmp_path / "state.jsonl")
    st.append({
        "schema_version": 1, "cycle": 0, "status": "ok", "campaign": "x",
        "host": "oldbox", "n_executed": 26, "n_failures": 1,
        "n_observations": 26, "n_new_rows": 26, "refit": True, "drift": None,
        "refit_s": 0.1, "recommend_s": 0.002,
        "decision": {"reconfigure": False, "explore": False,
                     "predicted_gain": 0.0, "config": {}},
        "current_config": {"num_workers": 2},
    })
    [rec] = st.cycles()
    assert rec["schema_version"] == STATE_SCHEMA_VERSION
    assert rec["collectors"] == 1 and rec["releases"] == 0
    assert rec["hosts"] == {"host_0": {"host": "oldbox", "n_executed": 26,
                                       "n_failures": 1, "releases": 0}}
    assert st.next_cycle() == 1
    assert st.current_config() == {"num_workers": 2}
    rendered = _format_status(st.cycles())
    assert "oldbox" in rendered and "per-host provenance:" in rendered


def test_loop_status_cli_shows_per_host_provenance(tmp_path, capsys):
    """Regression (PR 4 satellite): single-host --status surfaces host
    identity — fleet and single-host cycle records share one schema."""
    out = tmp_path / "cli"
    assert loop_main(["--campaign", "paper_concurrent", "--fast",
                      "--cycles", "1", "--min-observations", "4",
                      "--refit-every", "2", "--out-dir", str(out)]) == 0
    capsys.readouterr()
    assert loop_main(["--status", "--out-dir", str(out)]) == 0
    status = capsys.readouterr().out
    assert "hosts" in status  # the per-cycle collector-count column
    assert "per-host provenance:" in status
    assert f"host={socket.gethostname()}" in status
