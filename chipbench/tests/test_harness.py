"""The harness finds everything by name, and the schedules are seeded."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import run as harness
from chipbench import schedule

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]]
                         + [p.stem for p in (ROOT / "chipbench" / "pending").glob("*.json")])
def test_every_workload_resolves_its_files(workload, bench):
    cell = harness.resolve(workload, bench=bench)
    assert callable(cell.driver.run)
    assert cell.config["name"] == next(w["config"] for w in bench["workloads"]
                                       if w["name"] == workload)
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert "setup_s" in names and set(cell.readers) == names
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    moved = {m["moves"] for m in cell.per_layer}
    assert moved <= {m["name"] for m in cell.end_to_end}


def test_every_config_and_metric_has_a_file():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()


def test_a_traffic_file_and_an_entry_add_a_cell(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads((ROOT / "chipbench/traffic/serve-paper-steady.json").read_text())
    traffic["rate_rps"] *= 1.25
    (tmp_path / "chipbench/traffic/serve-paper-new.json").write_text(json.dumps(traffic))
    bench["workloads"].append({"name": "serve-paper-new", "config": "xgboost-paper",
                               "traffic": "serve-paper-new", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve("serve-paper-new", root=tmp_path)
    assert cell.traffic["rate_rps"] == traffic["rate_rps"]
    # a metric without a workloads key reaches the new cell too
    assert "setup_s" in cell.readers


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.resolve("no-such-cell")


def test_schedules_repeat_for_a_seed_and_differ_across_seeds():
    big = 2**31 + 12345
    for draw in (lambda s: np.diff(np.append(schedule.poisson_arrivals(300.0, 5.0, s), 5.0)),
                 lambda s: schedule.zipf_ranks(1000, 2000, 1.1, s),
                 lambda s: schedule.uniform_indices(500, 1800, s, "configs")):
        a, b, c = draw(big), draw(big), draw(big + 1)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)
        # the same multiset (of gaps, ranks, configurations) in another order
        assert np.allclose(np.sort(a), np.sort(c), rtol=1e-9, atol=1e-12)
    t1, w1 = schedule.tenant_contexts({"a": range(50), "b": range(50)}, 2000, 200, big)
    t2, _ = schedule.tenant_contexts({"a": range(50), "b": range(50)}, 2000, 200, big)
    t3, _ = schedule.tenant_contexts({"a": range(50), "b": range(50)}, 2000, 200, big + 1)
    assert t1 == t2 and t1 != t3
    assert not {tuple(c.items()) for c in t1} & {tuple(c.items()) for c in w1}
    assert schedule.log_uniform_contexts({"x": (1.0, 10.0)}, 8, big) == \
        schedule.log_uniform_contexts({"x": (1.0, 10.0)}, 8, big)


def test_observations_repeat_for_a_seed_and_share_their_contexts_across_seeds():
    from chipbench.observations import TARGET, observations

    knobs = {"batch_size": [16, 64], "num_workers": [0, 4], "block_kb": [4, 1024]}
    axes = {"file_size_mb": [1.0, 4096.0], "throughput_mb_s": [50.0, 5000.0]}
    a, b = observations(knobs, axes, 3, 7), observations(knobs, axes, 3, 8)
    assert a == observations(knobs, axes, 3, 7) and a != b and len(a) == 24
    for k in axes:
        assert sorted(r[k] for r in a) == sorted(r[k] for r in b)
        assert all(axes[k][0] <= r[k] <= axes[k][1] for r in a)
    assert all(r[TARGET] > 0 for r in a)


def test_arrivals_are_a_fixed_count_in_the_window():
    for seed in (1, 2, 3):
        t = schedule.poisson_arrivals(400.0, 10.0, seed)
        assert len(t) == 4000 and t[0] == 0.0 and t[-1] < 10.0
        assert np.all(np.diff(t) > 0)


def test_mix_counts_are_exact():
    kinds = schedule.mix(1001, {"predict": 0.7, "recommend": 0.3}, 5)
    assert kinds.count("predict") == 701 and kinds.count("recommend") == 300


def test_serve_requests_are_seeded_and_warmup_tenants_stay_out_of_the_window(bench):
    from chipbench.drivers import serve

    cell = harness.resolve("serve-paper-steady", bench=bench)
    knobs = cell.config["grid_paper"]
    a = serve.build_requests(cell.traffic, knobs, 2.0, 99)
    assert a == serve.build_requests(cell.traffic, knobs, 2.0, 99)
    assert a != serve.build_requests(cell.traffic, knobs, 2.0, 100)
    ctx = {p: {json.dumps(json.loads(b)["context"], sort_keys=True) for ph, _, _, b in a
               if ph == p} for p in ("warmup", "window")}
    assert ctx["warmup"] and ctx["window"] and not ctx["warmup"] & ctx["window"]


def test_knee_of_a_sweep_by_hand():
    from chipbench.calibrate import knee

    def line(rate, within, first, last):
        return {"rate_rps": rate, "latency": {"p95_ms": 2.6, "first_fifth_mean_ms": first,
                                              "last_fifth_mean_ms": last,
                                              "within_ms": {"15": within}}}

    # limit: 4 x 2.6 ms rounded up to 15 ms; 400 has a growing backlog
    sweep = [line(25, 1.0, 2, 2), line(200, 0.99, 3, 4), line(300, 0.95, 5, 9),
             line(400, 0.93, 6, 30), line(500, 0.5, 40, 90)]
    assert knee(sweep) == {"latency_limit_ms": 15.0, "knee_rps": 300, "rate_rps": 240}
    assert knee([line(25, 0.5, 2, 2)])["knee_rps"] is None


def test_latency_summary_holds_the_limit_the_knee_asks_for():
    from chipbench.calibrate import _latency_summary, knee

    # a lone request's p95 of 58 ms asks for a limit of 235 ms
    lat = [0.058] + [0.004] * 18 + [0.058]
    line = {"rate_rps": 20, "latency": _latency_summary(lat, None)}
    assert line["latency"]["within_ms"]["235"] == 1.0
    assert knee([line])["knee_rps"] == 20


def test_median_call_is_read_in_ms_and_a_run_without_calls_reads_nothing():
    read = harness.load_module(ROOT / "chipbench/metrics/recommend_call_ms_p50.py",
                               "median_call").read
    assert read({"call_s": [0.28, 1.7, 0.3]}) == 300.0
    assert read({}) is None


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cmd in (["chipbench/run.py"], ["-m", "chipbench.run"]):
        p = subprocess.run([sys.executable, *cmd, "--workload", BENCH["workloads"][0]["name"],
                            "--seed", "1", "--seconds", "1", "--trace", "0"],
                           cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
        assert p.returncode != 0
        assert "metrics" not in p.stdout and "correct" not in p.stdout
