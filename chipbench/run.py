"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything is found by name from
``BENCHMARK.json``: the cell's configuration (``chipbench/configs/<config>.json``),
its traffic (``chipbench/traffic/<traffic>.json``), the driver the traffic
names (``chipbench/drivers/<driver>.py``) and one reader per metric
(``chipbench/metrics/<metric>.py``).  The driver sets up, warms up, measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and returns a record; the readers reduce it to metrics.  With
``--trace 0`` the last line of stdout carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics from a traced slice of the window.
The numbers compared for ``correct`` end stderr and the result line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
WORK_DIR = ROOT / ".chipbench"  # traces and run files, never committed


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: pathlib.Path = ROOT, bench: Optional[dict] = None) -> Cell:
    """The cell ``workload`` of ``bench`` (by default ``root/BENCHMARK.json``),
    with its files under ``root``."""
    if bench is None:
        bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    pkg = root / "chipbench"
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((pkg / "traffic" / f"{w['traffic']}.json").read_text())
    driver = load_module(pkg / "drivers" / f"{traffic['driver']}.py",
                         f"chipbench_driver_{traffic['driver']}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    readers = {m["name"]: load_module(pkg / "metrics" / f"{m['name']}.py",
                                      f"chipbench_metric_{m['name']}").read
               for m in e2e + layer}
    return Cell(workload, int(w["chips"]), config, traffic, driver, e2e, layer, readers)


class Tracer:
    """One profiler slice of the window, with the benchmark's window span."""

    def __init__(self, directory: pathlib.Path):
        self.directory = directory
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        self.directory.mkdir(parents=True)
        jax.profiler.start_trace(str(self.directory))
        self._span = jax.profiler.TraceAnnotation("chipbench.window")
        self._span.__enter__()

    def stop(self):
        import jax

        from chipbench import trace_reduce

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return trace_reduce.load(self.directory)


@dataclasses.dataclass
class Run:
    """What a driver gets for one run."""

    seed: int
    seconds: float
    trace: bool
    t0: float = T0
    tracer: Optional[Tracer] = None
    control: bool = False  # also read the bfloat16 control (calibration only)

    def memory_peak_bytes(self) -> int:
        import jax

        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())


def finish(cell: Cell, record: dict, trace: bool, device: dict) -> dict:
    """The result line of a run from the driver's record."""
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.readers[m["name"]](record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = record["checks"]
    correct = (not record.get("errors")
               and all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()))
    out = {"correct": bool(correct), "attempted": int(record["attempted"]),
           "failed": int(record["failed"]), "metrics": metrics, "device": device}
    if trace and "trace" in record:
        from chipbench import trace_reduce

        tr = record["trace"]
        out["device"] = {**device, "busy_s": (trace_reduce.busy_ns(tr) or 0.0) / 1e9,
                         "window_s": tr.window_ns / 1e9}
        out["breakdown"] = trace_reduce.breakdown(tr)
    out["checks"] = checks
    return out


def _device_info(devices, peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"[chipbench] needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    run = Run(seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              tracer=Tracer(WORK_DIR / "trace") if args.trace else None)
    record = cell.driver.run(cell, run)
    result = finish(cell, record, bool(args.trace),
                    _device_info(devices, record["memory_peak_bytes"]))
    for err in record.get("errors", []):
        print(f"[chipbench] error: {err}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    sys.exit(main())
