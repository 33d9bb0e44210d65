"""Readings that set the benchmark's rates and limits, made on the chip in
one process; the benchmark's own runs never run this.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 [--control]
    python3 chipbench/calibrate.py --workload <cell> --seeds 1 --rates 100,200,400

The cell is one of ``BENCHMARK.json`` or of ``chipbench/pending/``.  For
each seed (and rate, for an open-loop cell) it runs the cell's driver
and prints one JSON line: the end-to-end metrics, the numbers compared for
``correct`` and, with ``--control``, the same numbers for the bfloat16
control answering the same requests.  With ``--rates`` it also prints the
latency percentiles, the share of requests within the traffic file's
``latency_limit_ms`` (or within each multiple of 5 ms up to 2 s, the
limits the knee can ask for) and the mean latency of the window's first and last
fifths, and last the knee (``knee``).  ``--dump DIR`` writes the reduced
trace of a traced run, a summary of the raw trace's planes and the part of
the raw trace that ``trace_reduce`` reads, as a text proto, there.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import run as harness  # noqa: E402


def _latency_summary(lat, limit_ms):
    xs = sorted(lat)
    n = len(xs)

    def pct(q):
        v = xs[max(0, math.ceil(q * n) - 1)]
        return v * 1e3 if math.isfinite(v) else None

    fifth = max(1, n // 5)
    head = [v for v in lat[:fifth] if math.isfinite(v)]
    tail = [v for v in lat[-fifth:] if math.isfinite(v)]
    return {"n": n, "p50_ms": pct(0.5), "p95_ms": pct(0.95), "p99_ms": pct(0.99),
            "within_ms": {str(m): sum(v * 1e3 <= m for v in lat) / n
                          for m in ([limit_ms] if limit_ms else range(5, 2005, 5))},
            "first_fifth_mean_ms": 1e3 * sum(head) / max(1, len(head)),
            "last_fifth_mean_ms": 1e3 * sum(tail) / max(1, len(tail))}


def with_pending(root: pathlib.Path = ROOT) -> dict:
    """``BENCHMARK.json`` with the cells of ``chipbench/pending/`` added:
    cells whose rate or limits wait for these readings."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for f in sorted((root / "chipbench" / "pending").glob("*.json")):
        cell = json.loads(f.read_text())
        bench["workloads"].append(cell["workload"])
        bench["end_to_end"] += cell["end_to_end"]
        bench["per_layer"] += cell["per_layer"]
    return bench


def knee(sweep: list) -> dict:
    """The knee of a rate sweep (lines in rising rate, each with its
    ``latency`` summary): the latency limit is 4 x the lowest rate's p95,
    rounded up to 5 ms; the knee is the highest rate up to which every rate
    has 90% of requests within it and no growing backlog (the last fifth's
    mean latency at most twice the first's plus 2 ms); the steady rate is
    0.8 x the knee."""
    limit = 5 * math.ceil(4 * sweep[0]["latency"]["p95_ms"] / 5)
    best = None
    for line in sweep:
        lat = line["latency"]
        within = lat["within_ms"].get(str(limit), 0.0)
        if within < 0.9 or lat["last_fifth_mean_ms"] > 2 * lat["first_fifth_mean_ms"] + 2:
            break
        best = line["rate_rps"]
    return {"latency_limit_ms": float(limit), "knee_rps": best,
            "rate_rps": None if best is None else round(0.8 * best)}


def _text_proto(pd) -> str:
    """The planes, lines and events that ``trace_reduce`` reads, as an
    XSpace text proto (times in picoseconds from the trace's first event)."""
    from chipbench import trace_reduce

    base = min((e.start_ns for p in pd.planes for line in p.lines for e in line.events),
               default=0.0)
    out = []
    for pid, plane in enumerate(pd.planes, 1):
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        if not (device or plane.name.startswith("/host:")):
            continue
        meta, lines = {}, []
        for lid, line in enumerate(plane.lines, 1):
            if device and line.name not in (trace_reduce.OPS_LINE,
                                            trace_reduce.PROGRAMS_LINE):
                continue
            evs = [e for e in line.events
                   if device or e.name.startswith(trace_reduce.SPAN_PREFIX)]
            if not evs:
                continue
            body = " ".join(
                f"events {{ metadata_id: {meta.setdefault(e.name, len(meta) + 1)} "
                f"offset_ps: {round((e.start_ns - base) * 1000)} "
                f"duration_ps: {round((e.end_ns - e.start_ns) * 1000)} }}" for e in evs)
            lines.append(f'  lines {{ id: {lid} name: {json.dumps(line.name)} '
                         f'timestamp_ns: 0 {body} }}')
        if lines:
            md = "\n".join(f"  event_metadata {{ key: {i} value {{ id: {i} "
                           f"name: {json.dumps(n)} }} }}" for n, i in meta.items())
            out.append(f'planes {{\n  id: {pid} name: {json.dumps(plane.name)}\n'
                       + "\n".join(lines) + "\n" + md + "\n}")
    return "\n".join(out) + "\n"


def _dump(out: pathlib.Path, trace) -> None:
    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    out.mkdir(parents=True, exist_ok=True)
    trace_reduce.save_json(trace, out / "trace_reduced.json")
    files = sorted((harness.WORK_DIR / "trace").rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    summary = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            summary.append({"plane": plane.name, "line": line.name, "events": len(evs),
                            "first": [evs[0].start_ns, evs[0].end_ns] if evs else None,
                            "names": names[:40]})
    (out / "trace_summary.json").write_text(json.dumps(summary, indent=1))
    (out / "trace.textproto").write_text(_text_proto(pd))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", type=pathlib.Path)
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload, bench=with_pending())
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("[calibrate] needs a TPU", file=sys.stderr)
        return 2
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    t0 = harness.T0
    sweep = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        for rate in rates:
            if rate is not None:
                cell.traffic = dict(cell.traffic, rate_rps=rate)
            run = harness.Run(seed=seed, seconds=args.seconds, trace=bool(args.trace), t0=t0,
                              tracer=harness.Tracer(harness.WORK_DIR / "trace")
                              if args.trace else None, control=args.control)
            record = cell.driver.run(cell, run)
            result = harness.finish(cell, record, bool(args.trace),
                                    harness._device_info(devices, record["memory_peak_bytes"]))
            line = {"seed": seed, "rate_rps": rate, "correct": result["correct"],
                    "attempted": result["attempted"], "failed": result["failed"],
                    "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                    "checks": {k: v["value"] for k, v in result["checks"].items()},
                    "control": record.get("control_checks"),
                    "memory_peak_bytes": record["memory_peak_bytes"],
                    "errors": record.get("errors")}
            if "latencies_s" in record:
                line["latency"] = _latency_summary(record["latencies_s"],
                                                   cell.traffic.get("latency_limit_ms"))
            if args.trace:
                line["device"] = result["device"]
                line["breakdown"] = result.get("breakdown")
                if args.dump and "trace" in record:
                    _dump(args.dump / f"{args.workload}_{seed}", record["trace"])
            print(json.dumps(line), flush=True)
            sweep.append(line)
            t0 = time.monotonic()
    if args.rates and "latency" in sweep[0]:
        print(json.dumps({"knee": knee(sweep)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
