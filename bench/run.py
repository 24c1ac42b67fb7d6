"""Chip benchmark of the Outback KVS index: one run of one cell.

Run from the root of a checkout, on a machine whose JAX sees a TPU:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is the file that entry's config names, its traffic mix is
``bench/traffic/<traffic>.json``, and each metric is read by
``bench/metrics/<metric>.py``, where a metric split by the kind of cell
that reports it (``get_p95_ms.cn_cache``) is read by the reader of the
name before its first dot.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of the
window.  The last line of standard output is one JSON object; the numbers
that decide ``correct`` are the last lines of standard error and the last
key of that object.  Without a TPU, or with fewer chips than the cell asks
for, or without the program beside the benchmark, it exits non-zero and
prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from lib import peaks, runner, trace as trace_lib  # noqa: E402


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, the cell's entry, its configuration, its mix)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, run):
    reader = name.split(".", 1)[0]
    path = BENCH / "metrics" / f"{reader}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{reader}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def checks(run) -> dict:
    """Each number compared, with its limit: answers that differ from the
    reference, per op kind (exact, so the limit is 0)."""
    return {f"{op}_wrong": {"value": n, "limit": 0, "of": run.compared[op]}
            for op, n in run.wrong.items()}


def breakdown(run) -> dict | None:
    window = run.traced_window()
    if window is None or not run.busy:
        return None
    a, b = window
    return {"device_ops": [[n, s] for n, s in trace_lib.top_ops(run.trace,
                                                                 a, b)],
            "idle_gaps": [[n, s] for n, s in trace_lib.top_gaps(
                run.trace, run.busy[0], a, b)]}


def require_chip(chips: int):
    """The devices of a TPU machine with at least ``chips`` chips."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    runner.log(f"device platform={dev.platform} kind={dev.device_kind} "
               f"count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is {dev.platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def result(run, bench: dict, cell: str, trace: bool, devices) -> dict:
    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks(run).values()),
           "attempted": run.ops(), "failed": sum(run.wrong.values()),
           "metrics": metrics, "device": device}
    window = run.traced_window()
    if trace and window is not None:
        device["window_s"] = window[1] - window[0]
        device["busy_s"] = trace_lib.mean_covered(run.busy, [window])
        bd = breakdown(run)
        if bd is not None:
            out["breakdown"] = bd
    out["checks"] = checks(run)
    return out


def parse(argv=None, description: str = __doc__.splitlines()[0]):
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_and_report(args, open_store=None) -> int:
    """One run of ``args.workload``; prints the result line."""
    bench, cell, config, traffic = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: no program at {ROOT / 'src' / 'repro'}")
    sys.path.insert(0, str(ROOT / "src"))
    devices = require_chip(int(cell["chips"]))
    from repro.compile_cache import enable_compile_cache
    runner.log(f"compile_cache={enable_compile_cache()}")

    run = runner.run_cell(args.workload, config, traffic, args.seed,
                          args.seconds, bool(args.trace),
                          t_process=T_PROCESS,
                          peaks=peaks.peaks(devices[0].device_kind),
                          open_store=open_store)
    out = result(run, bench, args.workload, bool(args.trace), devices)
    for name, c in out["checks"].items():
        runner.log(f"{name} {c['value']} limit {c['limit']} "
                   f"(of {c['of']} answers)")
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    return run_and_report(parse(argv))


if __name__ == "__main__":
    sys.exit(main())
