"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (the harness contract).  Derived is
modeled Mops (throughput figures), bits/key (memory figures), or a
figure-specific annotation.  EXPERIMENTS.md §Paper-validation interprets the
ratios against the paper's claims.

Suites may attach a 4th row element (a dict of extras, e.g. the simulated
latency percentiles from ``benchmarks.net_bench`` and the exact
``repro.api.StoreSpec`` the row's store was opened from); it never reaches
the CSV, but ``--json PATH`` persists it — that file is the perf-trajectory
contract (``BENCH_*.json``) future PRs diff against.  Every suite builds
its stores exclusively through ``repro.api.open_store``, so the JSON also
records the registry the run saw.

Exits non-zero when any suite produced an ``<suite>/ERROR`` row.

Run:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only lat,scale]
      [--json out.json]
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time


def _mod(name: str):
    """Deferred import of one benchmarks submodule (keeps ``--help`` and
    filtered runs from importing every suite's dependencies)."""
    return importlib.import_module(f"benchmarks.{name}")


# The single source of truth for the suite registry: every entry derives
# BOTH the execution loop and the ``--only`` help text, so a new suite
# cannot be runnable-but-undocumented (or vice versa).  Each builder takes
# (args, n) — n is the ``--quick``-scaled key count — and returns rows.
SUITES = (
    ("fig3", "",
     lambda a, n: _mod("paper_figs").fig3_motivation(min(n, 200_000))),
    ("fig9", "",
     lambda a, n: _mod("paper_figs").fig9_10_ycsb(n)),
    ("fig11", "",
     lambda a, n: _mod("paper_figs").fig11_sosd(n)),
    ("fig12", "",
     lambda a, n: _mod("paper_figs").fig12_mn_threads(n)),
    ("fig14", "",
     lambda a, n: _mod("paper_figs").fig14_load_factor(min(n, 200_000))),
    ("fig15", "",
     lambda a, n: _mod("paper_figs").fig15_num_pairs(
         (50_000, 100_000, 200_000) if a.quick
         else (200_000, 500_000, 800_000))),
    ("fig16", "",
     lambda a, n: _mod("paper_figs").fig16_cn_memory(
         (100_000, 200_000) if a.quick
         else (200_000, 1_000_000, 2_000_000))),
    ("fig17", "",
     lambda a, n: _mod("paper_figs").fig17_resize(min(n, 150_000))),
    ("zipf", "CN hot-key cache on/off across skew",
     lambda a, n: _mod("paper_figs").zipf_cache(min(n, 200_000))),
    ("lat", "simulated Get latency percentiles",
     lambda a, n: _mod("net_bench").lat_suite(a.quick)),
    ("scale", "simulated closed-loop throughput vs clients + resize dip",
     lambda a, n: _mod("net_bench").scale_suite(a.quick)),
    ("ycsb", "pipelined vs hand-batched vs scalar write mixes, "
             "BatchPolicy window sweep + Ludo build/resize-rebuild "
             "microbench",
     lambda a, n: _mod("ycsb_bench").ycsb_suite(a.quick,
                                                window=a.ycsb_window)),
    ("faults", "K=2 crash/failover: p999 through a seeded MN crash, "
               "availability curve, zero lost acked writes, dormant-plane "
               "meter identity",
     lambda a, n: _mod("faults_bench").faults_suite(a.quick)),
    ("obs", "telemetry plane: ycsb-C overhead with the hub on vs off, "
            "dormant byte-identity, span/snapshot cadence, "
            "outback-telemetry/v1 JSONL + Perfetto exports",
     lambda a, n: _mod("obs_bench").obs_suite(a.quick)),
    ("cluster", "multi-CN plane: aggregate Mops scaling across CNs at "
                "zipf(0.9), join/leave handoff O(shards moved), "
                "reconfiguration dip, zero lost acked writes through a "
                "leave, dormant single-CN byte-identity",
     lambda a, n: _mod("cluster_bench").cluster_suite(a.quick)),
    ("chaos", "partition-tolerant plane: full-cut partition with fenced "
              "lease arbitration and post-heal convergence, seeded chaos "
              "runs (zero lost/split-brain acked writes, linearizable "
              "reads, availability floor), bit-identical determinism, "
              "per-shard HRW resync savings, dormant-plane identity",
     lambda a, n: _mod("chaos_bench").chaos_suite(a.quick)),
    ("slo", "serving front door under open-loop multi-tenant load: "
            "goodput-vs-offered-load knee, p999 at 2x-knee overload with "
            "admission on/off, singleflight savings at zipf(0.99), "
            "per-tenant isolation, zero lost acked writes, dormant "
            "ingress identity (outback-slo/v1 rows)",
     lambda a, n: _mod("slo_bench").slo_suite(a.quick)),
    ("kernel_paged", "",
     lambda a, n: _mod("kernel_bench").paged_attention_traffic()),
    ("kernel_lookup", "",
     lambda a, n: _mod("kernel_bench").ludo_lookup_throughput()),
    ("kernel_pagetable", "",
     lambda a, n: _mod("kernel_bench").page_table_memory()),
)


def _only_help() -> str:
    parts = [f"{name} ({blurb})" if blurb else name
             for name, blurb, _fn in SUITES]
    return ("comma-separated substring filters over suite names: "
            + ", ".join(parts))


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Outback paper-figure reproductions + extensions.")
    ap.add_argument("--quick", action="store_true",
                    help="smaller key sets (CI-speed)")
    ap.add_argument("--only", default=None, help=_only_help())
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows (with extras such as latency "
                         "percentiles) as machine-readable JSON")
    ap.add_argument("--ycsb-window", type=int, default=None, metavar="N",
                    help="override the ycsb suite's BatchPolicy doorbell "
                         "window (default: the store policy's 1024)")
    args = ap.parse_args()

    from benchmarks.common import emit
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    n = 100_000 if args.quick else 300_000
    suites = [(name, lambda fn=fn: fn(args, n)) for name, _b, fn in SUITES]
    only = [t.strip() for t in args.only.split(",")] if args.only else None
    rows = []
    suite_seconds: dict[str, float] = {}
    for name, fn in suites:
        if only and not any(t and t in name for t in only):
            continue
        t0 = time.time()
        try:
            rows.extend(fn())
        except Exception as e:  # keep the harness running; report the miss
            rows.append((f"{name}/ERROR", 0.0, repr(e)[:80]))
        suite_seconds[name] = round(time.time() - t0, 3)
        print(f"# {name} done in {suite_seconds[name]:.1f}s", file=sys.stderr)
    emit([r[:3] for r in rows])

    if args.json:
        from repro.api import registered_kinds
        payload = {"quick": bool(args.quick),
                   "registry": {"kinds": list(registered_kinds())},
                   "suite_seconds": suite_seconds,  # perf trajectory anchor
                   "rows": [dict(suite=r[0].split("/")[0], name=r[0],
                                 us_per_call=r[1], derived=r[2],
                                 **(r[3] if len(r) > 3 else {}))
                            for r in rows]}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json} ({len(payload['rows'])} rows)",
              file=sys.stderr)

    errors = [r[0] for r in rows if "/ERROR" in r[0]]
    if errors:
        print(f"# {len(errors)} ERROR row(s): {', '.join(errors)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
