"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived,backend,rows_self,rows_intra,rows_inter``
CSV rows (value column is the figure's metric: imbalance ratio / speedup /
us, per the row name; the backend column tags rows measured under a
specific exchange transport — ``-`` for backend-independent rows; the three
trailing per-distance-class columns split a row's exchanged rows by lane
locality — self / intra-host / inter-host, blank for rows with no class
split).  Modules return 3-tuples ``(name, value, derived)``, 4-tuples
``(..., backend)``, or 5-tuples ``(..., backend, (self, intra, inter))``.

    python -m benchmarks.run [only] [--smoke] [--out bench.csv]

``only`` filters modules by substring.  ``--smoke`` runs each module's
small-N profile (its module-level ``SMOKE`` kwargs) — the CI gate profile;
the streaming + migration modules sweep the dense *and* ragged exchange
backends and raise (nonzero exit) on any exact-count mismatch between them.
``--out`` additionally writes the CSV rows to a file (CI artifact).

A module that raises prints a ``<name>/FAILED`` row *and* makes the process
exit nonzero, so failures gate CI instead of hiding in the CSV.
"""
from __future__ import annotations

import argparse
import sys
import time


MODULES = [
    "bench_partitioners",   # Fig 2
    "bench_migration",      # Fig 3
    "bench_spark_like",     # Fig 4
    "bench_overpartition",  # Fig 5
    "bench_streaming",      # Fig 6
    "bench_webcrawl",       # Fig 7/8
    "bench_sketches",       # §4 + extended paper
    "bench_moe",            # beyond paper: KIP expert placement
    "bench_kernels",        # Pallas hot paths
]


def main(argv: list[str] | None = None) -> int:
    import importlib

    ap = argparse.ArgumentParser(description="paper benchmark harness")
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on module names")
    ap.add_argument("--smoke", action="store_true",
                    help="small-N profile (each module's SMOKE kwargs)")
    ap.add_argument("--out", default=None,
                    help="also write the CSV rows to this file")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    lines: list[str] = []

    def emit(line: str) -> None:
        lines.append(line)
        print(line)

    emit("name,us_per_call,derived,backend,rows_self,rows_intra,rows_inter")
    failures: list[tuple[str, BaseException]] = []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            kwargs = getattr(mod, "SMOKE", {}) if args.smoke else {}
            rows = mod.run(**kwargs)
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            emit(f"{name}/FAILED,0,{type(e).__name__}: {e},-,,,")
            continue
        for row in rows:
            row_name, value, derived = row[:3]
            backend = row[3] if len(row) > 3 else "-"
            by_class = row[4] if len(row) > 4 else ("", "", "")
            cls = ",".join(str(c) for c in by_class)
            emit(f"{row_name},{value:.6g},{derived},{backend},{cls}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    if failures:
        for name, e in failures:
            print(f"FAILED {name}: {type(e).__name__}: {e}", file=sys.stderr)
        print(f"{len(failures)} benchmark module(s) failed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
