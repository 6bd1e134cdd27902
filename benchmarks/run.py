"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV blocks (cost-model microseconds on
TPU v5e — see common.py for why structural numbers on a CPU host) plus an
inline correctness check per table.

``--json`` additionally writes one ``BENCH_<table>.json`` per table — rows,
cross-row derived metrics and the git sha — so the perf trajectory is
recorded across PRs, not just printed and lost (tools/ci.sh passes it).

``--compare <baseline>`` is the regression gate: fresh derived metrics are
checked against a committed ``BENCH_<table>.json`` and the run fails when
any metric drops more than 20% below the baseline.  Derived metrics are
higher-is-better ratios by convention (each table's ``derived_metrics``
documents this), so no per-metric direction table is needed.  Baselines
are read up front (``--json`` may overwrite the same path afterwards), and
a baseline recorded at a different ``--smoke`` setting is skipped with a
note rather than compared against mismatched shapes.  ``<baseline>`` is a
``BENCH_<table>.json`` file when one table is selected, else a directory
holding one per table.

    PYTHONPATH=src python -m benchmarks.run            # all tables
    PYTHONPATH=src python -m benchmarks.run --only gemm,mla
    PYTHONPATH=src python -m benchmarks.run --only serving --smoke --json
    PYTHONPATH=src python -m benchmarks.run --only serving --smoke \
        --compare BENCH_serving.json
"""
import argparse
import dataclasses
import inspect
import json
import pathlib
import subprocess
import sys
import time

from repro.launch import compile_cache

from . import (
    bench_attention,
    bench_dequant,
    bench_gemm,
    bench_linear_attention,
    bench_loc,
    bench_mla,
    bench_serving,
)

TABLES = {
    "gemm": bench_gemm,
    "attention": bench_attention,
    "linear_attention": bench_linear_attention,
    "dequant": bench_dequant,
    "mla": bench_mla,
    "serving": bench_serving,
    "loc": bench_loc,
}


def git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=pathlib.Path(__file__).resolve().parent.parent, check=True,
        ).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def _jsonable(row):
    if dataclasses.is_dataclass(row):
        return dataclasses.asdict(row)
    return row


def write_json(name: str, rows, derived=None, out_dir=".",
               smoke: bool = False) -> pathlib.Path:
    """Write ``BENCH_<name>.json``: rows + derived metrics + git sha.

    ``smoke`` is recorded in the payload so trajectory comparisons never
    silently mix smoke-shape and full-shape numbers."""
    path = pathlib.Path(out_dir) / f"BENCH_{name}.json"
    payload = {
        "table": name,
        "git_sha": git_sha(),
        "smoke": smoke,
        "rows": [_jsonable(r) for r in rows],
        "derived": derived or {},
    }
    path.write_text(json.dumps(payload, indent=2, default=str) + "\n")
    print(f"# wrote {path}")
    return path


REGRESSION_THRESHOLD = 0.2  # fail when a metric drops >20% vs baseline


def load_baselines(arg: str, names) -> dict:
    """Map table name -> committed baseline payload.  Read eagerly so a
    later ``--json`` overwrite of the same path cannot corrupt the gate.
    A missing path is a hard error: a typo'd or renamed baseline must not
    silently disable the regression gate."""
    p = pathlib.Path(arg)
    if not p.exists():
        raise SystemExit(f"--compare baseline {arg!r} does not exist")
    if p.is_file() and len(names) > 1:
        raise SystemExit(
            "--compare got a single file but multiple tables are "
            "selected; pass a directory of BENCH_<table>.json files"
        )
    out = {}
    for name in names:
        path = p if p.is_file() else p / f"BENCH_{name}.json"
        if path.is_file():
            out[name] = json.loads(path.read_text())
        else:
            print(f"# compare[{name}]: no baseline at {path}; skipping")
    return out


def compare_derived(name: str, current: dict, baseline: dict,
                    smoke: bool) -> list:
    """Regression check for one table; returns failure strings.  Every
    derived metric is a higher-is-better ratio by convention."""
    if bool(baseline.get("smoke")) != smoke:
        print(f"# compare[{name}]: baseline smoke={baseline.get('smoke')} "
              f"!= current smoke={smoke}; shapes differ, skipping gate")
        return []
    failures = []
    for k, base in (baseline.get("derived") or {}).items():
        if not isinstance(base, (int, float)):
            continue
        cur = current.get(k)
        if not isinstance(cur, (int, float)):
            # a vanished metric must not silently defeat the gate: renaming
            # or dropping a tracked metric requires updating the baseline
            failures.append(
                f"{name}.{k}: missing from current run (baseline {base} @ "
                f"{baseline.get('git_sha', '?')[:12]})"
            )
            continue
        floor = base * (1.0 - REGRESSION_THRESHOLD)
        if base > 0 and cur < floor:
            failures.append(
                f"{name}.{k}: {cur} < {floor:.3f} "
                f"(baseline {base} @ {baseline.get('git_sha', '?')[:12]})"
            )
        else:
            print(f"# compare[{name}]: {k} = {cur} vs baseline {base}: ok")
    # metrics introduced after the baseline was recorded pass trivially
    # this run (nothing to gate against) — name them so the trajectory
    # shows they become gated once the baseline is regenerated
    for k in sorted(set(current) - set(baseline.get("derived") or {})):
        print(f"# compare[{name}]: {k} = {current[k]} is new "
              "(no baseline; gated after the next baseline refresh)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(TABLES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes where a table supports it")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<table>.json per table")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="BENCH_<table>.json (or a directory of them) to "
                         "gate derived metrics against; >20% regression "
                         "fails the run")
    args = ap.parse_args()
    compile_cache.enable()
    names = args.only.split(",") if args.only else list(TABLES)
    baselines = load_baselines(args.compare, names) if args.compare else {}
    t0 = time.time()
    total_rows = 0
    failures = []
    for name in names:
        mod = TABLES[name]
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        rows = mod.run(**kwargs)
        derive = getattr(mod, "derived_metrics", None)
        derived = derive(rows) if derive else {}
        if name in baselines:
            failures += compare_derived(
                name, derived, baselines[name], bool(kwargs.get("smoke"))
            )
        if args.json:
            write_json(name, rows, derived, smoke=bool(kwargs.get("smoke")))
        total_rows += len(rows)
    print(f"# benchmarks complete: {total_rows} rows in {time.time()-t0:.1f}s")
    if failures:
        for f in failures:
            print(f"# REGRESSION: {f}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
