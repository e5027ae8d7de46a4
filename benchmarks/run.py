import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Figures covered:
  Fig. 5  pattern-dependent reduction      (fig5_patterns)
  Fig. 7  strong scaling 2->128 procs      (fig7_scaling, modeled)
  Fig. 8  volume reductions (joint, hier)  (fig8_volume)
  Fig. 9  communication balance            (fig9_balance)
  Fig. 10 step-wise ablation, MEASURED     (fig10_ablation)
  Fig. 11 dense-column sensitivity         (fig11_ncols)
  Tab. 3  GNN case study + prep overhead   (table3_gnn)
  extra   SHIRO MoE dispatch (beyond-paper) (moe_dispatch)
  extra   bucketed-schedule padding sweep   (sched_buckets)
  extra   fused GAT attention (SDDMM+SpMM)  (gat_attention)
  extra   multi-tenant fleet placement      (fleet_serving)

Flags:
  --only MODULE   run a subset (repeatable; short names, e.g.
                  ``--only fig8_volume --only sched_buckets``)
  --json PATH     additionally write machine-readable BENCH records:
                  every CSV row becomes {"bench", "us_per_call", fields
                  parsed from the key=value derived string} — the format
                  CI diffs across PRs to catch schedule regressions.
                  Handle-driven benchmarks (fig10_ablation, fig11_ncols,
                  moe_dispatch) put the compile_spmm autotune decisions
                  (strategy, schedule kind, K, overlap, backend) in the
                  derived string, so every BENCH record carries what the
                  front door decided for that matrix.
  --compare PATH  regression GATE: compare this run's records against a
                  committed baseline (same --json format) and FAIL when
                  any deterministic field (padded_rows / modeled_time /
                  total_allocation_size, the last only under the
                  baseline's recorded jax version) exceeds
                  baseline · (1 + --tolerance), when a baseline record
                  is missing from this run (each missing record is
                  named), or when the baseline itself carries no usable
                  records.
  --tolerance F   relative slack for --compare (default 0.05).
  --family-timeout SECONDS
                  wall-clock bound per benchmark family (default: the
                  REPRO_BENCH_FAMILY_TIMEOUT env var, else unbounded). A
                  family still running when the bound expires is
                  abandoned: its partial rows ship plus one record with
                  an "error" field naming the timeout, and the harness
                  exits 2 — a hung family can no longer hang CI.

Exit codes (so CI can tell "regressed" from "crashed"):
  0  all benchmarks ran; no gate violation
  1  gate violation (--compare found regressions / missing records)
  2  a benchmark family raised mid-sweep or exceeded --family-timeout —
     its partial rows are still emitted, plus one record carrying an
     "error" field
"""
import argparse
import json
import sys
import threading
import traceback

EXIT_REGRESSED = 1
EXIT_CRASHED = 2

# deterministic outputs the --compare gate checks (wall times vary run
# to run and are tracked, not gated). total_allocation_size is an XLA
# property of the compiled executable — deterministic per jax version,
# so it is only gated when the baseline record's "jax" stamp matches
# the running version (see compare_records). crossover_p is the modeled
# 1.5D scaling crossover (fig7_scaling): a LARGER value means the
# replicated tier stopped winning until later (or at all) — a strategy
# regression, gated like the others. migrations (fleet_serving) counts
# rebalance moves for a pinned tenant set: a fleet migrating MORE than
# baseline means the placement policy stopped landing tenants well.
GATE_FIELDS = ("padded_rows", "modeled_time", "total_allocation_size",
               "crossover_p", "migrations")


def _jax_version() -> str:
    import jax

    return jax.__version__


def _parse_derived(derived: str) -> dict:
    """'k1=v1;k2=v2' -> {k1: v1, ...} with numeric coercion."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        v = v.rstrip("%")
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def _records(rows) -> list:
    recs = []
    for row in rows:
        name, us, derived = row.split(",", 2)
        rec = {"bench": f"BENCH_{name}", "us_per_call": float(us)}
        rec.update(_parse_derived(derived))
        # every record names the kernel family it measured; rows predating
        # the sddmm/fused siblings are plain spmm
        rec.setdefault("kernel", "spmm")
        recs.append(rec)
    return recs


def compare_records(current: list, baseline: list,
                    tolerance: float) -> list:
    """Gate check: list of human-readable violations (empty = pass).

    For every baseline record (keyed by its unique ``bench`` name) the
    matching current record must exist and keep each GATE_FIELDS value
    within ``baseline · (1 + tolerance)``. Records carrying an "error"
    field on either side are reported via the exit-code path, not here.
    """
    cur = {r["bench"]: r for r in current if "error" not in r}
    violations = []
    gated = [r for r in baseline if "error" not in r]
    if not gated:
        # an empty/all-error baseline silently passing would mean the
        # gate checks nothing; that's a failure of the gate, not a pass
        return ["baseline contains no usable records (empty or "
                "all-error); regenerate benchmarks/baseline_smoke.json"]
    for base in gated:
        name = base["bench"]
        rec = cur.get(name)
        if rec is None:
            violations.append(f"{name}: missing from this run")
            continue
        for field in GATE_FIELDS:
            if field not in base:
                continue
            if (field == "total_allocation_size"
                    and base.get("jax") != _jax_version()):
                continue  # cross-jax-version allocations aren't comparable
            try:
                b, c = float(base[field]), float(rec.get(field, "nan"))
            except (TypeError, ValueError):
                violations.append(f"{name}.{field}: non-numeric "
                                  f"({base.get(field)!r} -> {rec.get(field)!r})")
                continue
            if not c <= b * (1.0 + tolerance):
                pct = (f"+{(c / b - 1.0) * 100.0:.1f}%" if b
                       else "baseline was 0")
                violations.append(
                    f"{name}.{field}: {b:g} -> {c:g} "
                    f"({pct} > {tolerance * 100.0:.0f}% tolerance)")
    return violations


def _run_family(mod, rows: list) -> None:
    """Stream one family's CSV rows (printed as produced) into ``rows``."""
    for row in mod.run():
        print(row, flush=True)
        rows.append(row)
    if hasattr(mod, "run_group_aware"):
        for row in mod.run_group_aware():
            print(row, flush=True)
            rows.append(row)


def _env_family_timeout():
    raw = os.environ.get("REPRO_BENCH_FAMILY_TIMEOUT")
    return float(raw) if raw else None


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="SHIRO benchmark harness (one module per figure)")
    ap.add_argument("--only", action="append", default=None,
                    metavar="MODULE",
                    help="run only these benchmark modules (repeatable)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_* records as JSON to PATH")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="fail (exit 1) when padded_rows / modeled_time "
                         "regress beyond --tolerance vs this baseline JSON")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative slack for --compare (default 0.05)")
    ap.add_argument("--family-timeout", type=float,
                    default=_env_family_timeout(), metavar="SECONDS",
                    help="wall-clock bound per benchmark family; a family "
                         "still running after this is abandoned with an "
                         "error record and exit 2 (default: the "
                         "REPRO_BENCH_FAMILY_TIMEOUT env var, else none)")
    args = ap.parse_args(argv)

    from . import (fig5_patterns, fig7_scaling, fig8_volume, fig9_balance,
                   fig10_ablation, fig11_ncols, fleet_serving, gat_attention,
                   moe_dispatch, overlap_sweep, sched_buckets, table3_gnn)
    modules = [fig5_patterns, fig7_scaling, fig8_volume, fig9_balance,
               fig10_ablation, fig11_ncols, table3_gnn, moe_dispatch,
               sched_buckets, overlap_sweep, gat_attention, fleet_serving]
    if args.only:
        short = {m.__name__.rsplit(".", 1)[-1]: m for m in modules}
        unknown = [o for o in args.only if o not in short]
        if unknown:
            raise SystemExit(
                f"unknown benchmark module(s) {unknown}; "
                f"available: {sorted(short)}")
        modules = [short[o] for o in args.only]

    print("name,us_per_call,derived")
    crashed = 0
    records = []
    for mod in modules:
        short_name = mod.__name__.rsplit(".", 1)[-1]
        rows = []
        hung = False
        try:
            if args.family_timeout is None:
                _run_family(mod, rows)
            else:
                # the family runs on a daemon thread so a hang inside a
                # benchmark (a wedged collective, an XLA deadlock) can be
                # abandoned at the deadline instead of hanging the run
                failure = []

                def _target(mod=mod, rows=rows, failure=failure):
                    try:
                        _run_family(mod, rows)
                    except BaseException as e:  # re-raised on main thread
                        failure.append(e)

                t = threading.Thread(target=_target, daemon=True,
                                     name=f"bench-{short_name}")
                t.start()
                t.join(args.family_timeout)
                if t.is_alive():
                    hung = True
                    raise TimeoutError(
                        f"family exceeded {args.family_timeout:g}s (hung)")
                if failure:
                    raise failure[0]
        except Exception as e:
            crashed += 1
            print(f"{mod.__name__},nan,ERROR", flush=True)
            if hung:
                print(f"{mod.__name__}: {e}", file=sys.stderr)
            else:
                traceback.print_exc(file=sys.stderr)
            # partial records still ship, plus a marker the gate can
            # tell apart from a regression (exit 2 vs 1)
            records.append({"bench": f"BENCH_{short_name}",
                            "error": f"{type(e).__name__}: {e}"})
        # keep whatever the module got out (snapshot: an abandoned
        # family's thread may still be appending)
        records += _records(list(rows))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"records": records}, f, indent=1, sort_keys=True)
        print(f"wrote {len(records)} records to {args.json}",
              file=sys.stderr)

    violations = []
    if args.compare:
        try:
            with open(args.compare) as f:
                baseline = json.load(f)["records"]
        except (OSError, ValueError, KeyError) as e:
            # a broken harness/baseline is NOT a regression: exit 2 so
            # the gate's 1-vs-2 contract stays honest
            print(f"cannot load baseline {args.compare!r}: {e}",
                  file=sys.stderr)
            sys.exit(EXIT_CRASHED)
        violations = compare_records(records, baseline, args.tolerance)
        for v in violations:
            print(f"REGRESSION {v}", file=sys.stderr)
        if not violations:
            print(f"gate: {len(baseline)} baseline records within "
                  f"{args.tolerance * 100:.0f}% tolerance", file=sys.stderr)

    if crashed:
        sys.exit(EXIT_CRASHED)
    if violations:
        sys.exit(EXIT_REGRESSED)


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    enable_compile_cache(os.path.join(repo_root, ".jax_cache"))
    main()
