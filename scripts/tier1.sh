#!/usr/bin/env bash
# Tier-1 gate: test suite + determinism + perf smoke, machine-readable.
#
# Gates (all selected gates must pass; any failure exits nonzero):
#   * tests      — the full pytest suite (with line coverage when
#                  pytest-cov is installed)
#   * coverage   — line-coverage floor for src/repro/core (gated from
#                  coverage.xml; skipped-but-ok when pytest-cov is not
#                  installed — CI always installs it).  Requires the
#                  tests gate in the same run (it produces coverage.xml).
#   * golden     — fresh schedules for all 74 combos (56 kernel×strategy
#                  + fusion-variant extremes + static-autotune winners)
#                  diff bit-exact against artifacts/golden_schedules/
#                  (regenerate intentionally via
#                   `python scripts/golden_schedules.py --update-golden`)
#   * sched_bench — scheduler smoke bench under a wall-clock budget:
#                  decomposed-vs-seed geomean floor, and the exact
#                  backend's decomposed times within 1.25x (geomean) of
#                  a same-run, same-machine HiGHS-engine reference (the
#                  PR-2 backend), so the gate measures code, not host
#                  speed; the frozen dev-machine PR-2 numbers in
#                  BENCH_scheduler_pr2_baseline.json are reported as
#                  informational context only
#   * polybench  — generated-code smoke on the fast set (checksum-gated;
#                  ERROR rows fail; kernel-specific geomean floor 1.3x)
#   * pallas     — JAX-CPU (interpret) smoke: every Pallas kernel runs
#                  through the schedule-tree → lower_to_kernel_plan
#                  lowering and must numerically match kernels/ref.py
#   * chaos      — seeded fault-injection sweep (scripts/chaos_sweep.py):
#                  every fault site × the fast-set kernels must yield a
#                  legal schedule (numpy-oracle differential) or a clean
#                  typed error, bit-deterministically — including the
#                  schedd daemon scenarios (kill -9 mid-request and of a
#                  pool worker, garbage frames, slow-loris, version
#                  skew, missing socket); writes artifacts/chaos_summary.json
#   * schedd     — scheduling-daemon load bench (benchmarks/bench_schedd.py):
#                  concurrent identical requests must coalesce to one
#                  computation, and warm-hit plan latency through the
#                  daemon must stay within 2x of the in-process
#                  disk-hit path; writes benchmarks/BENCH_schedd.json
#   * loadgen    — multi-process load generator (benchmarks/bench_loadgen.py):
#                  distinct-key throughput at --workers 4 must be >= 3x
#                  the single-worker daemon with p99 <= 2x p50, zero
#                  request errors, and the shared-key mix must still
#                  coalesce to exactly one computation; writes
#                  benchmarks/BENCH_loadgen.json
#   * loadgen_tcp — loadgen TCP compare (bench_loadgen --tcp): one
#                  daemon at max workers serving the same pool over
#                  Unix and authenticated TCP; distinct-key TCP
#                  throughput must stay within ~10% of Unix, zero
#                  errors, and shared keys must still coalesce to one
#                  computation through the authenticated path; writes
#                  benchmarks/BENCH_loadgen_tcp.json
#   * serve      — serving-engine bench (benchmarks/bench_serve.py):
#                  continuous batching (chunked prefill interleaved with
#                  decode, paged KV, Pallas kernels) on the granite smoke
#                  config; greedy tokens must be bit-identical to each
#                  request's prefill + decode_step reference; writes
#                  benchmarks/BENCH_serve.json
#   * bench_compare — regression gate: fresh BENCH_*.json from this run
#                  vs benchmarks/baselines/ with per-metric tolerances
#                  (scripts/bench_compare.py); only host-portable ratio
#                  and count metrics are compared; writes
#                  artifacts/bench_delta.md
#
# Every run writes artifacts/tier1_summary.json (per-gate ok + metrics)
# for CI to upload/consume, even when a gate fails.  The summary's "ok"
# covers exactly the gates selected for that run.
#
# Usage:  scripts/tier1.sh [gate ...]      # no args = every gate
#   e.g.  scripts/tier1.sh tests coverage pallas
#         scripts/tier1.sh chaos schedd loadgen bench_compare
# Env:    POLYTOPS_TIER1_BUDGET       scheduler smoke budget in s (default 240)
#         POLYTOPS_TIER1_PB_BUDGET    polybench smoke budget in s (default 1200)
#         POLYTOPS_TIER1_REQUIRE_COV  1 = fail (not skip) when pytest-cov
#                                     is missing (CI sets this)
set -u
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

ALL_GATES=(tests coverage golden sched_bench polybench pallas chaos schedd
           loadgen loadgen_tcp serve bench_compare)
if [ "$#" -gt 0 ]; then
  GATES=("$@")
  for g in "${GATES[@]}"; do
    case " ${ALL_GATES[*]} " in
      *" $g "*) ;;
      *) echo "unknown gate '$g' (known: ${ALL_GATES[*]})" >&2; exit 2 ;;
    esac
  done
else
  GATES=("${ALL_GATES[@]}")
fi
export TIER1_GATES="${GATES[*]}"

want() {  # want <gate> — is the gate selected for this run?
  case " ${GATES[*]} " in *" $1 "*) return 0 ;; *) return 1 ;; esac
}

if want coverage && ! want tests; then
  echo "the coverage gate reads coverage.xml produced by the tests gate;" >&2
  echo "select both: scripts/tier1.sh tests coverage ..." >&2
  exit 2
fi

BUDGET="${POLYTOPS_TIER1_BUDGET:-240}"
PB_BUDGET="${POLYTOPS_TIER1_PB_BUDGET:-1200}"
RESULTS="$(mktemp)"
mkdir -p artifacts

record() {  # record <gate> <ok 0|1> <detail-json>
  printf '%s\t%s\t%s\n' "$1" "$2" "${3:-{\}}" >> "$RESULTS"
}

finish() {
  python - "$RESULTS" <<'PY' > artifacts/tier1_summary.json
import json, os, sys, pathlib
gates = {}
for ln in pathlib.Path(sys.argv[1]).read_text().splitlines():
    name, ok, detail = ln.split("\t", 2)
    gates[name] = {"ok": ok == "1"}
    try:
        gates[name].update(json.loads(detail))
    except json.JSONDecodeError:
        pass
expected = os.environ["TIER1_GATES"].split()
ok = all(gates.get(g, {}).get("ok") for g in expected)
print(json.dumps({"ok": ok, "selected": expected, "gates": gates},
                 indent=2, sort_keys=True))
PY
  rm -f "$RESULTS"
  echo "== tier-1 summary written to artifacts/tier1_summary.json =="
}
trap finish EXIT

if want tests; then
echo "== tier-1 tests =="
T0=$SECONDS
HAVE_COV=0
COV_ARGS=()
if python -c "import pytest_cov" 2>/dev/null; then
  HAVE_COV=1
  COV_ARGS=(--cov=repro.core --cov-report=xml:coverage.xml --cov-report=)
fi
if python -m pytest -x -q ${COV_ARGS[@]+"${COV_ARGS[@]}"}; then
  record tests 1 "{\"seconds\": $((SECONDS - T0))}"
else
  record tests 0 "{\"seconds\": $((SECONDS - T0))}"
  exit 1
fi
fi

if want coverage; then
echo "== coverage floor for src/repro/core =="
if [ "$HAVE_COV" = 1 ]; then
  if python - <<'PY'
import json, pathlib, sys
import xml.etree.ElementTree as ET
FLOOR = 60.0   # ratchet floor, percent of src/repro/core lines executed
root = ET.parse("coverage.xml").getroot()
pct = round(float(root.attrib["line-rate"]) * 100.0, 2)
detail = {"line_coverage_pct": pct, "floor_pct": FLOOR,
          "scope": "repro.core"}
pathlib.Path(".tier1_cov_detail.json").write_text(json.dumps(detail))
if pct < FLOOR:
    sys.exit(f"core coverage {pct}% < {FLOOR}% floor")
print(f"coverage OK: repro.core {pct}% line coverage (floor {FLOOR}%)")
PY
  then
    record coverage 1 "$(cat .tier1_cov_detail.json)"
    rm -f .tier1_cov_detail.json
  else
    record coverage 0 "$(cat .tier1_cov_detail.json 2>/dev/null || echo '{}')"
    rm -f .tier1_cov_detail.json
    exit 1
  fi
elif [ "${POLYTOPS_TIER1_REQUIRE_COV:-0}" = 1 ]; then
  # a gate that silently records ok when its tool is missing is not a
  # gate — CI requires coverage, so a missing pytest-cov is a failure
  echo "COVERAGE REQUIRED but pytest-cov is not installed" >&2
  record coverage 0 '{"error": "coverage required but pytest-cov not installed"}'
  exit 1
else
  echo "pytest-cov not installed: coverage gate skipped (CI installs it)"
  record coverage 1 '{"skipped": true, "reason": "pytest-cov not installed"}'
fi
fi

if want golden; then
echo "== golden-schedule determinism gate (74 combos) =="
T0=$SECONDS
if python scripts/golden_schedules.py check; then
  record golden 1 "{\"seconds\": $((SECONDS - T0)), \"combos\": 74}"
else
  record golden 0 "{\"seconds\": $((SECONDS - T0))}"
  exit 1
fi
fi

if want sched_bench; then
echo "== scheduler smoke bench (fast subset, ${BUDGET}s budget each engine) =="
BENCH_OUT="$(mktemp)"
# same-machine HiGHS-engine reference first (the PR-2 backend) ...
if ! POLYTOPS_BENCH_FAST=1 POLYTOPS_BENCH_REPS=2 POLYTOPS_BENCH_ENGINE=highs \
     timeout "$BUDGET" python -m benchmarks.bench_scheduler > "$BENCH_OUT"; then
  echo "HIGHS REFERENCE BENCH FAILED or exceeded ${BUDGET}s budget" >&2
  tail -5 "$BENCH_OUT" >&2
  rm -f "$BENCH_OUT"
  record sched_bench 0 '{"error": "highs reference bench failed or over budget"}'
  exit 1
fi
mv benchmarks/BENCH_scheduler_fast.json benchmarks/BENCH_scheduler_fast_highs.json
# ... then the default exact backend
if ! POLYTOPS_BENCH_FAST=1 POLYTOPS_BENCH_REPS=2 \
     timeout "$BUDGET" python -m benchmarks.bench_scheduler > "$BENCH_OUT"; then
  echo "SMOKE BENCH FAILED or exceeded ${BUDGET}s budget" >&2
  tail -5 "$BENCH_OUT" >&2
  rm -f "$BENCH_OUT"
  record sched_bench 0 '{"error": "bench failed or over budget"}'
  exit 1
fi
tail -1 "$BENCH_OUT"
rm -f "$BENCH_OUT"

# the smoke bench must keep a healthy margin over the seed path AND the
# exact backend must stay within 1.25x (geomean) of the same-run HiGHS
# reference — both engines measured on this machine, this commit
if python - <<'PY'
import json, math, pathlib, sys
d = json.loads(pathlib.Path("benchmarks/BENCH_scheduler_fast.json").read_text())
h = json.loads(
    pathlib.Path("benchmarks/BENCH_scheduler_fast_highs.json").read_text())
g = d["geomean_speedup_decomposed_vs_seed"]
ratios = []
for name, e in d["kernels"].items():
    hk = h["kernels"].get(name, {}).get("strategies", {})
    for s, per in e["strategies"].items():
        ref = hk.get(s, {}).get("decomposed")
        if ref:
            ratios.append(per["decomposed"] / ref)
r = (round(math.exp(sum(math.log(x) for x in ratios) / len(ratios)), 3)
     if ratios else None)
bad = []
if g < 2.0:
    bad.append(f"decomposed-vs-seed geomean {g}x < 2.0x floor")
if r is not None and r > 1.25:
    bad.append(f"exact backend {r}x slower than same-run HiGHS (cap 1.25x)")
detail = {"geomean_speedup_decomposed_vs_seed": g,
          "geomean_vs_highs_same_run": r,
          "geomean_vs_pr2_dev_baseline": d.get("geomean_vs_pr2_baseline")}
pathlib.Path(".tier1_sched_detail.json").write_text(json.dumps(detail))
if bad:
    sys.exit("; ".join(bad))
print(f"scheduler bench OK: {g}x over seed (floor 2.0x), "
      f"{r}x vs same-run HiGHS (cap 1.25x)")
PY
then
  record sched_bench 1 "$(cat .tier1_sched_detail.json)"
  rm -f .tier1_sched_detail.json
else
  record sched_bench 0 "$(cat .tier1_sched_detail.json 2>/dev/null || echo '{}')"
  rm -f .tier1_sched_detail.json
  exit 1
fi
fi

if want polybench; then
echo "== polybench smoke bench (fast set, ${PB_BUDGET}s budget) =="
PB_OUT="$(mktemp)"
if ! POLYTOPS_BENCH_FAST=1 \
     timeout "$PB_BUDGET" python -m benchmarks.bench_polybench > "$PB_OUT"; then
  echo "POLYBENCH SMOKE FAILED or exceeded ${PB_BUDGET}s budget" >&2
  tail -5 "$PB_OUT" >&2
  rm -f "$PB_OUT"
  record polybench 0 '{"error": "bench failed or over budget"}'
  exit 1
fi
tail -1 "$PB_OUT"
rm -f "$PB_OUT"

# generated-code quality gate: no errors, no checksum mismatches, and a
# healthy kernel-specific geomean over the pluto-style baseline
if python - <<'PY'
import json, pathlib, sys
d = json.loads(pathlib.Path("benchmarks/BENCH_polybench.json").read_text())
errs = d["total_errors"]
mism = d["checksum_mismatches"]
g = d["geomean_kernel_specific_vs_pluto"]
detail = {"geomean_kernel_specific_vs_pluto": g, "errors": errs,
          "checksum_mismatches": mism, "n_kernels": d["n_kernels"]}
pathlib.Path(".tier1_pb_detail.json").write_text(json.dumps(detail))
if errs:
    bad = {k: v["errors"] for k, v in d["kernels"].items() if v["errors"]}
    sys.exit(f"polybench smoke has {errs} ERROR rows: {bad}")
if mism:
    sys.exit(f"polybench smoke has {mism} checksum mismatches")
at_fail = d.get("autotune_failures", 0)
if at_fail:
    bad = {k: v.get("autotune_error") for k, v in d["kernels"].items()
           if v.get("autotune_error")}
    sys.exit(f"autotuner failed on {at_fail} kernel(s): {bad}")
if g is None or g < 1.3:
    sys.exit(f"kernel-specific speedup regressed: geomean {g}x < 1.3x floor")
print(f"polybench OK: kernel-specific geomean {g}x over "
      f"{d['n_kernels']} kernels (floor 1.3x), 0 errors, 0 mismatches")
PY
then
  record polybench 1 "$(cat .tier1_pb_detail.json)"
  rm -f .tier1_pb_detail.json
else
  record polybench 0 "$(cat .tier1_pb_detail.json 2>/dev/null || echo '{}')"
  rm -f .tier1_pb_detail.json
  exit 1
fi
fi

if want pallas; then
echo "== pallas smoke (JAX CPU, interpret mode, tree lowering) =="
T0=$SECONDS
PALLAS_OUT="$(mktemp)"
if JAX_PLATFORMS=cpu timeout 600 python -m repro.kernels.bench --smoke \
     > "$PALLAS_OUT" 2>&1; then
  cat "$PALLAS_OUT"
  record pallas 1 "{\"seconds\": $((SECONDS - T0))}"
  rm -f "$PALLAS_OUT"
else
  cat "$PALLAS_OUT" >&2
  echo "PALLAS SMOKE FAILED (crash or numerical mismatch vs kernels/ref.py)" >&2
  record pallas 0 "{\"seconds\": $((SECONDS - T0))}"
  rm -f "$PALLAS_OUT"
  exit 1
fi
fi

if want chaos; then
echo "== chaos sweep (fault injection + daemon × fast set, 120s budget) =="
T0=$SECONDS
if timeout 120 python scripts/chaos_sweep.py --out artifacts/chaos_summary.json; then
  CH_DETAIL="$(python - <<'PY'
import json
d = json.load(open("artifacts/chaos_summary.json"))
print(json.dumps({"seconds": d["seconds"], "scenarios": d["n_scenarios"],
                  "failures": d["n_failures"]}))
PY
)"
  record chaos 1 "$CH_DETAIL"
else
  echo "CHAOS SWEEP FAILED (escaped exception, illegal degraded schedule," >&2
  echo "nondeterministic fingerprint, hung daemon, or never-fired armed site)" >&2
  record chaos 0 "{\"seconds\": $((SECONDS - T0))}"
  exit 1
fi
fi

if want schedd; then
echo "== schedd daemon bench (coalescing + warm-hit latency, 120s budget) =="
T0=$SECONDS
if ! timeout 120 python -m benchmarks.bench_schedd; then
  echo "SCHEDD BENCH FAILED or exceeded 120s budget" >&2
  record schedd 0 "{\"seconds\": $((SECONDS - T0))}"
  exit 1
fi
if python - <<'PY'
import json, pathlib, sys
d = json.loads(pathlib.Path("benchmarks/BENCH_schedd.json").read_text())
co = d["coalescing"]
warm = d["warm_latency"]
detail = {"computed": co["computed"], "coalesced": co["coalesced"],
          "clients": co["clients"],
          "daemon_warm_p50_ms": warm["daemon_p50_ms"],
          "inprocess_disk_p50_ms": warm["inprocess_p50_ms"],
          "warm_ratio": warm["ratio_p50"],
          "fallbacks": d["fallbacks"]}
pathlib.Path(".tier1_schedd_detail.json").write_text(json.dumps(detail))
bad = []
if co["computed"] != 1 or co["coalesced"] < 1:
    bad.append(f"{co['clients']} identical concurrent requests -> "
               f"{co['computed']} computations, {co['coalesced']} coalesced "
               f"(want 1 computation, >=1 coalesced)")
if warm["ratio_p50"] > 2.0:
    bad.append(f"warm-hit p50 through daemon {warm['daemon_p50_ms']:.3f}ms is "
               f"{warm['ratio_p50']:.2f}x the in-process disk hit "
               f"{warm['inprocess_p50_ms']:.3f}ms (cap 2.0x)")
if bad:
    sys.exit("; ".join(bad))
print(f"schedd OK: {co['clients']} clients -> {co['computed']} computation "
      f"({co['coalesced']} coalesced); warm p50 {warm['daemon_p50_ms']:.2f}ms "
      f"vs in-process {warm['inprocess_p50_ms']:.2f}ms "
      f"({warm['ratio_p50']:.2f}x, cap 2.0x)")
PY
then
  record schedd 1 "$(cat .tier1_schedd_detail.json)"
  rm -f .tier1_schedd_detail.json
else
  record schedd 0 "$(cat .tier1_schedd_detail.json 2>/dev/null || echo '{}')"
  rm -f .tier1_schedd_detail.json
  exit 1
fi
fi

if want loadgen; then
echo "== schedd load generator (worker-pool scaling, 600s budget) =="
T0=$SECONDS
if ! timeout 600 python -m benchmarks.bench_loadgen; then
  echo "LOADGEN BENCH FAILED or exceeded 600s budget" >&2
  record loadgen 0 "{\"seconds\": $((SECONDS - T0))}"
  exit 1
fi
if python - <<'PY'
import json, pathlib, sys
d = json.loads(pathlib.Path("benchmarks/BENCH_loadgen.json").read_text())
speedup = d["speedup_distinct_4v1"]
tail = d["p99_over_p50_at_max_workers"]
errors = d["errors_total"]
shared = d["shared_computed_at_max_workers"]
detail = {"speedup_distinct_4v1": speedup,
          "p99_over_p50_at_max_workers": tail,
          "errors_total": errors,
          "shared_computed_at_max_workers": shared,
          "workers_sweep": d["workers_sweep"]}
pathlib.Path(".tier1_loadgen_detail.json").write_text(json.dumps(detail))
bad = []
if speedup is None or speedup < 3.0:
    bad.append(f"distinct-key speedup at max workers {speedup}x < 3.0x floor")
if tail is None or tail > 2.0:
    bad.append(f"p99/p50 at max workers {tail}x > 2.0x cap (starvation)")
if errors:
    bad.append(f"{errors} request error(s) under load (want 0)")
if shared != 1:
    bad.append(f"shared-key mix computed {shared} times (pool broke "
               f"coalescing; want exactly 1)")
if bad:
    sys.exit("; ".join(bad))
print(f"loadgen OK: {speedup}x distinct-key speedup (floor 3.0x), "
      f"p99/p50 {tail}x (cap 2.0x), 0 errors, shared mix computed once")
PY
then
  record loadgen 1 "$(cat .tier1_loadgen_detail.json)"
  rm -f .tier1_loadgen_detail.json
else
  record loadgen 0 "$(cat .tier1_loadgen_detail.json 2>/dev/null || echo '{}')"
  rm -f .tier1_loadgen_detail.json
  exit 1
fi
fi

if want loadgen_tcp; then
echo "== schedd loadgen TCP compare (unix vs authenticated tcp, 600s budget) =="
T0=$SECONDS
if ! timeout 600 python -m benchmarks.bench_loadgen --tcp; then
  echo "LOADGEN TCP BENCH FAILED or exceeded 600s budget" >&2
  record loadgen_tcp 0 "{\"seconds\": $((SECONDS - T0))}"
  exit 1
fi
if python - <<'PY'
import json, pathlib, sys
d = json.loads(pathlib.Path("benchmarks/BENCH_loadgen_tcp.json").read_text())
ratio = d["tcp_over_unix_distinct"]
errors = d["errors_total"]
shared = d["shared_computed_tcp"]
detail = {"tcp_over_unix_distinct": ratio, "errors_total": errors,
          "shared_computed_tcp": shared, "workers": d["workers"]}
pathlib.Path(".tier1_loadgen_tcp_detail.json").write_text(json.dumps(detail))
bad = []
if ratio is None or ratio < 0.9:
    bad.append(f"TCP distinct-key throughput is {ratio}x the Unix-socket "
               f"run (floor 0.9x — the transport may not cost >10%)")
if errors:
    bad.append(f"{errors} request error(s) over TCP (want 0)")
if shared != 1:
    bad.append(f"shared-key mix over TCP computed {shared} times "
               f"(auth path broke coalescing; want exactly 1)")
if bad:
    sys.exit("; ".join(bad))
print(f"loadgen_tcp OK: TCP/Unix distinct throughput {ratio}x "
      f"(floor 0.9x), 0 errors, shared mix computed once over TCP")
PY
then
  record loadgen_tcp 1 "$(cat .tier1_loadgen_tcp_detail.json)"
  rm -f .tier1_loadgen_tcp_detail.json
else
  record loadgen_tcp 0 "$(cat .tier1_loadgen_tcp_detail.json 2>/dev/null || echo '{}')"
  rm -f .tier1_loadgen_tcp_detail.json
  exit 1
fi
fi

if want serve; then
echo "== serve bench (continuous batching vs the reference steps, 600s budget) =="
T0=$SECONDS
if ! JAX_PLATFORMS=cpu timeout 600 python -m benchmarks.bench_serve; then
  echo "SERVE BENCH FAILED or exceeded 600s budget" >&2
  record serve 0 "{\"seconds\": $((SECONDS - T0))}"
  exit 1
fi
if python - <<'PY'
import json, pathlib, sys
d = json.loads(pathlib.Path("benchmarks/BENCH_serve.json").read_text())
ident = d["tokens_identical"]
detail = {"tokens_identical": ident,
          "overlap_ratio": d["overlap_ratio"],
          "p99_over_p50_inter_token": d["p99_over_p50_inter_token"],
          "paged_memory_ratio": d["paged_memory_ratio"],
          "tokens_per_s_continuous": d["continuous"]["tokens_per_s"]}
pathlib.Path(".tier1_serve_detail.json").write_text(json.dumps(detail))
if ident != 1:
    sys.exit("continuous-engine greedy tokens differ from the prefill + "
             "decode_step reference (want bit-identical)")
print(f"serve OK: bit-identical greedy tokens, overlap ratio "
      f"{d['overlap_ratio']}")
PY
then
  record serve 1 "$(cat .tier1_serve_detail.json)"
  rm -f .tier1_serve_detail.json
else
  record serve 0 "$(cat .tier1_serve_detail.json 2>/dev/null || echo '{}')"
  rm -f .tier1_serve_detail.json
  exit 1
fi
fi

if want bench_compare; then
echo "== bench regression gate (fresh BENCH_*.json vs baselines) =="
if python scripts/bench_compare.py; then
  BC_DETAIL="$(python - <<'PY'
import json
rows = open("artifacts/bench_delta.md").read().count("| ok |")
print(json.dumps({"metrics_ok": rows, "delta": "artifacts/bench_delta.md"}))
PY
)"
  record bench_compare 1 "$BC_DETAIL"
else
  record bench_compare 0 '{"delta": "artifacts/bench_delta.md"}'
  exit 1
fi
fi

echo "== tier-1 gate passed =="
