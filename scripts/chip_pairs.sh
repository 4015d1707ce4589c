#!/bin/bash
# Compare two trees of this repository on ONE chip in ONE chiprun call (PR 33; the verify skill says when).
# Before the call: unpack the parent into runs/parent and the change into runs/change (git archive; runs/ is
# git-ignored and travels with the copy). Then:
#   chiprun --timeout 3600 -- bash scripts/chip_pairs.sh <cell> <limit_s> <need_s> P1 C1 C2 P2 T4 P3 C3
# step = P<k> (parent) | C<k> (change) | T<k> (change, --trace 1) | Q<k> (parent, --trace 1); k picks the seed, so P<k> and C<k>
# share one, and so do Q<k> and T<k>.
# A step is skipped when fewer than <need_s> seconds of <limit_s> are left. Both trees run from ONE path
# (runs/cur: the compile cache's key holds source paths); the compile cache is this call's own (runs/jc, inside
# this checkout: two checkouts never meet in it) and unbounded, so each tree compiles cold once; with
# PAIRS_CACHE=default in the environment it is left where the program and the machine put it, as the driver's
# runs have it (PR 35, Step 0). Result lines and the program's last JSONL line land under chiprun_out/<PAIRS_OUT or
# pr_pairs>/; a run that exits non-zero has its failures and the last 30 lines of its stderr printed. With
# PAIRS_TRACE_TOOL=<script> a traced step keeps its trace and `python3 <script> <tree> <cell>` reads it there (PR 36:
# a scope's time split by phase, by hand; benchmark_out/ does not travel back, what the tool prints does).
cell=$1; limit=$2; need=$3; shift 3
root=$(cd "$(dirname "$0")/.." && pwd)
out=$root/chiprun_out/${PAIRS_OUT:-pr_pairs}/$cell; mkdir -p "$out"
[ "$PAIRS_CACHE" = default ] || export JAX_COMPILATION_CACHE_DIR=$root/runs/jc JAX_COMPILATION_CACHE_MAX_SIZE=42949672960
env | grep -E '^JAX_|^XLA_|^TPU_' | sort
t0=$(date +%s)
cd "$root/runs"
for step in "$@"; do
  now=$(( $(date +%s) - t0 ))
  if [ $(( now + need )) -gt $limit ]; then echo "SKIP $step at ${now}s"; continue; fi
  side=${step:0:1}; k=${step:1}; seed=$(( 2147400000 + 7919 * k )); trace=0; tree=parent
  [ $side = C ] || [ $side = T ] && tree=change
  [ $side = T ] || [ $side = Q ] && trace=1 && seed=$(( seed + 13 ))
  mv $tree cur
  keep=; [ $trace = 1 ] && [ -n "$PAIRS_TRACE_TOOL" ] && keep=--keep-trace
  ( cd cur && timeout 1700 python3 benchmark/run.py --workload $cell --seed $seed --seconds 20 --trace $trace $keep > $out/$step.out 2> $out/$step.err; echo "rc=$?" >> $out/$step.out )
  [ -n "$keep" ] && { python3 "$PAIRS_TRACE_TOOL" cur $cell 2>&1 | tee $out/$step.trace_tool; rm -rf cur/benchmark_out/$cell/trace; }
  m=cur/benchmark_out/$cell/metrics.jsonl
  [ -f $m ] && tail -1 $m > $out/$step.metrics && cp $m $out/$step.jsonl
  mv cur $tree
  python3 - $out/$step.out $out/$step.metrics $step $(( $(date +%s) - t0 )) <<'PY'
import json, sys
out, met, step, t = sys.argv[1:5]
lines = open(out).read().splitlines()
res = next((json.loads(l) for l in reversed(lines) if l.startswith("{")), None)
det = next((json.loads(l.split("detail ", 1)[1]) for l in lines if "benchmark: detail " in l), {})
try:
    sc = json.loads(open(met).read()).get("scalars", {})
except Exception:
    sc = {}
print(step, "t=%ss" % t, lines[-1] if lines else "", "seed", det.get("seed"),
      "correct", res and res.get("correct"), "failed", res and res.get("failed"),
      {k: v for k, v in (res or {}).get("metrics", {}).items()},
      "compile_s", det.get("setup", {}).get("compile_s"), "hits", det.get("setup", {}).get("cache_hits"), "misses", det.get("setup", {}).get("cache_misses"),
      # programs compiled or loaded, by the program's own counter, at each line of its record: flat across the window
      "programs_by_line", [json.loads(l).get("scalars", {}).get("compile/programs_total") for l in open(met[:-8] + ".jsonl")] if sc else None,
      "dispatches_total", sc.get("learner/dispatches_total"), "shared", sc.get("league/shared_pass_dispatches_total"), "frozen", sc.get("league/frozen_dispatches_total"),
      "stages", det.get("setup", {}).get("stages"), "setup_s", det.get("setup", {}).get("setup_s"),
      "failures", det.get("failures"), "agreement", det.get("agreement"),
      "memory", {k: det.get("memory_stats", {}).get(k) for k in ("peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved", "bytes_limit")},
      # the program's own account of its start (PR 35), as the run's last JSONL line holds it
      "startup", {k: round(v, 3) for k, v in sc.items() if v and (
          k == "startup/process_age_at_init_s" or k.startswith("compile/") and k.count("/") == 1
          or k.startswith(("span/startup/", "span/fused/build", "span/learner/train", "span/compile/")) and k.endswith("/total_s"))},
      flush=True)
if not lines or lines[-1] != "rc=0":
    print(step, "STDERR TAIL", *open(out[:-4] + ".err").read().splitlines()[-30:], sep="\n  ", flush=True)
PY
done
du -sh "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null
