#!/bin/bash
# One cell on several seeds from one checkout, in one call to the chip; the last of them traced if asked.
# usage (from the repo's root, through the chip tool):
#   bash tests/perf/cell_seeds.sh <out> <checkout> <cell> <first seed> <runs> [traced]
# <out> is a directory under chiprun_out/, <checkout> a directory that holds the tree to run (the repo's
# root, or `git archive $(git write-tree) | tar -x -C _archive_check`: the committed files alone).
# Each run prints its result line; the last lines give the quartile spread of train_tokens_per_s_chip
# (statistics.quantiles, n=4, over the untraced runs) beside half its bound.
root=$(pwd); out=$root/chiprun_out/$1; dir=$2; cell=$3; seed=$4; runs=$5; traced=${6:-}
mkdir -p $out; cd $dir
for i in $(seq 1 $runs); do
  trace=0; if [ -n "$traced" ] && [ $i -eq $runs ]; then trace=1; fi
  t0=$(date +%s)
  timeout 1500 python3 benchmarks/run.py --workload $cell --seed $seed --seconds 40 --trace $trace \
    > $out/$cell.$seed.out 2> $out/$cell.$seed.err
  rc=$?
  echo "$cell seed $seed trace $trace rc $rc in $(( $(date +%s) - t0 )) s: $(tail -n 1 $out/$cell.$seed.out | cut -c1-2600)"
  if [ $rc -ne 0 ]; then tail -n 3 $out/$cell.$seed.err | cut -c1-400; fi
  cp benchmarks/out/$cell.$seed.steps.json $out/ 2>/dev/null
  seed=$((seed + 1))
done
cp benchmarks/out/*.last.json $out/ 2>/dev/null
python3 - $out $cell <<'PY'
import glob, json, statistics, sys
rates, moe = [], []
for path in sorted(glob.glob(f"{sys.argv[1]}/{sys.argv[2]}.*.out")):
    lines = open(path).read().strip().splitlines()
    if not lines:
        continue
    line = json.loads(lines[-1])
    value = line.get("metrics", {}).get("train_tokens_per_s_chip", {}).get("value")
    if value is None or "device_idle_share.train" in line.get("metrics", {}):
        continue                                    # a failed run, or the traced one
    rates.append(value)
    try:
        record = json.load(open(path.replace(".out", ".steps.json")))
        moe.append((record["seed"], round(value, 1), record["moe"]["at_start"], record["moe"]["at_end"]))
    except (OSError, KeyError):
        pass
if len(rates) >= 2:
    q = statistics.quantiles(rates, n=4)
    print(f"untraced runs {len(rates)}: min {min(rates):.1f} median {statistics.median(rates):.1f} max {max(rates):.1f}; "
          f"quartile spread {(q[2] - q[0]) / statistics.median(rates) * 100:.3f} % (half the bound: 0.5 %)")
for row in moe:
    print("expert layers at the window's start | end:", *row)
PY
