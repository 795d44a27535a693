#!/bin/bash
# Parent against change in cells the benchmark already has, in one call to the chip, every pair on a seed
# of its own: usage (from the repo's root, the parent unpacked into _parent with `git archive`):
#   bash tests/perf/cell_pairs.sh <out> <cell> <seed> <side> [<side> ...]
# a side is "parent", "change" (the working tree) or "archive" (the committed files alone, unpacked into
# _archive_check with `git archive $(git write-tree)`); every two runs share a seed (parent change change parent: two pairs).
out=/root/repo/chiprun_out/$1; mkdir -p $out; cell=$2; seed=$3; shift 3
i=0
for side in "$@"; do
  case $side in parent) dir=/root/repo/_parent;; archive) dir=/root/repo/_archive_check;; *) dir=/root/repo;; esac
  t0=$(date +%s)
  (cd $dir && timeout 1500 python3 benchmarks/run.py --workload $cell --seed $seed --seconds 40 --trace 0 \
     > $out/$cell.$seed.$side.out 2> $out/$cell.$seed.$side.err)
  rc=$?
  echo "$cell seed $seed $side rc $rc in $(( $(date +%s) - t0 )) s: $(tail -n 1 $out/$cell.$seed.$side.out | cut -c1-420)"
  if [ $rc -ne 0 ]; then tail -n 4 $out/$cell.$seed.$side.err | cut -c1-600; fi
  i=$((i + 1)); if [ $((i % 2)) -eq 0 ]; then seed=$((seed + 1)); fi
done
