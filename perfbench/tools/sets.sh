#!/bin/bash
# Two sets of runs of one cell with the same seeds in both, each run a new
# process, results into chiprun_out/<tag>.jsonl:
#   bash perfbench/tools/sets.sh <cell> <seconds> <tag> <seed> [<seed> ...]
cell=$1; seconds=$2; tag=$3; shift 3
mkdir -p chiprun_out
for set in 1 2; do
  for seed in "$@"; do
    python3 perfbench/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 \
      > chiprun_out/_run.out 2> chiprun_out/_run.err
    rc=$?
    line=$(tail -n 1 chiprun_out/_run.out)
    if [ $rc -ne 0 ] || [ -z "$line" ]; then
      echo "run failed: set $set seed $seed rc $rc"; tail -n 5 chiprun_out/_run.err | cut -c1-400
      continue
    fi
    echo "{\"set\": $set, \"seed\": $seed, \"result\": $line}" >> "chiprun_out/$tag.jsonl"
    { echo "== set $set seed $seed"; grep '^\[perfbench' chiprun_out/_run.err | cut -c1-300; } >> "chiprun_out/$tag.log"
    echo "set $set seed $seed: $(echo "$line" | cut -c1-420)"
  done
done
python3 perfbench/tools/spread.py "chiprun_out/$tag.jsonl"
