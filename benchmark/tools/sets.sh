#!/bin/bash
# Two sets of N runs of one cell, the same seeds in both sets, every run a new
# process; result lines prefixed for tools/spread.py. On the chip:
#   chiprun -- bash benchmark/tools/sets.sh <cell> <seconds> <n> [trace] > runs.log
cell=$1; seconds=$2; n=${3:-6}; trace=${4:-0}
seeds=(7 2147483659 31337 4099 2147484001 65537 99991 123457)
for which in A B; do
  for i in $(seq 0 $((n - 1))); do
    seed=${seeds[$i]}
    out=$(python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" 2>/dev/null)
    rc=$?
    echo "$out" | grep -E "^\[benchmark\] (warm traffic|requests in window|utterances|window |/parse |voice_to_intent|token rate|output tokens|reference|NOT CORRECT|generator lateness)" | sed "s/^/  $cell $which $seed: /"
    echo "RUN $cell $which $seed $(echo "$out" | tail -n 1)"
    [ $rc -ne 0 ] && echo "  $cell $which $seed: exit code $rc"
  done
done
