#!/bin/sh
# Not a test: the runs the bound's rule asks for, in one chip call.
#   sh benchmark/tests/run_sets.sh <cell> <seconds> <sets> <seed> [<seed> ...]
# First one run that compiles (marked "first"), then <sets> sets over the same
# seeds; every result line goes to chiprun_out/sets_<cell>.jsonl, every run's
# earlier lines to chiprun_out/sets_<cell>.log.
cell=$1; seconds=$2; sets=$3; shift 3
mkdir -p chiprun_out
out=chiprun_out/sets_$cell.jsonl; log=chiprun_out/sets_$cell.log
python3 benchmark/run.py --workload $cell --seed 2147480000 --seconds $seconds --trace 0 > chiprun_out/_run.out 2>> $log
cat chiprun_out/_run.out >> $log
tail -n 1 chiprun_out/_run.out | sed 's/^{/{"first": true, /' >> $out
i=0
while [ $i -lt $sets ]; do
  for s in "$@"; do
    python3 benchmark/run.py --workload $cell --seed $s --seconds $seconds --trace 0 > chiprun_out/_run.out 2>> $log
    echo "rc=$? seed=$s" >> $log
    grep -v '^{"correct"' chiprun_out/_run.out >> $log
    tail -n 1 chiprun_out/_run.out >> $out
  done
  i=$((i + 1))
done
python3 benchmark/tests/spread.py $out $#
