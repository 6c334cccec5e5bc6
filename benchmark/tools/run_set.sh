# Several runs of one cell in one call, one process each, results under chiprun_out/<out-dir>/.
# usage: bash benchmark/tools/run_set.sh <out-dir> <workload> <seconds> <trace> <seed>...
# then:  python benchmark/tools/spread.py chiprun_out/<out-dir>
out=chiprun_out/$1; wl=$2; secs=$3; tr=$4; shift 4
mkdir -p $out
for seed in "$@"; do
  python benchmark/run.py --workload $wl --seed $seed --seconds $secs --trace $tr > $out/$wl.$seed.t$tr.out 2> $out/$wl.$seed.t$tr.err
  echo "rc=$? seed=$seed $(tail -n 1 $out/$wl.$seed.t$tr.out | cut -c1-1200)"
  grep '^{"event": "\(correctness\|window\|compiled_in_window\)"' $out/$wl.$seed.t$tr.err | cut -c1-1600
done
