#!/bin/bash
# Full measurement of one cell on a machine with the chip, as the bounds and
# limits in PERF.md were set: two sets of six runs on the same seeds, three
# traced runs, program readings on further seeds, control readings and a
# planted altered token, each benchmark run a process of its own. Outputs go
# to $MEASURE_OUT/<cell> (default .chipbench/measure/<cell>).
#
#   bash chipbench/measure.sh <cell> <seconds> <seed base> [readings] [controls]
cell=$1; secs=$2; base=$3; nread=${4:-4}; nctl=${5:-3}
out=${MEASURE_OUT:-.chipbench/measure}/$cell
mkdir -p "$out"
run() {  # run <tag> <seed> <trace>
  python3 chipbench/run.py --workload "$cell" --seed "$2" --seconds "$secs" \
    --trace "$3" > "$out/$1.out" 2> "$out/$1.err"
  echo "$1 seed=$2 trace=$3 rc=$? $(tail -1 "$out/$1.out" | cut -c1-1500)"
}
for s in 1 2 3 4 5 6; do run A$s $((base + s)) 0; done
for s in 1 2 3 4 5 6; do run B$s $((base + s)) 0; done
for s in 7 8 9; do run T$s $((base + s)) 1; done
seeds() { seq -s, $((base + $1)) $((base + $1 + $2 - 1)); }
readings() {  # readings <tag> <first seed offset> <count> [options]
  tag=$1; first=$2; n=$3; shift 3
  [ "$n" -gt 0 ] || return 0
  python3 chipbench/calibrate.py readings --workload "$cell" --seconds 10 \
    --seeds "$(seeds "$first" "$n")" "$@" > "$out/$tag.out" 2> "$out/$tag.err"
  echo "$tag rc=$?"; cut -c1-600 "$out/$tag.out"
}
readings readings 10 "$nread"
readings control 30 "$nctl" --control int8
readings fault 40 "$nctl" --fault token_altered
