#!/bin/bash
# The measurements that prove one cell on the chip, in one command: a
# smoke run, [a sweep that sets the rate], readings with float8 controls,
# two sets of 6 runs on the same seeds (for the bounds: chipbench/
# spread.py), 3 traced runs, [the small recorded trace of the tests].
# usage: chipbench/prove_cell.sh <cell> <out dir> <rates|-> <run_seconds> <seed base> [stages]
C=$1; T=$2; RATES=$3; S=$4; B=$5; STAGES=${6:-smoke,sweep,readings,sets,traces}
O=$T; mkdir -p $O
log() { echo "== $(date +%T) $*"; }
has() { [[ ",$STAGES," == *",$1,"* ]]; }
one() {  # one run: <label> <seed> <seconds> <trace>
  timeout 900 python3 chipbench/run.py --workload $C --seed $2 --seconds $3 --trace $4 >$O/$1.out 2>$O/$1.err
  local rc=$?
  echo "[$1] rc=$rc seed=$2"; grep -E "^\[run\]" $O/$1.err | cut -c1-700
  grep -E "^\[check\]" $O/$1.err; grep -E "^\[trace\]" $O/$1.err | cut -c1-1500; tail -1 $O/$1.out | cut -c1-1500
  return $rc
}
if has smoke; then
  log smoke
  one smoke $((B+1)) 10 1
  if [ $? -ne 0 ] || ! tail -1 $O/smoke.out | grep -q '"correct"'; then
    tail -80 $O/smoke.err | cut -c1-300; exit 1; fi
fi
if has sweep && [ "$RATES" != "-" ]; then
  log sweep
  timeout 1200 python3 chipbench/sweep.py --workload $C --seconds ${SWEEP_S:-20} --rates $RATES --seed $((B+2)) --set-rate >$O/sweep.out 2>$O/sweep.err
  echo "sweep rc=$?"; cat $O/sweep.out; grep -E "^\[run\]" $O/sweep.err | cut -c1-400
  cp chipbench/cells/$C.json $O/cell.json; cat $O/cell.json
fi
if has readings; then
  log readings
  timeout 1500 python3 chipbench/readings.py --workload $C --seconds ${READ_S:-20} --seeds $(seq -s, $((B+31)) $((B+30+${READ_SEEDS:-6}))) --controls 3 --set-limit >$O/readings.out 2>$O/readings.err
  echo "readings rc=$?"; cat $O/readings.out; grep -E "^\[run\]" $O/readings.err | cut -c1-300
  cp chipbench/cells/$C.json $O/cell.json; cat $O/cell.json
fi
if has sets; then
  for set in A B; do for i in 1 2 3 4 5 6; do
    log set $set $i; one set${set}_$i $((B+10+i)) $S 0
  done; done
fi
if has traces; then
  for i in 1 2 3; do log trace $i; one trace_$i $((B+20+i)) $S 1; done
  grep -h "^\[trace\]" $O/trace_1.err | cut -c1-2000
fi
if has record; then
  log record; mkdir -p $O/rec
  timeout 300 python3 chipbench/tests/record_trace.py $O/rec/tiny.xplane.pb >$O/rec/rec.log 2>&1
  echo "record rc=$?"; tail -3 $O/rec/rec.log | cut -c1-300; ls -la $O/rec
fi
log done
