# PR 42, four chips: mega2x2.roam from git archives of the parent
# commit and of the change, untraced, one seed, parent first. A poller
# reads the gate's relay and hold series while each side serves.
set -e
T0=$(date +%s)
for side in change parent; do
  rm -rf _archive/$side && mkdir -p _archive/$side
  tar -x -f _archive/$side.tar -C _archive/$side
done
run() {  # side label workload seeds trace
  ( cd _archive/$1 && export HOME=$PWD/.home TMPDIR=$PWD/.tmp && mkdir -p $HOME $TMPDIR \
    && python benchmark/tools/series.py --label "$2" --workload "$3" --seeds "$4" --seconds 40 --trace "$5" || true
    mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/ )
}
mkdir -p chiprun_out
python docs/chip_logs/pr42/poll_gate.py chiprun_out/m42_gate.jsonl \
  _archive/parent/.bench_work/mega2x2.roam/goworld_tpu.ini \
  _archive/change/.bench_work/mega2x2.roam/goworld_tpu.ini &
POLL=$!
run parent m42_parent mega2x2.roam 2147542101 0
run change m42_change mega2x2.roam 2147542101 0
kill $POLL || true
for i in $(seq 60); do pgrep -f goworld_tpu >/dev/null || break; sleep 1; done
pgrep -fa goworld_tpu && pkill -f goworld_tpu || true
echo "call took $(( $(date +%s) - T0 )) s"
