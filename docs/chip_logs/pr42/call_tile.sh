# PR 42, one chip: tile.roam from git archives of the parent commit and
# of the change: parent and change untraced on one seed, then the
# change traced (its line carries gate_relay_ms). A poller reads the
# gate's relay and hold series while each side serves.
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
python docs/chip_logs/pr42/poll_gate.py chiprun_out/t42_gate.jsonl \
  _archive/parent/.bench_work/tile.roam/goworld_tpu.ini \
  _archive/change/.bench_work/tile.roam/goworld_tpu.ini &
POLL=$!
run parent t42_parent tile.roam 2147542201 0
run change t42_change tile.roam 2147542201 0
run change t42_change_traced tile.roam 2147542211 1
kill $POLL || true
for i in $(seq 60); do pgrep -f goworld_tpu >/dev/null || break; sleep 1; done
pgrep -fa goworld_tpu && pkill -f goworld_tpu || true
echo "call took $(( $(date +%s) - T0 )) s"
