# PR 39, four chips: mega2x2.roam from git archives of the change (the
# index) and of the parent commit. The change traced, then parent and
# change untraced on one seed; a poller reads the game's /metrics and
# /vars for the ghost-row series while the change serves.
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
python docs/chip_logs/pr39/poll_metrics.py _archive/change/.bench_work/mega2x2.roam/goworld_tpu.ini chiprun_out/m39_metrics.jsonl &
POLL=$!
run change m39_change_traced mega2x2.roam 2147539111 1
run parent m39_parent mega2x2.roam 2147539101 0
run change m39_change mega2x2.roam 2147539101 0
kill $POLL || true
echo "call took $(( $(date +%s) - T0 )) s"
