# PR 39, one chip: tile.roam traced, the change (git archive of the
# index) and the parent commit on one seed. The tile runs no megaspace
# code: its device metrics should read the parent's to four digits.
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
run change t39_change_traced tile.roam 2147539121 1
run parent t39_parent_traced tile.roam 2147539121 1
# leave the chip free: wait for the served processes to exit
for i in $(seq 60); do pgrep -f goworld_tpu >/dev/null || break; sleep 1; done
pgrep -fa goworld_tpu && pkill -f goworld_tpu || true
echo "call took $(( $(date +%s) - T0 )) s"
