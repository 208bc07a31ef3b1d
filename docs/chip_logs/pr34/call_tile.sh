# PR 34, one chip, everything from committed files alone (the change is
# git archive of the index; the parent git archive ae4761b with this PR's
# BENCHMARK.json and benchmark/ laid over it, as the driver does):
# tile.roam parent, change, change, parent; one traced run of each; four
# more untraced change runs on seeds of their own (the spread of
# rpc_ms.p95); soak.spaces (cell file, no claim) once
set -e
T0=$(date +%s)
for side in parent final; do
  rm -rf _archive/$side && mkdir -p _archive/$side
  tar -x -f _archive/$side.tar -C _archive/$side
done
tar -x -f _archive/overlay.tar -C _archive/parent
run() {  # side label workload seeds traces [more options]
  ( cd _archive/$1 && export HOME=$PWD/.home TMPDIR=$PWD/.tmp && mkdir -p $HOME $TMPDIR \
    && python benchmark/tools/series.py --label "$2" --workload "$3" --seeds "$4" --seconds 40 --trace "$5" $6 || true
    mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/ )
}
run parent t34_parent tile.roam 2147534101 0
run final t34_change tile.roam 2147534101,2147534102 0
run parent t34_parent tile.roam 2147534102 0
run final t34_traced tile.roam 2147534103 1
run parent t34_parent_traced tile.roam 2147534103 1
run final t34_more tile.roam 2147534104,2147534105,2147534106,2147534107 0
run final s34_change soak.spaces 2147534301 0 "--cell-file benchmark/cells/soak.spaces.json"
echo "call took $(( $(date +%s) - T0 )) s"
