# PR 30, one chip, everything from committed files alone (the change is
# git archive of the index, the parent git archive d98f7f5): tile.roam
# parent, change, change, parent; one traced change; four more untraced
# change runs on seeds of their own (the spread of rpc_ms.p95)
set -e
for side in parent final; do
  rm -rf _archive/$side && mkdir -p _archive/$side
  tar -x -f _archive/$side.tar -C _archive/$side
done
run() {  # side label workload seeds traces
  ( cd _archive/$1 && export HOME=$PWD/.home TMPDIR=$PWD/.tmp && mkdir -p $HOME $TMPDIR \
    && python benchmark/tools/series.py --label "$2" --workload "$3" --seeds "$4" --seconds 40 --trace "$5" || true
    mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/ )
}
run parent t30_parent tile.roam 2147530101 0
run final t30_change tile.roam 2147530101,2147530102 0
run parent t30_parent tile.roam 2147530102 0
run final t30_traced tile.roam 2147530103 1
run final t30_more tile.roam 2147530104,2147530105,2147530106,2147530107 0
