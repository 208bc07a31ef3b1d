# tile.roam, parent (git archive 9b969bf) against change (git archive of the
# index) in turn: parent, change, change, parent
set -e
for side in parent final; do
  rm -rf _archive/$side && mkdir -p _archive/$side
  tar -x -f _archive/$side.tar -C _archive/$side
done
run() {  # side label seed trace
  ( cd _archive/$1 && export HOME=$PWD/.home TMPDIR=$PWD/.tmp && mkdir -p $HOME $TMPDIR \
    && python benchmark/tools/series.py --label "$2" --workload tile.roam --seeds "$3" --seconds 40 --trace "$4" || true
    mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/ )
}
run parent tile_parent 2147510101 0
run final tile_change 2147510101 0
run final tile_change 2147510102 0
run parent tile_parent 2147510102 0
run final tile_change_traced 2147510103 1
