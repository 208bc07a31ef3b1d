# PR 30, second tile call, one chip, from the same archives as call_tile.sh
# (the program is byte for byte the first call's): two more pairs in the
# other order (change, parent, parent, change) and a second traced change
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
run final t30b_change_a tile.roam 2147530111 0
run parent t30b_parent tile.roam 2147530111,2147530112 0
run final t30b_change_b tile.roam 2147530112 0
run final t30b_traced tile.roam 2147530113 1
