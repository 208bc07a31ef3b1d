# review round, one call on one chip, everything from committed files alone
# (git archive of the index; the parent is git archive 9b969bf):
# soak.spaces with the mix as it stands (hop_every_s 16, drawn instants): three
# untraced runs, one traced, one planted fault (--plant stay) at the cell's own
# size; then tile.roam parent, change, change, parent and one traced change
set -e
for side in parent final; do
  rm -rf _archive/$side && mkdir -p _archive/$side
  tar -x -f _archive/$side.tar -C _archive/$side
done
run() {  # side label workload seeds traces [more]
  ( cd _archive/$1 && export HOME=$PWD/.home TMPDIR=$PWD/.tmp && mkdir -p $HOME $TMPDIR \
    && python benchmark/tools/series.py --label "$2" --workload "$3" --seeds "$4" --seconds 40 --trace "$5" $6 || true
    mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/ )
}
run final c8 soak.spaces 2147520011,2147520012,2147520013,2147520014 0,0,1,0 "--cell-file benchmark/cells/soak.spaces.json"
( cd _archive/final && export HOME=$PWD/.home TMPDIR=$PWD/.tmp \
  && python benchmark/run.py --cell-file benchmark/cells/soak.spaces.json --workload soak.spaces --seed 2147520021 --seconds 20 --trace 0 --plant stay > ../../chiprun_out/c8_plant_stay.log 2>&1 || true
  grep -a "^\[run\] clients\|^check\|^correct\|window closed" ../../chiprun_out/c8_plant_stay.log | cut -c1-700 )
run parent t8_parent tile.roam 2147520101 0
run final t8_change tile.roam 2147520101 0
run final t8_change tile.roam 2147520102 0
run parent t8_parent tile.roam 2147520102 0
run final t8_change_traced tile.roam 2147520103 1
