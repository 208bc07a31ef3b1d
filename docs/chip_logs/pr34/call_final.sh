# PR 34, one chip, from committed files alone, the tree as committed (after
# the waiter lets go of a landed tick's output planes): tile.roam once, and
# soak.spaces (cell file, no claim) parent and change on one machine
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
run final t34_final tile.roam 2147534108 0
run parent s34_parent soak.spaces 2147534302 0 "--cell-file benchmark/cells/soak.spaces.json"
run final s34_final soak.spaces 2147534302 0 "--cell-file benchmark/cells/soak.spaces.json"
echo "call took $(( $(date +%s) - T0 )) s"
