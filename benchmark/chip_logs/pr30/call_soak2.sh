# PR 30, one chip, from the committed files alone: soak.spaces (the cell file;
# not admitted, no claim) change, parent, change, untraced: the first run
# (call_soak.sh) met a 4.2 s frame with no compile in it and a generator 3.7 s
# late, and read not `correct` (hop_unanswered 34): is it the loop or the host?
set -e
for side in parent final; do
  rm -rf _archive/$side && mkdir -p _archive/$side
  tar -x -f _archive/$side.tar -C _archive/$side
done
run() {  # side label seeds
  ( cd _archive/$1 && export HOME=$PWD/.home TMPDIR=$PWD/.tmp && mkdir -p $HOME $TMPDIR \
    && python benchmark/tools/series.py --label "$2" --workload soak.spaces --cell-file benchmark/cells/soak.spaces.json --seeds "$3" --seconds 40 --trace 0 || true
    mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/ )
}
run final s30b_change_a 2147530302
run parent s30b_parent 2147530302
run final s30b_change_b 2147530303
