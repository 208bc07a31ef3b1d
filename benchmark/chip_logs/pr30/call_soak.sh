# PR 30, one chip, from the committed files alone: soak.spaces (the cell
# file; not admitted, no claim) once under the new serve loop, untraced
set -e
rm -rf _archive/final && mkdir -p _archive/final
tar -x -f _archive/final.tar -C _archive/final
cd _archive/final
export HOME=$PWD/.home TMPDIR=$PWD/.tmp
mkdir -p $HOME $TMPDIR chiprun_out ../../chiprun_out
python benchmark/tools/series.py --label s30 --workload soak.spaces --cell-file benchmark/cells/soak.spaces.json --seeds 2147530301 --seconds 40 --trace 0 || true
cp -r chiprun_out/. ../../chiprun_out/
