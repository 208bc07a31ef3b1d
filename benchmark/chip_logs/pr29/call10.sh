# review round, third call, one chip, from the committed files alone (the tree
# with the first-run rule of the frame cut and the latched readiness):
# soak.spaces three untraced runs and one traced; tile.roam one untraced, one traced
set -e
rm -rf _archive/final && mkdir -p _archive/final
tar -x -f _archive/final.tar -C _archive/final
cd _archive/final
export HOME=$PWD/.home TMPDIR=$PWD/.tmp
mkdir -p $HOME $TMPDIR chiprun_out ../../chiprun_out
python benchmark/tools/series.py --label c10 --workload soak.spaces --cell-file benchmark/cells/soak.spaces.json --seeds 2147520051,2147520052,2147520053,2147520054 --seconds 40 --trace 1,0,0,0 || true
cp -r chiprun_out/. ../../chiprun_out/
python benchmark/tools/series.py --label t10 --workload tile.roam --seeds 2147520111,2147520112 --seconds 40 --trace 0,1 || true
cp -r chiprun_out/. ../../chiprun_out/
