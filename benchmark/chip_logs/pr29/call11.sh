# review round, four chips, from the committed files alone: one traced run of
# mega2x2.roam under the harness as it stands (capture in mid-window again)
set -e
rm -rf _archive/final && mkdir -p _archive/final
tar -x -f _archive/final.tar -C _archive/final
cd _archive/final
export HOME=$PWD/.home TMPDIR=$PWD/.tmp
mkdir -p $HOME $TMPDIR chiprun_out ../../chiprun_out
python benchmark/tools/series.py --label m11 --workload mega2x2.roam --seeds 2147520201 --seconds 40 --trace 1 || true
cp -r chiprun_out/. ../../chiprun_out/
