# one traced run of mega2x2.roam from the committed files alone (four chips)
set -e
rm -rf _archive/final && mkdir -p _archive/final
tar -x -f _archive/final.tar -C _archive/final
cd _archive/final
export HOME=$PWD/.home TMPDIR=$PWD/.tmp
mkdir -p $HOME $TMPDIR
python benchmark/tools/series.py --label mega_traced --workload mega2x2.roam --seeds 2147510201 --seconds 40 --trace 1 || true
mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/
