# review round, second call: call 8's soak.spaces runs never got ready (bots.py
# asked that ALL pairs mirror each other at a sent position at ONE instant, which
# 32 EnterSpace a second never allow; the readiness is latched per client now).
# soak.spaces from the committed files alone: three untraced runs, one traced,
# one planted fault (--plant stay) at the cell's own size
set -e
rm -rf _archive/final && mkdir -p _archive/final
tar -x -f _archive/final.tar -C _archive/final
cd _archive/final
export HOME=$PWD/.home TMPDIR=$PWD/.tmp
mkdir -p $HOME $TMPDIR chiprun_out ../../chiprun_out
python benchmark/tools/series.py --label c9 --workload soak.spaces --cell-file benchmark/cells/soak.spaces.json --seeds 2147520031,2147520032,2147520033,2147520034 --seconds 40 --trace 0,0,1,0 || true
cp -r chiprun_out/. ../../chiprun_out/
python benchmark/run.py --cell-file benchmark/cells/soak.spaces.json --workload soak.spaces --seed 2147520041 --seconds 20 --trace 0 --plant stay > ../../chiprun_out/c9_plant_stay.log 2>&1 || true
grep -a "^\[run\] clients\|^check\|^correct\|window closed\|^\[bots\]" ../../chiprun_out/c9_plant_stay.log | cut -c1-700
