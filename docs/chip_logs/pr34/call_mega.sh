# PR 34, four chips, everything from committed files alone (as
# call_tile.sh): mega2x2.roam parent and change on one seed, one traced
# change, then untraced change runs on seeds of their own, one at a time,
# as many of MORE as begin within LIMIT seconds of the call's start
set -e
T0=$(date +%s); LIMIT=${LIMIT:-900}
for side in parent final; do
  rm -rf _archive/$side && mkdir -p _archive/$side
  tar -x -f _archive/$side.tar -C _archive/$side
done
tar -x -f _archive/overlay.tar -C _archive/parent
run() {  # side label workload seeds traces
  ( cd _archive/$1 && export HOME=$PWD/.home TMPDIR=$PWD/.tmp && mkdir -p $HOME $TMPDIR \
    && python benchmark/tools/series.py --label "$2" --workload "$3" --seeds "$4" --seconds 40 --trace "$5" || true
    mkdir -p ../../chiprun_out && cp -r chiprun_out/. ../../chiprun_out/ )
}
run parent m34_parent mega2x2.roam 2147534201 0
run final m34_change mega2x2.roam 2147534201 0
run final m34_traced mega2x2.roam 2147534202 1
for seed in ${MORE:-2147534203 2147534204}; do
  if [ $(( $(date +%s) - T0 )) -gt $LIMIT ]; then echo "skipped seed $seed: $(( $(date +%s) - T0 )) s gone"; continue; fi
  run final m34_more_$seed mega2x2.roam $seed 0
done
echo "call took $(( $(date +%s) - T0 )) s"
