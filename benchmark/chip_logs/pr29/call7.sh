python benchmark/tools/series.py --label c7 --workload soak.spaces --cell-file benchmark/cells/soak.spaces.json --seeds 2147510071,2147510072,2147510073,2147510074 --seconds 40 --trace 0,0,0,1
