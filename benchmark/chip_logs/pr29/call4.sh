python benchmark/tools/series.py --label c4 --workload soak.spaces --seeds 2147510041,2147510042 --seconds 40 --trace 1 --capture-s 3.3,2.2
