python benchmark/tools/series.py --label c5 --workload soak.spaces --seeds 2147510051,2147510052,2147510053,2147510054 --seconds 40 --trace 1,0,1,0
