python benchmark/tools/series.py --label c6 --workload soak.spaces --seeds 2147510061,2147510062,2147510063 --seconds 40 --trace 0
