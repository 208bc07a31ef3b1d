python benchmark/tools/series.py --label c3 --workload soak.spaces --seeds 2147510021,2147510022,2147510023 --seconds 40 --trace 1,0,0
python benchmark/tools/series.py --label c3ctl --workload soak.spaces --seeds 2147510031,2147510032,2147510033 --seconds 20 --trace 0 --control-faults "drop:gate->dispatcher:mt=14:0.8"
