"""The chip benchmark: cells named in ``BENCHMARK.json``, run one at a time
by ``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``."""
