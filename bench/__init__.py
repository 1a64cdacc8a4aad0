"""The chip benchmark: one command runs one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that defines a cell is data found by name: configurations in
``bench/configs/``, traffic mixes in ``bench/traffic/`` (each names its
driver in ``bench/drivers/``), and one reader per per-layer metric in
``bench/layer_metrics/``. The yardstick (traffic generation, trace
reduction, peak table, operation and byte counts, plain references and
the comparison that decides ``correct``) lives here and imports nothing
of the program.
"""
