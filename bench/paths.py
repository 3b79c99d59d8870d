"""Where the benchmark's files live: the checkout root and ``bench/``."""
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
