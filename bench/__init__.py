"""Benchmark of split-LM serving and COMtune fine-tuning on the chip: run
with ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the checkout root (see ``BENCHMARK.json``)."""
