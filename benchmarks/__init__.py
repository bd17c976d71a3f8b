"""The benchmark of petastorm-tpu: see run.py and PERF.md."""
