"""The chip benchmark of the neural-graphics program: ``bench/run.py``
runs one cell of ``BENCHMARK.json`` once."""
